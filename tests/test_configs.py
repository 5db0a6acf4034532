"""Config registry: published sizes, shape applicability, reduced configs."""
import pytest

from repro.configs import (ARCH_IDS, PAPER_MODEL_IDS, SHAPES, get_config,
                           shape_applicable)

PUBLISHED_B = {
    "jamba-1.5-large-398b": (340, 400),   # MoE total (ff assumption: ±)
    "llama3-405b": (400, 412),
    "yi-34b": (33, 36),
    "mistral-large-123b": (118, 126),
    "gemma3-1b": (0.9, 1.1),
    "paligemma-3b": (2.3, 2.7),           # text backbone (SigLIP is a stub)
    "dbrx-132b": (126, 136),
    "qwen3-moe-30b-a3b": (29, 32),
    "mamba2-2.7b": (2.6, 2.8),
    "seamless-m4t-medium": (0.8, 1.2),
    "llama3-8b": (7.8, 8.3),
    "qwen2.5-32b": (31, 34),
    "mixtral-8x7b": (45, 48),
}

ACTIVE_B = {
    "qwen3-moe-30b-a3b": (2.8, 3.8),
    "dbrx-132b": (34, 38),
    "mixtral-8x7b": (12, 14),
}


@pytest.mark.parametrize("arch", list(ARCH_IDS) + list(PAPER_MODEL_IDS))
def test_param_counts_match_published(arch):
    cfg = get_config(arch)
    lo, hi = PUBLISHED_B[arch]
    n = cfg.param_count() / 1e9
    assert lo <= n <= hi, f"{arch}: {n:.2f}B outside [{lo},{hi}]"


@pytest.mark.parametrize("arch", list(ACTIVE_B))
def test_active_params(arch):
    cfg = get_config(arch)
    lo, hi = ACTIVE_B[arch]
    n = cfg.active_param_count() / 1e9
    assert lo <= n <= hi


def test_long_context_applicability():
    long = SHAPES["long_500k"]
    runnable = [a for a in ARCH_IDS if shape_applicable(get_config(a), long)[0]]
    assert set(runnable) == {"mamba2-2.7b", "jamba-1.5-large-398b", "gemma3-1b"}


@pytest.mark.parametrize("arch", ARCH_IDS)
def test_reduced_configs_are_small(arch):
    red = get_config(arch).reduced()
    assert red.param_count() < 20e6
    assert red.family == get_config(arch).family


def test_hybrid_structure():
    cfg = get_config("jamba-1.5-large-398b")
    assert cfg.num_attn_layers == 9 and cfg.num_ssm_layers == 63
    assert cfg.layer_kind(4) == "attn" and cfg.layer_kind(0) == "ssm"
    assert cfg.layer_is_moe(1) and not cfg.layer_is_moe(0)


def test_gemma3_local_global():
    cfg = get_config("gemma3-1b")
    globals_ = [i for i in range(cfg.num_layers) if cfg.layer_is_global(i)]
    assert globals_ == [5, 11, 17, 23]


@pytest.mark.parametrize("arch,layers", [("qwen2.5-32b", 6), ("yi-34b", 1)])
def test_runner_config_cuts_depth_only(arch, layers):
    """runner_layers keeps every published width and the model's own dtype,
    cuts depth, and the pool's rows (hence --hbm-budget-gb sizing) are the
    executed model's rows, not the full-depth timing model's."""
    import dataclasses
    import jax.numpy as jnp
    from repro.configs import ServingConfig, runner_config
    from repro.core.duplexkv import block_bytes_of, hbm_block_capacity
    from repro.serving.paged_runner import PagedKVStore
    full = get_config(arch)
    cut = runner_config(full, layers)
    assert cut.num_layers == layers and cut.dtype == full.dtype == "bfloat16"
    assert dataclasses.replace(cut, num_layers=full.num_layers) == full
    bb, _ = block_bytes_of(cut, 16)
    assert bb == 16 * 2 * cut.num_kv_heads * cut.head_dim * 2 * layers
    assert bb * full.num_layers == block_bytes_of(full, 16)[0] * layers
    assert hbm_block_capacity(cut, 16, 4 << 30) == (4 << 30) // bb
    store = PagedKVStore(cut, ServingConfig(num_hbm_blocks=2), jnp.bfloat16,
                         staging=1)
    row = store.pool[0]
    assert row.nbytes == bb and row.shape == (layers, 2, 16, cut.num_kv_heads,
                                              cut.head_dim)


def test_runner_config_default_is_reduced_float32():
    from repro.configs import runner_config
    cfg = get_config("qwen2.5-32b")
    red = runner_config(cfg)
    assert red.dtype == "float32" and red.d_model == cfg.reduced().d_model
    with pytest.raises(ValueError):
        runner_config(cfg, -1)
    with pytest.raises(ValueError):
        runner_config(cfg, cfg.num_layers + 1)
