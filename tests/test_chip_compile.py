"""Compile the main path's kernels for a described TPU v5e (no chip needed).

The TPU compiler is installed with jaxlib and compiles for a topology that
is described, not attached. These tests lower the Pallas kernels and the
paged runner's bf16 decode step at qwen2.5-32b's published widths with 6
layers, and check that Mosaic accepted each kernel (``tpu_custom_call`` in
the compiled text): interpret-mode tests cannot see tiling or VMEM limits.

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, and a file that decided at
import whether its tests exist would give pytest-xdist workers different
collections.
"""
import dataclasses

import jax
import jax.numpy as jnp
import pytest

from repro.configs import ServingConfig, get_config, runner_config

LAYERS = 6
NB = 8192                     # pool rows the compiled programs address
B, MB = 8, 256                # decode batch, block-table width (4096 tokens)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to a persistent cache but
    # cannot be read back without one: keep it out of any cache in use
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    try:
        yield topologies.get_topology_desc(platform="tpu",
                                           topology_name="v5e:2x2")
    except Exception as e:          # no TPU compiler in this install
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    finally:
        jax.config.update("jax_enable_compilation_cache", enabled)


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module")
def cfg():
    return runner_config(get_config("qwen2.5-32b"), LAYERS)


def _struct(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _pool_shapes(cfg, one_chip, dtype):
    row = (cfg.num_layers, 2, 16, cfg.num_kv_heads, cfg.head_dim)
    pool = _struct((NB,) + row, dtype, one_chip)
    scales = _struct((NB, cfg.num_layers, 2, cfg.num_kv_heads), jnp.float32,
                     one_chip)
    return pool, scales


def _compiled_text(fn, *args, **jit_kw):
    return jax.jit(fn, **jit_kw).lower(*args).compile().as_text()


@pytest.mark.parametrize("mb", [MB, 2 * MB])     # 4096 and 8192 tokens
@pytest.mark.parametrize("kv_dtype", ["bf16", "int8"])
def test_paged_attention_compiles(cfg, one_chip, kv_dtype, mb):
    from repro.kernels.paged_attention import paged_attention_tpu
    dtype = jnp.int8 if kv_dtype == "int8" else jnp.bfloat16
    pool, scales = _pool_shapes(cfg, one_chip, dtype)
    q = _struct((B, cfg.num_heads, cfg.head_dim), jnp.bfloat16, one_chip)
    bt = _struct((B, mb), jnp.int32, one_chip)
    cl = _struct((B,), jnp.int32, one_chip)

    def attend(q, pool, bt, cl, scales=None):
        return paged_attention_tpu(q, pool, bt, cl, layer=LAYERS - 1,
                                   kv_scales=scales, interpret=False)

    args = (q, pool, bt, cl) + ((scales,) if kv_dtype == "int8" else ())
    assert "tpu_custom_call" in _compiled_text(attend, *args)


@pytest.mark.parametrize("rows", ["pool", "scales"])
def test_kv_copy_compiles(cfg, one_chip, rows):
    from repro.kernels.kv_copy import kv_copy_tpu
    pool, scales = _pool_shapes(cfg, one_chip, jnp.bfloat16)
    target = pool if rows == "pool" else scales
    idx = _struct((64,), jnp.int32, one_chip)
    text = _compiled_text(lambda p, s, d: kv_copy_tpu(p, s, d, interpret=False),
                          target, idx, idx, donate_argnums=(0,))
    assert "tpu_custom_call" in text


def test_decode_step_compiles_and_fits(cfg, one_chip):
    """The runner's bf16 decode step: 6 published-width layers, the whole
    152064-row vocabulary and a 3.2 GB pool in one v5e's 16 GB."""
    from repro.models.blocks import layer_param_defs, make_layer_spec
    from repro.models.common import param_structs
    from repro.models.lm import LM
    from repro.serving.paged_runner import PagedModelRunner

    place = lambda t: jax.tree.map(            # noqa: E731
        lambda s: _struct(s.shape, s.dtype, one_chip), t)
    layers = [place(param_structs(layer_param_defs(cfg, make_layer_spec(cfg, i)),
                                  jnp.bfloat16))
              for i in range(cfg.num_layers)]
    head = {k: v for k, v in place(LM(cfg).param_structs()).items()
            if k in ("embed", "final_norm", "lm_head")}
    pool, _ = _pool_shapes(cfg, one_chip, jnp.bfloat16)
    toks = _struct((B,), jnp.int32, one_chip)
    bt = _struct((B, MB), jnp.int32, one_chip)

    # the runner's constructor would allocate 9 GB of weights on this CPU:
    # give the decode step only the attributes it reads
    runner = PagedModelRunner.__new__(PagedModelRunner)
    runner.cfg = cfg
    runner.serving = dataclasses.replace(ServingConfig(), num_hbm_blocks=NB)
    runner.interpret = False
    runner._psum_attn = runner._psum_mlp = False
    compiled = jax.jit(runner._decode_impl, donate_argnums=(2,)).lower(
        layers, head, pool, toks, bt, toks).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    total = (mem.argument_size_in_bytes + mem.output_size_in_bytes
             - mem.alias_size_in_bytes + mem.temp_size_in_bytes)
    assert total < 16e9, mem
