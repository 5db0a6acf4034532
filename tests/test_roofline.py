"""Roofline extraction: HLO collective parser + term arithmetic + the
extrapolation identity (cost_analysis undercounts scan bodies; the shallow
unrolled variants must agree with a fully-unrolled deep compile)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, TPU_V5E, get_config
from repro.launch import roofline

HLO_SAMPLE = """
HloModule test
ENTRY main {
  %p0 = f32[16,128]{1,0} parameter(0)
  %ar = f32[16,128]{1,0} all-reduce(%p0), replica_groups={}
  %ag = bf16[32,128]{1,0} all-gather(%p0), dimensions={0}
  %rs = f32[8,128]{1,0} reduce-scatter(%ar), dimensions={0}
  %cp = s32[4]{0} collective-permute(%p0)
  %a2a = bf16[16,64]{1,0} all-to-all(%ag), dimensions={0}
  %ard = f32[1]{0} all-reduce-done(%ar)
}
"""


def test_collective_parser():
    got = roofline.collective_bytes(HLO_SAMPLE)
    assert got["all-reduce"] == 16 * 128 * 4
    assert got["all-gather"] == 32 * 128 * 2
    assert got["reduce-scatter"] == 8 * 128 * 4
    assert got["collective-permute"] == 4 * 4
    assert got["all-to-all"] == 16 * 64 * 2
    assert got["all-reduce_count"] == 1   # -done line not double counted


def test_roofline_terms_bottleneck():
    t = roofline.roofline_terms(197e12, 819e9 / 2, 0, TPU_V5E)
    assert t["bottleneck"] == "compute"
    t2 = roofline.roofline_terms(1e12, 819e9 * 2, 0, TPU_V5E)
    assert t2["bottleneck"] == "memory"
    t3 = roofline.roofline_terms(1e12, 1e9, 50e9 * 3, TPU_V5E)
    assert t3["bottleneck"] == "collective"


def test_model_flops_scaling():
    cfg = get_config("llama3-8b")
    tr = roofline.model_flops(cfg, SHAPES["train_4k"])
    # 6*N*D within 30% (attention adds on top)
    six_nd = 6 * cfg.param_count() * SHAPES["train_4k"].global_batch \
        * SHAPES["train_4k"].seq_len
    assert six_nd * 0.9 <= tr <= six_nd * 1.6
    de = roofline.model_flops(cfg, SHAPES["decode_32k"])
    assert de < tr / 1000


def test_moe_uses_active_params():
    dense_like = get_config("yi-34b")
    moe = get_config("qwen3-moe-30b-a3b")
    f = roofline.model_flops(moe, SHAPES["train_4k"])
    six_nd_active = 6 * moe.active_param_count() * 256 * 4096
    assert f == pytest.approx(six_nd_active, rel=0.5)


def test_extrapolation_identity_small():
    """F(L) from 2-point extrapolation == direct unrolled compile at L=3p."""
    import dataclasses
    from repro.distributed.sharding import sharding_ctx, TRAIN_RULES
    from repro.models.api import make_step_bundle

    base = dataclasses.replace(get_config("yi-34b").reduced(), num_layers=1)
    shape = dataclasses.replace(SHAPES["prefill_32k"], seq_len=64,
                                global_batch=2)

    def flops_at(L):
        cfg = dataclasses.replace(base, num_layers=L)
        b = make_step_bundle(cfg, shape, unroll=True)
        c = jax.jit(b.fn).lower(*b.args_structs).compile().cost_analysis()
        return float(c["flops"])

    f1, f2, f3 = flops_at(1), flops_at(2), flops_at(3)
    extrap = f1 + 2 * (f2 - f1)
    assert extrap == pytest.approx(f3, rel=0.02)


def test_analytic_memory_model_decode():
    cfg = get_config("yi-34b")
    m = roofline.analytic_memory_bytes(
        cfg, SHAPES["decode_32k"], weights_local=1e9, opt_local=0,
        cache_local=4e9, data_shards=16, model_shards=16, fsdp_shards=16)
    assert m["weights"] == 1e9 and m["kv"] == 4e9
    assert m["total"] >= 5e9
