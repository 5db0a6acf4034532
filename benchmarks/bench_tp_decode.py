"""Tensor-parallel paged decode: parity, per-shard footprint, capacity.

Three claims under test:

  1. Sharding the KV pool + kernels over a ("model",) mesh leaves the
     token streams bit-identical to the single-chip runner (TP in
     {1, 2, 4}) while keeping launch counts invariant — decode is still
     ONE batched paged-attention invocation per layer per iteration.
  2. The per-shard KV-pool footprint (and the per-shard DuplexKV byte
     counters) are exactly 1/TP of the global numbers.
  3. The capacity model: llama3-405b bf16 weights (~756 GiB) cannot fit
     a single GH200 (144 GiB HBM) but fit at TP=8 (~94.5 GiB/chip) with
     HBM left over for a KV block pool.

Needs 4 XLA devices. Run alone, it sets the host-device-count flag itself;
when jax is already up with fewer (e.g. under ``benchmarks.run`` after other
modules imported it), it stops with the recipe: export
``XLA_FLAGS=--xla_force_host_platform_device_count=4`` before the process
starts. It never starts a second process, which could not reach a chip
that this one holds.

    PYTHONPATH=src python -m benchmarks.bench_tp_decode [--quick]

CSV rows: name,seconds,derived.
"""
import dataclasses
import sys
import time

import numpy as np

NEED_DEVICES = 4


def make_requests(cfg, n, out_len, seed=11):
    from repro.core.types import Request
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        plen = int(rng.integers(8, 16))
        reqs.append(Request(
            req_id=i, arrival_time=0.0, prompt_len=plen, output_len=out_len,
            prompt_ids=[int(x) for x in rng.integers(1, cfg.vocab_size,
                                                     plen)]))
    return reqs


def run_engine(cfg, tp, n_req, out_len):
    from repro.configs import GH200, ServingConfig
    from repro.serving.engine import ServingEngine
    sv = ServingConfig(num_hbm_blocks=12, num_dram_blocks=512,
                       scheduler="rotasched", block_size=4, max_model_len=64,
                       prefill_chunk=8, paged_runner=True, tp=tp)
    eng = ServingEngine(cfg, sv, GH200, runner_cfg=cfg, runner_seed=7)
    for r in make_requests(cfg, n_req, out_len):
        eng.add_request(r)
    t0 = time.time()
    eng.drain(max_time_s=500)
    dt = time.time() - t0
    streams = {r.req_id: list(r.generated_ids) for r in eng.core.submitted}
    return eng, dt, streams


def main() -> None:
    from repro.launch.hostenv import ensure_host_devices
    ensure_host_devices(NEED_DEVICES)   # raises with the recipe if too late

    from repro.configs import GH200, get_config
    from repro.core.duplexkv import block_bytes_of
    from repro.distributed.tp import plan_tp_sharding

    quick = "--quick" in sys.argv
    n_req = 4 if quick else 8
    out_len = 6 if quick else 16
    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32", num_heads=8, num_kv_heads=4,
                              head_dim=16)

    print("name,seconds,derived")
    runs = {}
    for tp in (1, 2, 4):
        eng, dt, streams = run_engine(cfg, tp, n_req, out_len)
        runs[tp] = (eng, streams)
        ex = eng.core.executor
        store = ex.store
        toks = sum(r.tokens_generated for r in eng.core.submitted)
        assert store.pool_shard_bytes * tp == store.pool_global_bytes, \
            (tp, store.pool_shard_bytes, store.pool_global_bytes)
        derived = (f"tok/s={toks / dt:.1f} "
                   f"pool_shard_KiB={store.pool_shard_bytes / 1024:.0f} "
                   f"(=global/{tp}) decode_iters={ex.decode_batches} "
                   f"attn_launches={ex.attn_launches}")
        print(f"tp{tp}_decode_{n_req}req,{dt:.2f},{derived}")

    ref_eng, ref_streams = runs[1]
    assert sum(r.rotations for r in ref_eng.core.submitted) > 0, \
        "reference run never rotated — parity check would be too easy"
    ref_ex = ref_eng.core.executor
    for tp in (2, 4):
        eng, streams = runs[tp]
        assert streams == ref_streams, \
            f"tp={tp} changed the token streams vs single-chip"
        ex = eng.core.executor
        # launch-count invariance: sharding fans each launch across the
        # mesh, it does not multiply launches
        assert (ex.decode_batches, ex.attn_launches) == \
            (ref_ex.decode_batches, ref_ex.attn_launches), (tp,)
        ctr = eng.core.kv.transfer_counters()
        assert ctr["kv_shards"] == tp and ctr["d2h_bytes"] > 0
        assert ctr["d2h_bytes_per_shard"] == ctr["d2h_bytes"] // tp
    print(f"# tp 1/2/4 token-identical under rotation; "
          f"{ref_ex.attn_launches} attn launches at every tp")

    # -- capacity model: llama3-405b on GH200 ------------------------------
    big = get_config("llama3-405b")
    wbytes = big.param_count() * 2          # bf16 weights
    bb, _ = block_bytes_of(big, 16)
    t0 = time.time()
    fits = {}
    for tp in (1, 8):
        plan = plan_tp_sharding(big, tp)
        per_chip = wbytes // tp
        fits[tp] = per_chip < GH200.hbm_bytes
        headroom = max(GH200.hbm_bytes - per_chip, 0)
        blocks = headroom * tp // bb if fits[tp] else 0
        derived = (f"weights_per_chip_GiB={per_chip / 2**30:.1f} "
                   f"hbm_GiB={GH200.hbm_bytes / 2**30:.0f} "
                   f"fits={'yes' if fits[tp] else 'NO'} "
                   f"kv_blocks_global={blocks} kv_shards={plan.kv_shards}")
        print(f"llama3-405b_tp{tp},{time.time() - t0:.2f},{derived}")
    assert not fits[1] and fits[8], fits
    print("# llama3-405b: bf16 weights "
          f"{wbytes / 2**30:.0f} GiB need TP=8 on GH200 "
          f"({wbytes / 8 / 2**30:.1f} GiB/chip); TP=1 cannot hold them")


if __name__ == "__main__":
    main()
