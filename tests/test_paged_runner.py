"""PagedModelRunner: token parity with the legacy dense RealExecutor (with
and without rotation, and with the prefix cache ON — the combination the
dense executor cannot run), physical row movement through the PagedKVStore,
batched-decode launch accounting, and the RealExecutor mid-prefill swap
contract."""
import dataclasses

import numpy as np
import pytest

from repro.configs import GH200, ServingConfig, get_config
from repro.core.blocktable import BlockLoc
from repro.core.types import Request
from repro.serving.engine import ServingEngine
from repro.serving.executor import (ExecutionResult, RealExecutor,
                                    RealExecutorAdapter, SimExecutor)
from repro.serving.paged_runner import PagedKVStore, PagedModelRunner

CFG = dataclasses.replace(get_config("llama3-8b").reduced(), dtype="float32")
SEED = 42


def make_requests(n, seed=3, shared_prefix=0, out_hi=16):
    rng = np.random.default_rng(seed)
    pref = ([int(x) for x in rng.integers(1, CFG.vocab_size, shared_prefix)]
            if shared_prefix else [])
    reqs = []
    for i in range(n):
        plen = int(rng.integers(8, 16))
        ids = pref + [int(x) for x in rng.integers(1, CFG.vocab_size, plen)]
        reqs.append(Request(req_id=i, arrival_time=0.02 * i,
                            prompt_len=len(ids),
                            output_len=int(rng.integers(10, out_hi)),
                            prompt_ids=ids))
    return reqs


def serving(hbm, prefix_cache=False, paged=False):
    return ServingConfig(num_hbm_blocks=hbm, num_dram_blocks=512,
                         scheduler="rotasched", block_size=4,
                         max_model_len=64, prefill_chunk=8,
                         paged_runner=paged, prefix_cache=prefix_cache)


def run_engine(kind, hbm, prefix_cache=False, shared_prefix=0):
    sv = serving(hbm, prefix_cache=prefix_cache, paged=(kind == "paged"))
    real = RealExecutor(CFG, seed=SEED) if kind == "legacy" else None
    eng = ServingEngine(CFG, sv, GH200, real_executor=real,
                        runner_cfg=CFG, runner_seed=SEED)
    for r in make_requests(5, shared_prefix=shared_prefix):
        eng.add_request(r)
    eng.drain(max_time_s=500)
    eng.kv.table.check_invariants()
    streams = {r.req_id: list(r.generated_ids) for r in eng.core.submitted}
    return streams, eng


@pytest.fixture(scope="module")
def legacy_streams():
    """Reference token streams: dense RealExecutor, ample memory (prefix
    cache is forced off under it — the dense caches cannot share)."""
    plain, _ = run_engine("legacy", 4096)
    shared, _ = run_engine("legacy", 4096, shared_prefix=12)
    return {"plain": plain, "shared": shared}


# ------------------------------------------------------------ token parity

def test_paged_matches_legacy_no_rotation(legacy_streams):
    streams, eng = run_engine("paged", 4096)
    assert eng.stats.active_rotations + eng.stats.passive_preemptions == 0
    assert streams == legacy_streams["plain"]


def test_paged_matches_legacy_under_rotation(legacy_streams):
    """Tight HBM forces real rotations: pool rows physically round-trip
    through the host tier and the token streams must not change."""
    streams, eng = run_engine("paged", 16)
    rot = eng.stats.active_rotations + eng.stats.passive_preemptions
    assert rot > 0
    store = eng.core.executor.store
    assert store.d2h_rows > 0 and store.h2d_rows > 0
    assert store.copy_launches > 0            # batched kv_copy staging path
    assert streams == legacy_streams["plain"]


def test_paged_prefix_cache_parity_and_hits(legacy_streams):
    """The newly unlocked combination: prefix cache + real execution.
    Cache-hit blocks are shared pool rows, so prefill work drops while the
    token streams stay identical to the cache-less dense reference."""
    streams, eng = run_engine("paged", 4096, prefix_cache=True,
                              shared_prefix=12)
    assert eng.kv.table.cache_hit_tokens > 0
    assert streams == legacy_streams["shared"]


def test_paged_prefix_cache_with_rotation(legacy_streams):
    streams, eng = run_engine("paged", 16, prefix_cache=True,
                              shared_prefix=12)
    rot = eng.stats.active_rotations + eng.stats.passive_preemptions
    assert rot > 0
    assert eng.kv.table.cache_hit_tokens > 0
    assert streams == legacy_streams["shared"]


@pytest.fixture(scope="module")
def batched_decode_run():
    """Five requests that arrive together and decode as one batch."""
    sv = serving(4096, paged=True)
    eng = ServingEngine(CFG, sv, GH200, runner_cfg=CFG, runner_seed=SEED)
    for r in make_requests(5, seed=9):
        r.arrival_time = 0.0               # all decode together
        eng.add_request(r)
    eng.drain(max_time_s=500)
    return eng


def test_decode_is_single_batched_launch(batched_decode_run):
    """N concurrent decodes must execute as one batched kernel invocation
    per layer per iteration — launch count scales with iterations, never
    with batch size (the legacy path pays N model calls per iteration)."""
    ex = batched_decode_run.core.executor
    assert ex.decode_tokens > ex.decode_batches        # real batching
    assert ex.attn_launches == ex.decode_batches * len(ex._layers)


def test_attention_block_counters(batched_decode_run):
    """attn_blocks_live counts the blocks the real contexts occupy, layer by
    layer: a request that generated G tokens ran G - 1 decode steps, at
    contexts prompt_len + 1 .. prompt_len + G - 1. The padded grid the old
    kernel walked (attn_block_slots) is never smaller."""
    eng = batched_decode_run
    ex = eng.core.executor
    P = ex.serving.block_size
    live = sum(-(-ctx // P)
               for r in eng.core.submitted
               for ctx in range(r.prompt_len + 1,
                                r.prompt_len + len(r.generated_ids)))
    assert live > 0
    assert ex.attn_blocks_live == live * len(ex._layers)
    assert ex.attn_blocks_live <= ex.attn_block_slots


def test_flag_off_keeps_sim_executor():
    eng = ServingEngine(CFG, serving(4096, paged=False), GH200)
    assert type(eng.core.executor) is SimExecutor
    assert eng.core.executor.execute(None, {}).tokens == {}


# --------------------------------------------------- physical store unit

def test_paged_kv_store_roundtrip():
    """Rows survive device -> host -> device movement bit-exactly, and CoW
    D2D copies duplicate rows inside the pool."""
    import jax.numpy as jnp
    sv = serving(8)
    store = PagedKVStore(CFG, sv, jnp.float32, staging=4)
    rng = np.random.default_rng(0)
    rows = rng.standard_normal((3,) + store.row_shape).astype(np.float32)
    pool = np.array(store.pool)          # writable copy
    pool[:3] = rows
    store.pool = jnp.asarray(pool)

    @dataclasses.dataclass
    class Desc:
        block_id: int
        src_slot: int
        dst_slot: int

    store.run_d2h([Desc(0, 0, 10), Desc(1, 1, 11), Desc(2, 2, 12)])
    assert set(store.host) == {10, 11, 12}
    np.testing.assert_array_equal(store.host[11], rows[1])
    # scatter them back to different device rows
    store.run_h2d([Desc(0, 10, 5), Desc(1, 11, 6), Desc(2, 12, 7)])
    np.testing.assert_array_equal(np.asarray(store.pool[5]), rows[0])
    np.testing.assert_array_equal(np.asarray(store.pool[7]), rows[2])
    store.run_d2d([(5, 4)])
    np.testing.assert_array_equal(np.asarray(store.pool[4]), rows[0])
    with pytest.raises(RuntimeError):
        store.run_h2d([Desc(9, 99, 0)])    # no such host copy: data loss
    assert store.copy_launches >= 3


def test_runner_rejects_non_attention_configs():
    ssm_cfg = dataclasses.replace(get_config("mamba2-2.7b").reduced(),
                                  dtype="float32")
    with pytest.raises(ValueError):
        PagedModelRunner(ssm_cfg, serving(16), GH200, seed=0)


# ------------------------------------------- RealExecutor swap contract

def test_real_executor_mid_prefill_swap_roundtrip():
    """A request rotated out before its prefill ran has no cache; the swap
    cycle must be explicit about that state and resume cleanly: prefill
    after the round-trip yields the same token as an undisturbed run."""
    ex1 = RealExecutor(CFG, seed=7)
    ex2 = RealExecutor(CFG, seed=7)
    prompt = list(range(1, 9))
    t_plain = ex1.prefill(1, prompt, 32)
    ex2.swap_out(1)                 # mid-prefill: no cache yet — legal
    ex2.swap_in(1)
    assert ex2.prefill(1, prompt, 32) == t_plain
    assert ex2.decode(1, t_plain, len(prompt)) == ex1.decode(1, t_plain,
                                                            len(prompt))


def test_real_executor_lost_cache_is_loud():
    """The dense-cache leak surface: a token-bearing request whose cache
    vanished must fail loudly on swap_out/swap_in/decode, not resume with
    no KV."""
    ex = RealExecutor(CFG, seed=7)
    ex.prefill(1, list(range(1, 9)), 32)
    ex._caches.pop(1)               # simulate the lost-cache state
    with pytest.raises(RuntimeError, match="lost"):
        ex.swap_out(1)
    with pytest.raises(RuntimeError, match="without a KV"):
        ex.swap_in(1)
    with pytest.raises(RuntimeError, match="no device cache"):
        ex.decode(1, 3, 8)


def test_adapter_forwards_lifecycle_and_skips_idless_requests():
    class FakeReal:
        def __init__(self):
            self.dropped = []

        def prefill(self, rid, toks, capacity):
            return 5

        def decode(self, rid, tok, cl):
            return 6

        def swap_out(self, rid):
            pass

        def swap_in(self, rid):
            pass

        def drop(self, rid):
            self.dropped.append(rid)

    fake = FakeReal()
    ad = RealExecutorAdapter(fake, SimExecutor(CFG, GH200))
    assert not ad.supports_prefix_cache
    ad.drop(3)
    assert fake.dropped == [3]
    from repro.serving.executor import BatchPlan
    r = Request(req_id=0, arrival_time=0.0, prompt_len=4, output_len=2)
    plan = BatchPlan(prefill_chunks=[(0, 4)], prefill_tokens=4)
    out = ad.execute(plan, {0: r})
    assert isinstance(out, ExecutionResult)
    assert out.tokens == {}         # no prompt_ids -> oracle mode, no token
