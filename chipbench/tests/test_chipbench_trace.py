"""The trace reduction on a synthetic trace whose answers are known."""
import pytest

import trace_reduce as tr

MS = 1_000_000


def test_busy_idle_programs_kernels_and_gaps():
    dev = "/device:TPU:0"
    ev = dict(
        ops=[["fusion", 0 * MS, 2 * MS, dev],
             ["_decode_impl [kernel]", 1 * MS, 2 * MS, dev],  # overlap
             ["_copy [kernel]", 6 * MS, 1 * MS, dev],
             ["fusion", 20 * MS, 5 * MS, dev]],               # outside
        modules=[["jit__decode_impl(7)", 0, 3 * MS, dev],
                 ["jit__copy(2)", 6 * MS, 1 * MS, dev]],
        spans=[[tr.WINDOW_SPAN, 0, 10 * MS, "main"],
               ["EngineCore.step", 2 * MS, 6 * MS, "driver"],
               ["PagedKVStore.run_d2h", 3 * MS, 2 * MS, "driver"]])
    out = tr.reduce(ev)
    assert out["window_s"] == pytest.approx(0.010)
    assert out["busy_s"] == pytest.approx(0.004)          # [0,3] + [6,7]
    assert out["programs"]["decode"] == dict(seconds=pytest.approx(0.003),
                                             calls=1)
    assert out["programs"]["kv_copy"]["calls"] == 1
    assert out["kernels"] == {"paged_attention": pytest.approx(0.002),
                              "kv_copy": pytest.approx(0.001)}
    idle = dict(out["idle_gaps"])
    # gap [3,6] sits in the D2H span, gap [7,10] in no span
    assert idle == {"PagedKVStore.run_d2h": pytest.approx(0.003),
                    "other": pytest.approx(0.003)}
    assert out["device_ops"][0] == ["fusion", pytest.approx(0.002)]


def test_op_names_as_the_v5e_trace_gives_them():
    kernel = ('%_decode_impl.7 = bf16[64,8,5,128]{3,2,1,0} custom-call('
              's32[64,512]{1,0} %copy-done.25), custom_call_target='
              '"tpu_custom_call", operand_layout_constraints={}')
    assert tr.op_name(kernel) == "_decode_impl [kernel]"
    assert tr.op_name("%fusion.123 = bf16[8]{0} fusion(%p)") == "fusion"
    assert tr.op_name("%copy-start.1 = (bf16[2]) copy-start(%x)") == \
        "copy-start"


def test_recorded_v5e_slice_against_a_brute_force_timeline():
    """A 0.4 s slice of a trace recorded on one TPU v5e in the rotate cell
    (decode, prefill, KV copy and upload programs, with host spans): the
    reduction's busy time, kernel time and idle attribution agree with a
    1-microsecond boolean timeline computed here."""
    import gzip
    import json

    import numpy as np
    from common import BENCH_DIR
    with gzip.open(BENCH_DIR / "tests" / "data" /
                   "v5e_rotate_slice.json.gz", "rt") as f:
        ev = json.load(f)
    out = tr.reduce(ev)
    lo, dur = next((s[1], s[2]) for s in ev["spans"]
                   if s[0] == tr.WINDOW_SPAN)
    us = int(dur // 1000)
    busy = np.zeros(us, bool)
    for _, s, d, _dev in ev["ops"]:
        a, b = max(int((s - lo) // 1000), 0), min(int((s + d - lo) // 1000), us)
        busy[a:b] = True
    assert out["busy_s"] == pytest.approx(busy.sum() * 1e-6, rel=2e-3)
    assert out["window_s"] == pytest.approx(dur * 1e-9)
    pa = sum(d for n, s, d, _ in ev["ops"] if n == "_decode_impl [kernel]")
    assert out["kernels"]["paged_attention"] == pytest.approx(pa * 1e-9)
    assert out["programs"]["decode"]["calls"] == 2
    assert set(out["programs"]) == {"decode", "prefill", "kv_copy",
                                    "kv_upload"}
    idle = sum(v for _, v in out["idle_gaps"])
    assert idle == pytest.approx(out["window_s"] - out["busy_s"], rel=1e-6)
    # the host-tier upload path holds the chip idle longest in this slice
    assert out["idle_gaps"][0][0] == "PagedKVStore.run_h2d"
