"""The readers of the program's host spans, on synthetic counter snapshots
whose answers are known, and the idle-gap attribution once program spans
sit inside the run-time wrappers."""
import pytest

import span_metrics as sm
import trace_reduce as tr

MS = 1_000_000


def snap(iterations, qw_count, qw_ns, **spans_ns):
    """A counter snapshot as the child takes it, with ``host`` as
    ``TelemetryBus.host_counters()`` gives it."""
    spans = {"superinfer." + k.replace("__", "."): dict(
        calls=1, total_ns=v, self_ns=v) for k, v in spans_ns.items()}
    return dict(iterations=iterations, host=dict(
        clock="host", spans=spans,
        queue_wait=dict(count=qw_count, total_ns=qw_ns, le_s=[0.1],
                        buckets=[qw_count, 0])))


def ctx(c0, c1):
    return dict(c0=c0, c1=c1, window_s=1.0, trace=None)


def test_readers_on_a_window():
    c0 = snap(10, 4, 40 * MS, engine__step=100 * MS,
              duplexkv__plan=10 * MS, runner__execute=60 * MS,
              runner__sync=40 * MS, kvstore__d2h_readback=5 * MS)
    c1 = snap(30, 9, 90 * MS, engine__step=700 * MS,
              duplexkv__plan=50 * MS, runner__execute=460 * MS,
              runner__sync=300 * MS, kvstore__d2h_readback=45 * MS)
    c = ctx(c0, c1)
    assert sm.queue_wait_ms(c) == pytest.approx(10.0)       # 50 ms / 5
    # (600 - 40 - 400) ms over 20 iterations
    assert sm.engine_host_ms(c) == pytest.approx(8.0)
    assert sm.d2h_wait_ms(c) == pytest.approx(2.0)          # 40 ms / 20
    assert sm.runner_host_ms(c) == pytest.approx(7.0)       # (400-260)/20


def test_a_span_that_never_ran_reads_zero():
    c0 = snap(0, 0, 0, engine__step=0, runner__execute=0)
    c1 = snap(5, 0, 0, engine__step=50 * MS, runner__execute=30 * MS)
    c = ctx(c0, c1)
    assert sm.d2h_wait_ms(c) == 0.0
    assert sm.engine_host_ms(c) == pytest.approx(4.0)
    assert sm.queue_wait_ms(c) is None                      # none admitted


@pytest.mark.parametrize("reader", [sm.queue_wait_ms, sm.engine_host_ms,
                                    sm.d2h_wait_ms, sm.runner_host_ms])
def test_readers_give_none_without_host_counters(reader):
    # a program without the spans (the parent commit) or its recorder off
    bare = ctx(dict(iterations=1), dict(iterations=9))
    off = ctx(dict(iterations=1, host=None), dict(iterations=9, host=None))
    assert reader(bare) is None and reader(off) is None
    # nor with no iteration in the window
    idle = ctx(snap(3, 0, 0), snap(3, 0, 0))
    if reader is not sm.queue_wait_ms:
        assert reader(idle) is None


def test_idle_gap_goes_to_the_innermost_program_span():
    """With program spans inside the run-time ``EngineCore.step`` wrapper,
    a gap that the wrapper alone would take goes to the innermost
    ``superinfer.`` span open at its midpoint."""
    dev = "/device:TPU:0"
    ev = dict(
        ops=[["fusion", 0, 2 * MS, dev], ["fusion", 6 * MS, 2 * MS, dev]],
        modules=[],
        spans=[[tr.WINDOW_SPAN, 0, 10 * MS, "main"],
               ["EngineCore.step", 1 * MS, 9 * MS, "driver"],
               ["superinfer.engine.step", 1 * MS, 7 * MS, "driver"],
               ["superinfer.engine.schedule", 2 * MS, 1 * MS, "driver"],
               ["superinfer.runner.execute", 3 * MS, 4 * MS, "driver"],
               ["superinfer.runner.prepare", 3 * MS, 2 * MS, "driver"]])
    idle = dict(tr.reduce(ev)["idle_gaps"])
    # gap [2,6]: midpoint 4 ms, inside prepare; gap [8,10]: midpoint 9 ms,
    # after the program's step closed, so the wrapper takes it
    assert idle == {"superinfer.runner.prepare": pytest.approx(0.004),
                    "EngineCore.step": pytest.approx(0.002)}
