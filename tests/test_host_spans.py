"""Host spans and counters of the flight recorder on the real serving path.

  * ``TelemetryBus.span`` counts calls, total and self time per name (a
    parent's self time excludes its children, per thread and per asyncio
    task); without a bus every span is one shared null context;
  * each span also opens a ``jax.profiler.TraceAnnotation``, so a profiler
    session holds the ``superinfer.*`` events nested as the code nests them;
  * the reduced paged runner served through ``EngineCore`` fires every
    engine, runner and KV-store span, stamps each request recv <= admit <=
    first token on the host clock and records its iterations there, while
    its tokens stay those of a run with the recorder off;
  * the host series reach Prometheus, and a real-path ``/v1/trace`` names
    the host clock.
"""
import asyncio
import dataclasses
import json
import threading
import time

import numpy as np
import pytest

from repro.configs import GH200, ServingConfig, get_config
from repro.core.types import SamplingParams
from repro.serving.core import EngineCore
from repro.serving.telemetry import (HOST_SPANS, HS_DRIVER_CONTROL,
                                     HS_DRIVER_DELIVER, HS_DRIVER_WAIT,
                                     HS_HTTP_GENERATE, NULL_SPAN,
                                     QUEUE_WAIT_EDGES_S, TelemetryBus,
                                     host_span, render_prometheus,
                                     validate_prometheus_text)

CFG = dataclasses.replace(get_config("llama3-8b").reduced(), dtype="float32")
# spans only the HTTP front door and the async driver open
FRONT_SPANS = {HS_HTTP_GENERATE, HS_DRIVER_CONTROL, HS_DRIVER_DELIVER,
               HS_DRIVER_WAIT}


def _busy(ns):
    t = time.perf_counter_ns()
    while time.perf_counter_ns() - t < ns:
        pass


# ------------------------------------------------------------ span counters
def test_null_span_without_a_bus_records_nothing():
    assert host_span(None, "superinfer.a") is NULL_SPAN
    assert host_span(None, "superinfer.b") is NULL_SPAN
    with host_span(None, "superinfer.a") as sp:
        assert sp is None
    bus = TelemetryBus()
    with host_span(bus, "superinfer.a"):
        pass
    assert bus.host_counters()["spans"]["superinfer.a"]["calls"] == 1
    assert TelemetryBus().host_counters()["spans"] == {}


def test_nested_spans_give_self_time():
    bus = TelemetryBus()
    with bus.span("superinfer.outer"):
        _busy(2_000_000)
        for _ in range(2):
            with bus.span("superinfer.inner"):
                _busy(3_000_000)
    sp = bus.host_counters()["spans"]
    outer, inner = sp["superinfer.outer"], sp["superinfer.inner"]
    assert outer["calls"] == 1 and inner["calls"] == 2
    assert inner["self_ns"] == inner["total_ns"] >= 6_000_000
    assert outer["total_ns"] >= inner["total_ns"] + 2_000_000
    assert outer["self_ns"] == outer["total_ns"] - inner["total_ns"]


def test_spans_on_two_threads_and_interleaved_tasks():
    """The HTTP event loop and the engine driver close spans at once: the
    counts add up, and a task's span is nobody else's parent."""
    bus = TelemetryBus()

    def work():
        for _ in range(500):
            with bus.span("superinfer.t"):
                pass
    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(30)
    assert not any(t.is_alive() for t in threads)
    assert bus.host_counters()["spans"]["superinfer.t"]["calls"] == 2000

    async def task(delay):
        with bus.span("superinfer.task"):
            await asyncio.sleep(delay)

    async def both():
        await asyncio.gather(task(0.02), task(0.01))
    asyncio.run(both())
    t = bus.host_counters()["spans"]["superinfer.task"]
    assert t["calls"] == 2 and t["self_ns"] == t["total_ns"]


def test_queue_wait_histogram_buckets():
    bus = TelemetryBus()
    for s in (0.0005, 0.002, 0.002, 20.0):
        bus.count_queue_wait(int(s * 1e9))
    qw = bus.host_counters()["queue_wait"]
    assert qw["count"] == 4
    assert qw["total_ns"] == int(0.0005e9) + 2 * int(0.002e9) + int(20e9)
    assert qw["le_s"] == list(QUEUE_WAIT_EDGES_S)
    assert qw["buckets"][0] == 1 and qw["buckets"][1] == 2
    assert qw["buckets"][-1] == 1 and sum(qw["buckets"]) == 4


def test_spans_land_nested_in_a_profiler_trace(tmp_path):
    """In a profiler session each span is a host event of the same name,
    nested as the code nests it."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData

    bus = TelemetryBus()
    x = jnp.ones((64, 64))
    (x @ x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    try:
        with bus.span("superinfer.engine.step"):
            with bus.span("superinfer.runner.execute"):
                with bus.span("superinfer.runner.launch"):
                    y = x @ x
                with bus.span("superinfer.runner.sync"):
                    y.block_until_ready()
    finally:
        jax.profiler.stop_trace()
    path = sorted(tmp_path.rglob("*.xplane.pb"))[-1]
    ev = {}
    for plane in ProfileData.from_file(str(path)).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("superinfer."):
                    ev[e.name] = (e.start_ns, e.start_ns + e.duration_ns)
    assert set(ev) == {"superinfer.engine.step", "superinfer.runner.execute",
                       "superinfer.runner.launch", "superinfer.runner.sync"}

    def inside(child, parent):
        return ev[parent][0] <= ev[child][0] <= ev[child][1] <= ev[parent][1]
    assert inside("superinfer.runner.execute", "superinfer.engine.step")
    assert inside("superinfer.runner.launch", "superinfer.runner.execute")
    assert inside("superinfer.runner.sync", "superinfer.runner.execute")
    assert ev["superinfer.runner.launch"][1] <= ev["superinfer.runner.sync"][0]


# ------------------------------------------------------ the real serving path
def _serve_paged(telemetry):
    """Four prompts through the reduced paged runner on a pool tight enough
    to rotate KV out to the host tier and back."""
    sv = ServingConfig(num_hbm_blocks=16, num_dram_blocks=512,
                       scheduler="rotasched", block_size=4, max_model_len=64,
                       prefill_chunk=8, paged_runner=True,
                       telemetry=telemetry)
    core = EngineCore(CFG, sv, GH200, runner_cfg=CFG, runner_seed=42)
    rng = np.random.default_rng(3)
    handles = []
    for _ in range(4):
        ids = [int(x) for x in rng.integers(1, CFG.vocab_size,
                                            int(rng.integers(8, 16)))]
        handles.append(core.add_request(
            prompt_ids=ids, sampling_params=SamplingParams(
                max_tokens=int(rng.integers(10, 16)))))
    core.drain(max_time_s=500)
    return core, [h.request for h in handles]


@pytest.fixture(scope="module")
def served():
    return _serve_paged(True)


def test_paged_runner_fires_every_engine_span(served):
    core, reqs = served
    st, store = core.stats, core.executor.store
    assert st.active_rotations + st.passive_preemptions > 0
    assert store.d2h_rows > 0 and store.h2d_rows > 0
    spans = core.telemetry.host_counters()["spans"]
    assert set(HOST_SPANS) - FRONT_SPANS <= set(spans)
    assert spans["superinfer.engine.step"]["calls"] >= st.iterations
    for name, c in spans.items():
        assert 0 <= c["self_ns"] <= c["total_ns"], name
    # the runner's host time and waits sit inside its execute span
    ex = spans["superinfer.runner.execute"]["total_ns"]
    assert ex >= sum(spans[f"superinfer.runner.{k}"]["total_ns"]
                     for k in ("prepare", "launch", "sync"))


def test_paged_runner_stamps_requests_on_the_host_clock(served):
    core, reqs = served
    for r in reqs:
        assert r.recv_ns <= r.admit_ns <= r.first_token_ns, r.req_id
    qw = core.telemetry.host_counters()["queue_wait"]
    assert qw["count"] == len(reqs)
    assert qw["total_ns"] == sum(r.admit_ns - r.recv_ns for r in reqs)
    tel = core.telemetry
    assert tel.clock == "host"
    ev = list(tel.events)
    assert len(ev) == core.stats.iterations
    now = time.perf_counter()
    for e in ev:
        assert e.t_start <= e.exec_start <= e.exec_start + e.exec_s \
            <= e.t_end <= now
        assert e.overlap_s == e.stall_s == e.plan_hidden_s == 0.0
    admits = [s for s in tel.spans if s.kind == "ADMIT"]
    assert sorted(s.req_id for s in admits) == sorted(r.req_id for r in reqs)
    for s in admits:
        r = next(q for q in reqs if q.req_id == s.req_id)
        assert s.t_start == r.recv_ns * 1e-9 and s.t_end == r.admit_ns * 1e-9
    assert any(s.kind == "ROTATE_OUT" for s in tel.spans)


def test_recorder_on_leaves_the_paged_tokens_unchanged(served):
    core, reqs = served
    _, off = _serve_paged(False)
    assert [r.generated_ids for r in reqs] == [r.generated_ids for r in off]
    assert all(r.recv_ns is None and r.admit_ns is None for r in off)


def test_prometheus_host_series(served):
    core, _ = served
    text = render_prometheus([core])
    fams = validate_prometheus_text(text)
    assert fams["superinfer_host_span_seconds_total"] == "counter"
    assert fams["superinfer_host_span_calls_total"] == "counter"
    assert fams["superinfer_queue_wait_seconds"] == "histogram"
    assert 'span="superinfer.kvstore.d2h_readback"' in text
    count = [ln for ln in text.splitlines()
             if ln.startswith("superinfer_queue_wait_seconds_count")]
    assert count and float(count[0].rsplit(" ", 1)[1]) == 4


def test_real_path_trace_export_names_the_host_clock():
    """Over HTTP on the paged runner: the front door and the driver record
    their spans, and ``/v1/trace`` says its stamps are host-clock."""
    from test_server import ServerUnderTest, http, stream_events
    with ServerUnderTest(paged_runner=True, pace=False, seed=7) as sut:
        evts = stream_events(sut.port, {"prompt_ids": [3, 1, 4, 1, 5, 9],
                                        "max_tokens": 4})
        assert evts[-1]["finish_reason"] == "length"
        status, body = http(sut.port, "GET", "/v1/trace")
        assert status == 200
        trace = json.loads(body)
        assert trace["otherData"]["clock"] == "host-perf_counter-seconds*1e6"
        spans = sut.engine.telemetry.host_counters()["spans"]
        assert {HS_HTTP_GENERATE, HS_DRIVER_CONTROL, HS_DRIVER_DELIVER,
                "superinfer.runner.launch"} <= set(spans)
        r = sut.engine.submitted[0]
        assert r.recv_ns <= r.admit_ns <= r.first_token_ns
    assert sut.stop() == 0
