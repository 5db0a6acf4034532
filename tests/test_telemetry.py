"""Flight recorder: lifecycle spans, Perfetto export, Prometheus text,
TTFT-miss attribution, and the structured JSON-lines logger.

The acceptance contract this file pins down:

  * telemetry defaults OFF and is structurally inert — the same workload
    replayed with the bus on produces a bit-identical SLO report;
  * a pipelined tight-HBM run exports a trace whose D2H and H2D tracks
    demonstrably overlap (full-duplex evidence) and whose geometric
    transfer-under-compute overlap agrees with the engine's own
    ``overlap_ms`` accounting;
  * every TTFT decomposes exactly into queue-wait + rotation-stall +
    prefill-compute (within 1e-6 sim-seconds), per request and summed in
    ``SLOReport.ttft_miss``;
  * ``render_prometheus`` emits syntactically valid text-format 0.0.4,
    and the live server serves it on ``/v1/metrics`` via content
    negotiation alongside ``/v1/trace``.
"""
import json

import pytest

from repro.configs import (GH200, RotaSchedConfig, ServingConfig, SLOConfig,
                           get_config)
from repro.core.types import Request
from repro.serving.disagg import DisaggCluster
from repro.serving.engine import ServingEngine
from repro.serving.metrics import TTFTMissBreakdown
from repro.serving.telemetry import (SPAN_ADMIT, SPAN_FINISH, SPAN_KINDS,
                                     SPAN_MIGRATE, SPAN_ROTATE_IN,
                                     SPAN_ROTATE_OUT, TelemetryBus, buses_of,
                                     log_event, render_prometheus,
                                     slo_buckets, validate_prometheus_text)
from repro.serving.trace_export import (TRACK_D2H, TRACK_H2D, analyze_trace,
                                        export_trace, trace_from_cores)
from repro.serving.workload import (generate_bursty_requests,
                                    generate_requests)

CFG = get_config("llama3-8b")


def tight_sv(**kw):
    """Enough memory pressure to force rotations on the sharegpt trace.

    Pipelined by default: the sync path at this pool size thrashes into
    hundreds of thousands of iterations (minutes of wall time) while the
    pipelined engine serves the same trace in seconds with thousands of
    rotations — plenty of telemetry signal. Sync-specific tests override.
    """
    kw.setdefault("num_hbm_blocks", 200)
    kw.setdefault("num_dram_blocks", 100000)
    kw.setdefault("scheduler", "rotasched")
    kw.setdefault("pipeline", True)
    return ServingConfig(**kw)


def run_engine(sv, rps=10, duration=5, seed=0, max_time_s=600, slo=None):
    reqs = generate_requests("sharegpt", rps, duration, seed=seed, slo=slo)
    eng = ServingEngine(CFG, sv, GH200)
    rep = eng.run(reqs, max_time_s=max_time_s)
    return eng, rep, reqs


# ----------------------------------------------------- default off + inert
def test_telemetry_default_off():
    sv = ServingConfig(num_hbm_blocks=64, num_dram_blocks=256)
    assert sv.telemetry is False
    eng = ServingEngine(CFG, sv, GH200)
    assert eng.core.telemetry is None


def test_telemetry_on_is_replay_inert():
    """Same seed, bus on vs off: the SLO report rows are identical — the
    flight recorder observes the engine without perturbing it."""
    rows = {}
    for on in (False, True):
        _, rep, _ = run_engine(tight_sv(pipeline=True, telemetry=on))
        rows[on] = rep.row()
    assert rows[True] == rows[False]


# ----------------------------------------------------------- span capture
def test_lifecycle_spans_cover_every_request():
    eng, rep, reqs = run_engine(tight_sv(telemetry=True))
    bus = eng.core.telemetry
    assert bus is not None
    spans = list(bus.spans)
    assert spans and all(s.kind in SPAN_KINDS for s in spans)
    by_kind = {}
    for s in spans:
        by_kind.setdefault(s.kind, []).append(s)
    # every request was admitted exactly once and finished exactly once
    assert sorted(s.req_id for s in by_kind[SPAN_ADMIT]) == \
        sorted(r.req_id for r in reqs)
    assert sorted(s.req_id for s in by_kind[SPAN_FINISH]) == \
        sorted(r.req_id for r in reqs)
    for s in by_kind[SPAN_ADMIT]:
        assert s.t_end >= s.t_start
        assert s.attrs["queue_wait_s"] == pytest.approx(s.t_end - s.t_start)
    # the tight pool forced rotations, and each leg carries bytes+direction
    assert rep.rotations > 0
    assert by_kind.get(SPAN_ROTATE_OUT) and by_kind.get(SPAN_ROTATE_IN)
    for s in by_kind[SPAN_ROTATE_OUT]:
        assert s.attrs["direction"] == "d2h" and s.attrs["bytes"] > 0
    for s in by_kind[SPAN_ROTATE_IN]:
        assert s.attrs["direction"] == "h2d"
    # FINISH spans carry the terminal attribution
    fin = by_kind[SPAN_FINISH][0]
    assert "reason" in fin.attrs and "tokens" in fin.attrs
    ev = list(bus.events)
    assert len(ev) == eng.core.stats.iterations
    assert all(e.attrs["hbm_free_blocks"] >= 0 for e in ev)
    assert all("vlt_max" in e.attrs for e in ev)


def test_ring_buffer_drops_oldest_and_counts():
    bus = TelemetryBus(capacity=4)
    for i in range(10):
        bus.record("ADMIT", req_id=i, t_start=float(i), t_end=float(i))
    assert len(list(bus.spans)) == 4
    assert [s.req_id for s in bus.spans] == [6, 7, 8, 9]
    assert bus.counters()["spans_dropped"] == 6


def test_migration_spans_on_both_replicas():
    reqs = generate_bursty_requests("sharegpt", 12, 10, seed=0,
                                    burst_factor=3.0)
    rot = RotaSchedConfig(alpha=3.0, beta_b=0.0, beta_f=0.5, b_xfer=2400)
    sv = ServingConfig(num_hbm_blocks=4000, num_dram_blocks=100000,
                       scheduler="rotasched", rotary=rot, auto_b_xfer=True,
                       telemetry=True)
    dc = DisaggCluster(CFG, sv, GH200, prefill_replicas=1,
                       decode_replicas=1)
    rep = dc.run(reqs, max_time_s=500)
    assert rep.migrations > 0
    buses = buses_of(dc.replicas)
    assert [b.role for b in buses] == ["prefill", "decode"]
    src = [s for s in buses[0].spans if s.kind == SPAN_MIGRATE]
    dst = [s for s in buses[1].spans if s.kind == SPAN_MIGRATE]
    assert len(src) == rep.migrations == len(dst)
    for s in src:
        assert s.attrs["direction"] == "d2h" and s.attrs["bytes"] > 0
        assert s.attrs["dst_replica"] == 1
    for s in dst:
        assert s.attrs["direction"] == "h2d" and s.attrs["src_replica"] == 0


# --------------------------------------------------- trace export/analysis
def test_pipelined_trace_shows_duplex_overlap_and_matches_overlap_ms(
        tmp_path):
    """The acceptance trace: a pipelined run under rotation pressure must
    show D2H and H2D slices running concurrently (full duplex), and the
    geometric transfer-under-compute overlap recomputed from the trace
    must equal what the engine credited iteration by iteration."""
    from repro.launch.serve import main
    out = tmp_path / "trace.json"
    row = main(["--rps", "10", "--duration", "5", "--hbm-blocks", "200",
                "--dram-blocks", "100000", "--pipeline",
                "--trace-out", str(out), "--json"])
    assert row["telemetry"]["spans"] > 0
    assert row["telemetry"]["spans_dropped"] == 0
    trace = json.loads(out.read_text())
    assert trace["traceEvents"]
    a = analyze_trace(trace)
    assert a["d2h_h2d_concurrent_pairs"] >= 1
    assert a["d2h_h2d_overlap_s"] > 0
    # span-recomputed overlap == engine-recorded overlap (same geometry)
    assert a["span_overlap_s"] == pytest.approx(a["event_overlap_s"],
                                                abs=1e-6)
    # and together with plan-hiding it reproduces the report's overlap_ms
    assert (a["event_overlap_s"] + a["plan_hidden_s"]) * 1e3 == \
        pytest.approx(row["overlap_ms"], rel=1e-9)


def test_trace_track_layout_and_request_tracks():
    eng, _, reqs = run_engine(tight_sv(telemetry=True))
    trace = trace_from_cores([eng.core])
    evs = trace["traceEvents"]
    names = {e["args"]["name"] for e in evs if e["ph"] == "M"
             and e["name"] == "thread_name"}
    assert {"scheduler", "compute", "D2H", "H2D"} <= names
    # one lifecycle track per request
    assert any(n.startswith("req 0 ") for n in names)
    d2h = [e for e in evs if e.get("tid") == TRACK_D2H and e["ph"] == "X"]
    h2d = [e for e in evs if e.get("tid") == TRACK_H2D and e["ph"] == "X"]
    assert d2h and h2d
    assert all(e["dur"] > 0 and e["args"]["bytes"] >= 0 for e in d2h + h2d)
    assert trace["otherData"]["replicas"] == 1
    assert trace["otherData"]["counters"]["0"]["spans_recorded"] > 0


def test_export_trace_empty_bus_is_valid():
    trace = export_trace([TelemetryBus(capacity=16)])
    a = analyze_trace(trace)
    assert a["d2h_h2d_concurrent_pairs"] == 0
    assert a["span_overlap_s"] == 0.0


# -------------------------------------------------- TTFT-miss attribution
def test_ttft_breakdown_sums_exactly_per_request():
    # threshold tighter than the achievable TTFT so misses exist to
    # attribute; tight HBM so some of them stall on rotation
    _, rep, reqs = run_engine(tight_sv(), slo=SLOConfig(ttft_s=0.2))
    assert rep.rotations > 0
    seen_rot = 0
    for r in reqs:
        d = r.ttft_breakdown()
        if d is None:
            continue
        assert d["queue_wait_s"] >= 0
        assert d["rotation_stall_s"] >= 0
        assert d["queue_wait_s"] + d["rotation_stall_s"] \
            + d["prefill_compute_s"] == pytest.approx(r.ttft(), abs=1e-6)
        seen_rot += d["rotation_stall_s"] > 0
    assert seen_rot > 0, "no pre-first-token rotation stall was attributed"


def test_slo_report_miss_breakdown_components_sum():
    _, rep, reqs = run_engine(tight_sv(), slo=SLOConfig(ttft_s=0.2))
    bd = rep.ttft_miss
    assert isinstance(bd, TTFTMissBreakdown)
    assert bd.n_missed == sum(1 for r in reqs
                              if not r.aborted and r.ttft_ok() is False)
    assert bd.n_missed > 0, "workload produced no TTFT misses to attribute"
    assert bd.queue_wait_s + bd.rotation_stall_s + bd.prefill_compute_s \
        == pytest.approx(bd.ttft_s, abs=1e-6)
    # serialized in the report row (serve --json / HTTP /v1/metrics)
    row = rep.row()
    assert row["ttft_miss"]["n_missed"] == bd.n_missed
    for cls_row in row["per_class"].values():
        m = cls_row["ttft_miss"]
        assert m["queue_wait_s"] + m["rotation_stall_s"] \
            + m["prefill_compute_s"] == pytest.approx(m["ttft_s"], abs=1e-6)


def test_breakdown_none_without_first_token():
    r = Request(req_id=0, arrival_time=0.0, prompt_len=8, output_len=4)
    assert r.ttft_breakdown() is None
    r.start_running(2.0)
    assert r.ttft_breakdown() is None       # still no token
    r.rotate_out(3.0)
    r.resume(5.0)
    r.record_token(6.0)
    d = r.ttft_breakdown()
    assert d == {"ttft_s": 6.0, "queue_wait_s": 2.0,
                 "rotation_stall_s": 2.0, "prefill_compute_s": 2.0}
    # post-first-token rotations do not pollute the stall attribution
    r.rotate_out(7.0)
    r.resume(9.0)
    assert r.ttft_breakdown() == d


# ------------------------------------------------------------- prometheus
def test_render_prometheus_valid_and_complete():
    eng, rep, _ = run_engine(tight_sv(telemetry=True))
    text = render_prometheus([eng.core], extra={"ready": 1})
    fams = validate_prometheus_text(text)
    for name in ("superinfer_requests_total",
                 "superinfer_tokens_generated_total",
                 "superinfer_rotations_total",
                 "superinfer_transfer_bytes_total",
                 "superinfer_hbm_free_blocks",
                 "superinfer_queue_depth",
                 "superinfer_ttft_missed_total",
                 "superinfer_ttft_miss_component_seconds_total",
                 "superinfer_server_ready"):
        assert name in fams, f"{name} missing from exposition"
    assert fams["superinfer_ttft_seconds"] == "histogram"
    assert fams["superinfer_iteration_seconds"] == "histogram"
    assert 'replica="0"' in text and 'slo_class="standard"' in text
    assert 'direction="d2h"' in text and 'component="rotation_stall"' in text
    # counter values agree with the engine's own accounting
    tok = [ln for ln in text.splitlines()
           if ln.startswith("superinfer_tokens_generated_total{")]
    total = sum(float(ln.rsplit(" ", 1)[1]) for ln in tok)
    assert total == pytest.approx(
        sum(r.tokens_generated for r in eng.core.submitted))


def test_prometheus_works_without_telemetry_bus():
    """Counters/gauges/histograms come from engine state; the exposition
    must not require the ring buffer to be enabled."""
    eng, _, _ = run_engine(tight_sv(), rps=5, duration=2)
    fams = validate_prometheus_text(render_prometheus([eng.core]))
    assert "superinfer_requests_total" in fams
    assert "superinfer_telemetry_spans_recorded" not in fams


def test_slo_buckets_shape():
    bs = slo_buckets(0.4)
    assert bs == [0.05, 0.1, 0.2, 0.4, 0.8, 1.6]
    assert bs == sorted(bs)


def test_validator_rejects_malformed_text():
    with pytest.raises(ValueError):
        validate_prometheus_text("superinfer_x{bad 1.0\n")
    with pytest.raises(ValueError):        # sample without a TYPE line
        validate_prometheus_text("no_type_metric 1.0\n")
    with pytest.raises(ValueError):        # histogram missing _count
        validate_prometheus_text(
            "# TYPE h histogram\n"
            'h_bucket{le="+Inf"} 1\nh_sum 0.5\n')


# ------------------------------------------------- structured JSON logging
def test_log_event_emits_json_lines(capsys):
    log_event("engine_up", replicas=2, model="llama3-8b")
    log_event("weird", obj=object())      # non-serializable -> stringified
    err = capsys.readouterr().err.strip().splitlines()
    rows = [json.loads(ln) for ln in err]
    assert rows[0]["event"] == "engine_up" and rows[0]["replicas"] == 2
    assert "ts" in rows[0]
    assert rows[1]["event"] == "weird" and isinstance(rows[1]["obj"], str)


# ------------------------------------------------------------ HTTP surface
def test_server_scrapes_prometheus_and_trace():
    from test_server import ServerUnderTest, http, stream_events
    with ServerUnderTest(pace=False) as sut:
        evts = stream_events(sut.port, {"prompt_len": 48, "max_tokens": 8})
        assert evts[-1]["finished"]
        # default JSON stays (back-compat), negotiation selects Prometheus
        status, body = http(sut.port, "GET", "/v1/metrics")
        assert status == 200 and json.loads(body)["n"] >= 1
        status, body = http(sut.port, "GET",
                            "/v1/metrics?format=prometheus")
        assert status == 200
        fams = validate_prometheus_text(body.decode())
        assert "superinfer_requests_total" in fams
        assert "superinfer_server_streams_started" in fams
        status, body = http(sut.port, "GET", "/v1/trace")
        assert status == 200
        trace = json.loads(body)
        assert trace["traceEvents"]
        kinds = {e["name"] for e in trace["traceEvents"]
                 if e.get("cat") == "request"}
        assert SPAN_ADMIT in kinds and SPAN_FINISH in kinds
    assert sut.stop() == 0
