"""Per-layer metrics read from the program's own host spans and counters:
the flight recorder's ``TelemetryBus.host_counters()``, which a run's
counter snapshots carry under ``host``. Span names are the program's fixed
names (``repro.serving.telemetry.HOST_SPANS``), copied here so that the
benchmark imports nothing of the program.

Each reader takes the run's context (``run.context``) and returns None
where a snapshot lacks ``host`` (a program without these spans, or one
serving with its recorder off) or where the window holds nothing to divide
by. Times are host-clock milliseconds."""
from __future__ import annotations

STEP = "superinfer.engine.step"
PLAN = "superinfer.duplexkv.plan"
EXECUTE = "superinfer.runner.execute"
SYNC = "superinfer.runner.sync"
D2H_READBACK = "superinfer.kvstore.d2h_readback"


def _host(ctx):
    h0, h1 = ctx["c0"].get("host"), ctx["c1"].get("host")
    return (h0, h1) if h0 and h1 else None


def _span_ns(host, name):
    """Nanoseconds inside span ``name`` over the window (0 where it never
    ran)."""
    def total(h):
        return h["spans"].get(name, {}).get("total_ns", 0)
    return total(host[1]) - total(host[0])


def _per_iteration_ms(ctx, ns):
    c0, c1 = ctx["c0"].get("iterations"), ctx["c1"].get("iterations")
    if c0 is None or c1 is None or c1 == c0:
        return None
    return ns / (c1 - c0) / 1e6


def queue_wait_ms(ctx):
    """HTTP front door: mean host-clock wait of the requests admitted in the
    window, from their receipt by the front door to their admission."""
    host = _host(ctx)
    if host is None:
        return None
    q0, q1 = host[0]["queue_wait"], host[1]["queue_wait"]
    n = q1["count"] - q0["count"]
    return (q1["total_ns"] - q0["total_ns"]) / n / 1e6 if n else None


def engine_host_ms(ctx):
    """Engine + RotaSched: host milliseconds per iteration inside the
    engine's step outside DuplexKV's planning and the runner's execute
    (scheduling, admission, batch building, commit)."""
    host = _host(ctx)
    if host is None:
        return None
    ns = (_span_ns(host, STEP) - _span_ns(host, PLAN)
          - _span_ns(host, EXECUTE))
    return _per_iteration_ms(ctx, ns)


def d2h_wait_ms(ctx):
    """Block table + DuplexKV: host milliseconds per iteration blocked on
    the KV store's device-to-host readback."""
    host = _host(ctx)
    if host is None:
        return None
    return _per_iteration_ms(ctx, _span_ns(host, D2H_READBACK))


def runner_host_ms(ctx):
    """Runner: host milliseconds per iteration inside the runner's execute
    outside its waits on the device (block tables, padding, launches)."""
    host = _host(ctx)
    if host is None:
        return None
    return _per_iteration_ms(ctx, _span_ns(host, EXECUTE)
                             - _span_ns(host, SYNC))
