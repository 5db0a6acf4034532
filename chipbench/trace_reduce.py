"""From a profiler trace to device busy time, per-program and per-kernel
device time, and idle gaps attributed to what the host was doing.

``extract`` (needs JAX, runs in the process that traced) turns the
``.xplane.pb`` into plain event lists; ``reduce`` (pure Python) computes
everything else from those lists, so the tests can check it on a recorded
trace without a chip.

Names, as a TPU v5e trace shows them (JAX 0.9): programs are events of
the device planes' "XLA Modules" line, named ``jit_<function>(<hash>)``
(``jit__decode_impl(...)``); ops are events of the "XLA Ops" line, named
by their whole HLO instruction (``%fusion.3 = bf16[...] fusion(...)``). A
Pallas kernel's op is a ``custom-call`` with target ``tpu_custom_call``
named after the jitted function that launches it, since the kernels set
no ``name=``: ``%_decode_impl.N`` is paged attention (the decode step's
only kernel) and ``%_copy.N`` the KV row copy. ``PROGRAMS`` and
``KERNELS`` map those names to what the metrics call them. Host spans
come from the ``TraceAnnotation`` wrappers the child installs
(``HOST_SPANS``).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Tuple

WINDOW_SPAN = "chipbench.window"
HOST_SPANS = ("EngineCore.step", "DuplexKV.plan_iteration",
              "PagedKVStore.run_d2h", "PagedKVStore.run_h2d",
              "PagedModelRunner.execute", WINDOW_SPAN)
PROGRAMS = {"decode": "_decode_impl", "prefill": "_prefill_impl",
            "kv_copy": "_copy", "kv_upload": "_upload"}
KERNELS = {"paged_attention": "_decode_impl", "kv_copy": "_copy"}
CUSTOM = 'custom_call_target="tpu_custom_call"'


def op_name(hlo: str) -> str:
    """The short name of an op event: its HLO instruction's name without
    the instance number, marked ``[kernel]`` for a Pallas custom call."""
    name = hlo.split(" = ", 1)[0].lstrip("%") if " = " in hlo else hlo
    name = re.sub(r"[.:]\d+$", "", name)
    return name + " [kernel]" if CUSTOM in hlo else name


# ------------------------------------------------------------------ extract
def extract(path: str) -> Dict[str, list]:
    """Plain lists from one ``.xplane.pb``: device ops (short names, see
    ``op_name``) and programs as ``[name, start_ns, dur_ns, device]``, host
    spans as ``[name, start_ns, dur_ns, thread]``."""
    from jax.profiler import ProfileData
    prof = ProfileData.from_file(path)
    ops, mods, spans = [], [], []
    for plane in prof.planes:
        if plane.name.startswith("/device:"):
            dev = plane.name
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops += [[op_name(e.name), e.start_ns, e.duration_ns, dev]
                            for e in line.events]
                elif line.name == "XLA Modules":
                    mods += [[e.name, e.start_ns, e.duration_ns, dev]
                             for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in HOST_SPANS:
                        spans.append([e.name, e.start_ns, e.duration_ns,
                                      line.name])
    return dict(ops=ops, modules=mods, spans=spans)


# ------------------------------------------------------------------- reduce
def _union(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _clip(s: float, e: float, lo: float, hi: float) -> Optional[tuple]:
    s, e = max(s, lo), min(e, hi)
    return (s, e) if e > s else None


def _match(table: Dict[str, str], name: str) -> Optional[str]:
    return next((k for k, sub in table.items() if sub in name), None)


def reduce(ev: Dict[str, list], top: int = 10,
           kernels: Optional[Dict[str, str]] = None) -> Dict[str, object]:
    """Busy and idle time, program and kernel time, inside the window the
    ``chipbench.window`` host span marks (the whole trace if it has none).
    Times in seconds; ``busy_s`` is averaged over the devices that ran.
    ``kernels`` is an architecture's own table (``arch``), matched before
    ``KERNELS``."""
    win = [s for s in ev["spans"] if s[0] == WINDOW_SPAN]
    if win:
        lo, hi = win[0][1], win[0][1] + win[0][2]
    else:
        pts = [(o[1], o[1] + o[2]) for o in ev["ops"]]
        lo, hi = min(p[0] for p in pts), max(p[1] for p in pts)
    devices = sorted({o[3] for o in ev["ops"]})
    busy_ns, by_op, kernel_ns = 0.0, {}, {}
    gaps: List[Tuple[float, float]] = []
    for dev in devices:
        iv = [c for o in ev["ops"] if o[3] == dev
              for c in [_clip(o[1], o[1] + o[2], lo, hi)] if c]
        merged = _union(iv)
        busy_ns += sum(e - s for s, e in merged)
        prev = lo
        for s, e in merged:
            if s > prev:
                gaps.append((prev, s))
            prev = e
        if hi > prev:
            gaps.append((prev, hi))
    for o in ev["ops"]:
        c = _clip(o[1], o[1] + o[2], lo, hi)
        if not c:
            continue
        dur = c[1] - c[0]
        by_op[o[0]] = by_op.get(o[0], 0.0) + dur
        if o[0].endswith(" [kernel]"):
            k = _match(kernels or {}, o[0]) or _match(KERNELS, o[0])
            if k:
                kernel_ns[k] = kernel_ns.get(k, 0.0) + dur
    programs: Dict[str, Dict[str, float]] = {}
    for m in ev["modules"]:
        c = _clip(m[1], m[1] + m[2], lo, hi)
        p = _match(PROGRAMS, m[0])
        if not c or not p:
            continue
        d = programs.setdefault(p, dict(seconds=0.0, calls=0))
        d["seconds"] += (c[1] - c[0]) * 1e-9
        d["calls"] += 1
    idle = _attribute(gaps, [s for s in ev["spans"] if s[0] != WINDOW_SPAN])
    n_dev = max(len(devices), 1)
    return dict(
        window_s=(hi - lo) * 1e-9,
        busy_s=busy_ns / n_dev * 1e-9,
        programs=programs,
        kernels={k: v * 1e-9 for k, v in kernel_ns.items()},
        device_ops=[[k, v * 1e-9] for k, v in
                    sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        idle_gaps=[[k, v] for k, v in
                   sorted(idle.items(), key=lambda kv: -kv[1])[:top]])


def _attribute(gaps: List[Tuple[float, float]],
               spans: List[list]) -> Dict[str, float]:
    """Seconds of device idle time by the innermost host span open at each
    gap's midpoint ("other" where none is: waiting for requests, the HTTP
    front door, Python outside the wrapped calls)."""
    spans = sorted(spans, key=lambda s: s[1])
    out: Dict[str, float] = {}
    j, live = 0, []
    for s, e in sorted(gaps, key=lambda g: g[0] + g[1]):
        mid = (s + e) / 2
        while j < len(spans) and spans[j][1] <= mid:
            live.append(spans[j])
            j += 1
        live = [sp for sp in live if sp[1] + sp[2] >= mid]
        name = min(live, key=lambda sp: sp[2])[0] if live else "other"
        out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out
