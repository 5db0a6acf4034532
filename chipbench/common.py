"""Shared plumbing of the on-chip benchmark: where its files live, how a
cell resolves to its configuration, traffic and settings, and the peaks
table. Imports nothing of JAX, so the load generator can use it."""
from __future__ import annotations

import json
import sys
from pathlib import Path
from typing import Any, Dict

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
BENCHMARK_JSON = ROOT / "BENCHMARK.json"
SRC = ROOT / "src"


def load_json(path: Path) -> Any:
    with open(path) as f:
        return json.load(f)


def benchmark() -> Dict[str, Any]:
    return load_json(BENCHMARK_JSON)


def resolve_cell(name: str, bench: Dict[str, Any] = None) -> Dict[str, Any]:
    """Everything one cell needs, found by name: its ``BENCHMARK.json``
    entry, its configuration file (``configs/<config>.json``), its traffic
    mix (``traffic/<traffic>.json``) and its server settings
    (``workloads/<name>.json``)."""
    bench = bench if bench is not None else benchmark()
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    entry = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    conf_entry = configs[entry["config"]]
    return dict(
        name=name,
        entry=entry,
        config=load_json(ROOT / conf_entry["file"]),
        traffic=load_json(BENCH_DIR / "traffic" / f"{entry['traffic']}.json"),
        settings=load_json(BENCH_DIR / "workloads" / f"{name}.json"),
        end_to_end=[m for m in bench["end_to_end"] if name in
                    m.get("workloads", [name])],
        per_layer=[m for m in bench["per_layer"] if name in
                   m.get("workloads", [name])],
    )


def peaks(device_kind: str) -> Dict[str, float]:
    """Published peaks of one chip of ``device_kind``. A kind that is not in
    the table is an error, never a default."""
    table = load_json(BENCH_DIR / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(table)}")
    return table[device_kind]


def log(*parts: object) -> None:
    """Progress line on standard error (standard output carries results)."""
    print(*parts, file=sys.stderr, flush=True)
