"""Benchmark driver: one module per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only fig16,table1]

Prints ``name,seconds,derived`` CSV rows (per-module sections) and, for
every module attempted, writes a machine-readable
``benchmarks/results/BENCH_<tag>.json`` (status, wall seconds, argv, and —
when the module's ``main()`` returns a dict — its headline metrics), so the
perf trajectory across PRs is tracked in-repo instead of only in stdout.
"""
import json
import os
import sys
import time
import traceback

MODULES = [
    ("table1", "benchmarks.bench_transfer_engine"),
    ("fig5_12", "benchmarks.bench_segment_bw"),
    ("fig1", "benchmarks.bench_wf_sf"),
    ("fig2", "benchmarks.bench_swap_bw"),
    ("fig16", "benchmarks.bench_main_slo"),
    ("fig17", "benchmarks.bench_ablation_modules"),
    ("fig18", "benchmarks.bench_alpha"),
    ("fig19_20", "benchmarks.bench_beta"),
    ("fig21", "benchmarks.bench_bxfer"),
    ("fig22", "benchmarks.bench_throughput"),
    ("fig23", "benchmarks.bench_fcfs_sjf"),
    ("roofline", "benchmarks.bench_roofline"),
    ("router", "benchmarks.bench_router_scaling"),
    ("prefix_cache", "benchmarks.bench_prefix_cache"),
    ("paged_decode", "benchmarks.bench_paged_decode"),
    ("tp_decode", "benchmarks.bench_tp_decode"),
    ("disagg", "benchmarks.bench_disagg"),
    ("pipeline", "benchmarks.bench_pipeline"),
    ("server", "benchmarks.bench_server"),
    ("kv_quant", "benchmarks.bench_kv_quant"),
]

RESULTS_DIR = os.path.join(os.path.dirname(__file__), "results")


def _write_result(tag: str, record: dict) -> None:
    os.makedirs(RESULTS_DIR, exist_ok=True)
    path = os.path.join(RESULTS_DIR, f"BENCH_{tag}.json")
    with open(path, "w") as f:
        json.dump(record, f, indent=1, sort_keys=True, default=str)
        f.write("\n")


def main() -> None:
    from repro.core.blocktable import OutOfBlocks

    only = None
    for a in sys.argv[1:]:
        if a.startswith("--only"):
            only = set(a.split("=", 1)[1].split(",")) if "=" in a else None
    import importlib
    t_all = time.time()
    failed = []
    for tag, modname in MODULES:
        if only and tag not in only:
            continue
        print(f"# === {tag} ({modname}) ===", flush=True)
        t0 = time.time()
        record = dict(bench=tag, module=modname, argv=sys.argv[1:],
                      status="ok", metrics=None)
        try:
            ret = importlib.import_module(modname).main()
            if isinstance(ret, dict):
                record["metrics"] = ret
            print(f"# {tag} done in {time.time()-t0:.0f}s", flush=True)
        except OutOfBlocks:
            # a capacity bug in the engine under benchmark is a real defect,
            # not a bad config — fail the whole run
            record.update(status="failed", error="OutOfBlocks")
            record["seconds"] = round(time.time() - t0, 1)
            _write_result(tag, record)
            raise
        except (ImportError, OSError, RuntimeError, ValueError, KeyError,
                TypeError, AssertionError) as e:
            # environment/config failures (missing optional dep, bad grid
            # point, jax backend quirk) and failed headline assertions: log
            # with full context, run the other modules, fail the run at the
            # end; anything else propagates
            print(f"# {tag} FAILED ({type(e).__name__}):\n"
                  f"{traceback.format_exc()}", flush=True)
            record.update(status="failed",
                          error=f"{type(e).__name__}: {e}")
            failed.append(tag)
        record["seconds"] = round(time.time() - t0, 1)
        _write_result(tag, record)
    print(f"# total {time.time()-t_all:.0f}s")
    if failed:
        print(f"# FAILED modules: {','.join(failed)}", flush=True)
        sys.exit(1)


if __name__ == "__main__":
    main()
