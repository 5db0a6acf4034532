"""The whole harness on the CPU at the program's reduced size: both
processes, the HTTP streams, the reference check and the result line. A
sound run is correct and names the CPU with no device metric; a run whose
served tokens are altered where they are produced is not correct, nor is
one that judges the int8 control in the served tokens' place; without a
chip, or without the program beside it, a run prints no result."""
import json
import os
import shutil
import subprocess
import sys

import pytest

from common import BENCH_DIR, ROOT

RUN = [sys.executable, str(BENCH_DIR / "run.py")]
CELL = "qwen2.5-32b.longdoc"


def run(args, cwd=ROOT, timeout=240):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(RUN[:1] + [str(cwd / "chipbench" / "run.py")]
                          + args, cwd=cwd, env=env, capture_output=True,
                          text=True, timeout=timeout)


def result(p):
    assert p.returncode == 0, p.stderr[-3000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


def test_rehearsal_is_correct_and_names_the_cpu():
    p = run(["--workload", CELL, "--seed", str(2 ** 31 + 11), "--seconds",
             "4", "--trace", "0", "--rehearse-cpu"])
    line = result(p)
    assert line["correct"] is True, p.stderr[-3000:]
    assert line["device"]["platform"] == "cpu"
    assert "busy_s" not in line["device"] and "breakdown" not in line
    # every end-to-end metric that a CPU run has samples for (its tokens
    # can be too slow to leave gaps inside a short window)
    assert {"ttft_p90_s", "ttft_attainment", "output_tokens_per_s",
            "setup_s"} <= set(line["metrics"]) <= {
        "ttft_p90_s", "tbt_p99_s", "ttft_attainment", "output_tokens_per_s",
        "setup_s"}
    assert list(line)[-1] == "checks"
    gap = line["checks"]["widest_logit_gap"]
    assert gap["value"] <= gap["limit"]
    assert "check widest_logit_gap" in p.stderr.strip().splitlines()[-3]


def test_altered_tokens_are_caught():
    p = run(["--workload", "yi-34b.rag", "--seed", "5", "--seconds", "4",
             "--trace", "1", "--rehearse-cpu", "--fault", "token",
             "--rate", "3"])
    line = result(p)
    assert line["correct"] is False
    assert line["attempted"] == 12                   # 3/s for 4 s
    gap = line["checks"]["widest_logit_gap"]
    assert gap["value"] > gap["limit"]
    # counters only: no device metric from a CPU run
    assert set(line["metrics"]) == {"engine.iteration_ms",
                                    "engine.rotations_per_s",
                                    "duplexkv.rows_moved_per_s",
                                    "frontdoor.queue_wait_ms",
                                    "engine.host_ms",
                                    "duplexkv.d2h_wait_ms",
                                    "runner.host_ms",
                                    "kernels.attn_live_share"}


def test_no_chip_no_result():
    p = run(["--workload", CELL, "--seed", "1", "--seconds", "2",
             "--trace", "0"], timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = run(["--workload", CELL, "--seed", "1", "--seconds", "2",
             "--trace", "0", "--rehearse-cpu"], cwd=tmp_path, timeout=120)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def _report(control, gaps):
    """``run.report`` over one finished request whose served tokens and
    int8 control read the given gaps."""
    import argparse
    import importlib.util
    from client import Stream
    spec = importlib.util.spec_from_file_location("chipbench_run",
                                                  BENCH_DIR / "run.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    s = Stream(prompt_ids=[1, 2], max_tokens=2, slo_class="batch",
               token_ids=[3, 4], finish_reason="length", req_id=0)
    s.events = [(1.0, 1), (1.1, 1)]
    out = dict(res=dict(gaps=[gaps], memory_peak_bytes=1), e2e={},
               sample=[s], win=[s], rotated=set(), setup_s=1.0,
               wcfg=dict(vocab_size=10))
    cell = dict(settings=dict(correct=dict(max_logit_gap=0.1)),
                end_to_end=[], per_layer=[])
    args = argparse.Namespace(rehearse_cpu=False, control=control, trace=0)
    return mod.report(args, cell, dict(platform="cpu", kind="cpu", count=1),
                      out)


@pytest.mark.parametrize("control,correct", [(False, True), (True, False)])
def test_control_is_judged_in_place_of_the_served_tokens(control, correct):
    line = _report(control, dict(served=[0.0, 0.02], control=[0.0, 0.5]))
    assert line["correct"] is correct
    name = "control_widest_logit_gap" if control else "widest_logit_gap"
    assert set(line["checks"]) == {name, "sampled_tokens",
                                   "malformed_streams"}
    assert line["checks"][name]["value"] == (0.5 if control else 0.02)
