#!/usr/bin/env python3
"""Run one cell of the on-chip serving benchmark once.

    python chipbench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

This process is the open-loop load generator and does the metric
arithmetic; it never imports JAX. It starts ``child.py``, which holds the
chip and serves the program's HTTP front door (``POST /v1/generate``) with
weights made from the seed. Then, on the client's clock:

1. set-up (``setup_s``, from the child's start to the window's opening):
   the child's start, weights, the warm-up requests that meet every padded
   shape the cell's traffic can reach (``traffic.warmup_plan``), and a
   pre-roll of the cell's own traffic that lets the KV pool fill;
2. the window: ``--seconds`` of the cell's traffic, each request timed from
   when it was due;
3. a bounded drain for the window's requests to get their first token;
4. the check: the child frees the program and runs the plain reference
   over a seeded sample of the finished requests (the longest among them,
   and rotated ones where the cell rotates); the widest gap by which a
   served token's logit lies below the reference's best has to stay
   within the cell's limit. With ``--control`` the tokens that the int8
   control puts first at the same positions are judged instead, and the
   run has to come out not correct.

The last line of standard output is the result (JSON); the numbers that
decided ``correct`` are the last lines of standard error. Without a TPU
the child exits 3 and this prints no result (``--rehearse-cpu`` runs the
whole path on the CPU at the program's reduced size, for tests: it
reports ``platform: cpu`` and no device metric).
"""
from __future__ import annotations

import argparse
import asyncio
import importlib.util
import json
import queue
import random
import subprocess
import sys
import threading
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

sys.path.insert(0, str(Path(__file__).resolve().parent))

import client                                    # noqa: E402
import stats                                     # noqa: E402
import traffic as traffic_gen                    # noqa: E402
from arch import resolve                         # noqa: E402
from common import BENCH_DIR, ROOT, log, peaks, resolve_cell   # noqa: E402

CHILD_START_TIMEOUT_S = 900.0     # first run of a cell compiles
WARMUP_FIRST_TOKEN_TIMEOUT_S = 300.0
DRAIN_S = 60.0            # after the window, for its first tokens


class ChildFailed(RuntimeError):
    pass


class ChildProc:
    """The chip-holding child and its line protocol."""

    def __init__(self, spec: Dict[str, Any]):
        self.proc = subprocess.Popen(
            [sys.executable, "-u", str(BENCH_DIR / "child.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=str(ROOT))
        self.replies: "queue.Queue[Optional[dict]]" = queue.Queue()
        threading.Thread(target=self._read, daemon=True).start()
        self.send(spec)

    def _read(self):
        for line in self.proc.stdout:
            if line.startswith("@@"):
                self.replies.put(json.loads(line[2:]))
        self.replies.put(None)

    def send(self, obj: Dict[str, Any]) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def expect(self, kind: str, timeout: float) -> Dict[str, Any]:
        try:
            msg = self.replies.get(timeout=timeout)
        except queue.Empty:
            raise ChildFailed(f"no {kind!r} from the child in {timeout} s")
        if msg is None:
            raise ChildFailed(f"child exited ({self.proc.wait()}) before "
                              f"{kind!r}")
        if msg["msg"] != kind:
            raise ChildFailed(f"child sent {msg['msg']!r}, wanted {kind!r}")
        return msg

    async def aexpect(self, kind: str, timeout: float) -> Dict[str, Any]:
        return await asyncio.get_running_loop().run_in_executor(
            None, self.expect, kind, timeout)

    def close(self) -> int:
        """Wait for the child to end (killing it if it will not)."""
        try:
            self.proc.stdin.close()
        except OSError:
            pass
        try:
            return self.proc.wait(timeout=120)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            return self.proc.wait()


# ------------------------------------------------------------------ phases
async def warm_up(drv: client.Driver, stages) -> None:
    """Each stage's groups in turn: submit a group, wait for all of its
    first tokens, go on; a decode stage is cut once its last group is in
    (its requests only exist to fill the batch), a prefill stage runs out."""
    for groups in stages:
        t0 = time.monotonic()
        streams = []
        for g in groups:
            batch = client.to_streams(g)
            for s in batch:
                drv.start(s)
            streams += batch
            await drv.wait_first(batch, WARMUP_FIRST_TOKEN_TIMEOUT_S)
        if any(s.max_tokens > 1 for s in streams):
            await asyncio.sleep(0.3)
            await drv.cancel(streams)
            await asyncio.sleep(0.2)
        else:
            await drv.wait_done(streams, WARMUP_FIRST_TOKEN_TIMEOUT_S)
        bad = [s.error for s in streams if s.error and "Cancel" not in s.error]
        if bad:
            raise ChildFailed(f"warm-up request failed: {bad[0]}")
        log(f"chipbench: warm-up stage of {len(streams)} requests "
            f"({[len(g[0]['prompt_ids']) for g in groups][:2]}...) took "
            f"{time.monotonic() - t0:.3f} s")


def pick_sample(streams: List[client.Stream], rotated: set, seed: int,
                want: Dict[str, int]) -> List[client.Stream]:
    """The requests the reference checks: the longest finished one, up to
    half of the rest from those that were rotated out of HBM, the others
    drawn from the seed, until ``min_tokens`` served tokens or
    ``max_requests`` requests."""
    done = [s for s in streams if s.finish_reason == "length"]
    if not done:
        return []
    done.sort(key=lambda s: (len(s.prompt_ids) + s.max_tokens, s.due))
    rng = random.Random(seed)
    pick = [done.pop()]
    rot = [s for s in done if s.req_id in rotated]
    rest = [s for s in done if s.req_id not in rotated]
    rng.shuffle(rot)
    rng.shuffle(rest)
    n_rot = want["max_requests"] // 2
    for s in rot[:n_rot] + rest + rot[n_rot:]:
        if (len(pick) >= want["max_requests"]
                or sum(len(p.token_ids) for p in pick) >= want["min_tokens"]):
            break
        pick.append(s)
    return pick


def load_reader(name: str):
    path = BENCH_DIR / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


async def drive(args, cell, child: ChildProc, t_spawn: float):
    cfg, tr, st = cell["config"], cell["traffic"], cell["settings"]
    rehearse = args.rehearse_cpu
    wcfg = dict(cfg, **cfg["rehearsal"]) if rehearse else cfg
    if rehearse:
        tr = dict(tr, **tr["rehearsal"])
    if args.rate:
        tr = dict(tr, rate_rps=args.rate)
    vocab = int(wcfg["vocab_size"])
    ready = await child.aexpect("ready", CHILD_START_TIMEOUT_S)
    drv = client.Driver(ready["port"])
    log(f"chipbench: server up after {time.monotonic() - t_spawn:.3f} s, "
        f"pool {ready['hbm_blocks']} blocks")
    if not rehearse:
        stages = traffic_gen.warmup_plan(tr, st, args.seed, vocab)
        child.send(dict(cmd="phase", phase="warmup"))
        t = time.monotonic()
        await warm_up(drv, stages)
        log(f"chipbench: warm-up of {len(stages)} stages took "
            f"{time.monotonic() - t:.3f} s")
    plan = traffic_gen.schedule(tr, args.seed, args.seconds, vocab)
    pre, win = (client.to_streams(plan["preroll"]),
                client.to_streams(plan["window"]))
    offsets = [r["due"] for r in plan["preroll"] + plan["window"]]
    child.send(dict(cmd="phase", phase="preroll"))
    t0 = time.monotonic() + 0.2
    w0 = t0 + plan["window_start"]
    w1 = w0 + args.seconds
    sender = asyncio.ensure_future(drv.open_loop(pre + win, t0, offsets))
    if args.trace:
        await asyncio.sleep(max(w0 - 2.0 - time.monotonic(), 0))
        child.send(dict(cmd="trace_start"))
    await asyncio.sleep(max(w0 - time.monotonic(), 0))
    setup_s = time.monotonic() - t_spawn
    child.send(dict(cmd="mark", at="window"))
    m0 = await child.aexpect("mark", 60)
    await asyncio.sleep(max(w1 - time.monotonic(), 0))
    child.send(dict(cmd="mark", at="drain"))
    m1 = await child.aexpect("mark", 60)
    await sender
    deadline = time.monotonic() + DRAIN_S
    await drv.wait_first(win, DRAIN_S)
    drain_end = time.monotonic()
    want = (st["rehearsal"] if rehearse else st)["sample"]["min_tokens"]
    while (time.monotonic() < deadline and sum(
            len(s.token_ids) for s in pre + win if s.finished) < 2 * want):
        await asyncio.sleep(0.1)          # finished requests to check
    await drv.cancel()
    e2e = stats.end_to_end(win, pre + win, w0, w1, drain_end,
                           tr["slo_ttft_s"])
    waiting = [sum(1 for s in pre + win if s.due <= t and
                   (s.first is None or s.first > t)) for t in (w0, w1)]
    log(f"chipbench: rate {tr['rate_rps']} /s; requests due and waiting for "
        f"a first token at the window's start and end: {waiting}")
    late = [s.sent - s.due for s in win if s.sent is not None]
    log(f"chipbench: generator lateness {stats.quantiles(late)} s over "
        f"{len(late)} sends")
    sample = pick_sample(pre + win, set(m1["rotated"]), args.seed,
                         st["rehearsal"]["sample"] if rehearse
                         else st["sample"])
    child.send(dict(cmd="finish", samples=[
        dict(prompt_ids=s.prompt_ids, token_ids=s.token_ids)
        for s in sample]))
    res = await child.aexpect("result", 1800)
    return dict(e2e=e2e, setup_s=setup_s, m0=m0, m1=m1, res=res, win=win,
                all=pre + win, sample=sample, rotated=set(m1["rotated"]),
                w0=w0, w1=w1, wcfg=wcfg)


def report(args, cell, device, out) -> Dict[str, Any]:
    """The result line and the check lines."""
    res, e2e, st = out["res"], out["e2e"], cell["settings"]
    limit = float((st["rehearsal"] if args.rehearse_cpu else st)
                  ["correct"]["max_logit_gap"])
    # the control's tokens stand in the served tokens' place: its run is
    # judged by the same comparison, and has to come out not correct
    key = "control" if args.control else "served"
    per_req = [max(g[key]) for g in res["gaps"]]
    widest = max(per_req) if per_req else None
    n_tok = sum(len(g[key]) for g in res["gaps"])
    malformed = [s for s in out["sample"]
                 if len(s.token_ids) != s.max_tokens
                 or not all(0 <= t < int(out["wcfg"]["vocab_size"])
                            for t in s.token_ids)]
    name = "control_widest_logit_gap" if args.control else "widest_logit_gap"
    checks = {name: dict(value=widest, limit=limit),
              "sampled_tokens": dict(value=n_tok, limit=1),
              "malformed_streams": dict(value=len(malformed), limit=0)}
    correct = (widest is not None and widest <= limit and n_tok >= 1
               and not malformed)
    n_rot = sum(1 for s in out["sample"] if s.req_id in out["rotated"])
    served = [round(max(g["served"]), 6) for g in res["gaps"]]
    log(f"chipbench: sample of {len(out['sample'])} requests, {n_tok} served "
        f"tokens, {n_rot} of them rotated out of HBM; widest gap per request "
        f"{served}" + (f", the int8 control's {[round(x, 6) for x in per_req]}"
                       if args.control else ""))
    failed = sum(1 for s in out["win"] if s.first is None)
    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace:
        ctx = context(args, cell, device, out)
        for m in cell["per_layer"]:
            v = load_reader(m["name"])(ctx)
            if v is not None:
                metrics[m["name"]] = dict(value=v, unit=m["unit"])
    else:
        vals = dict(e2e, setup_s=out["setup_s"])
        for m in cell["end_to_end"]:
            if m["name"] in vals:            # no gaps: a CPU rehearsal
                metrics[m["name"]] = dict(value=vals[m["name"]],
                                          unit=m["unit"])
    dev = dict(device, memory_peak_bytes=res["memory_peak_bytes"])
    line = dict(correct=bool(correct), attempted=len(out["win"]),
                failed=failed, metrics=metrics, device=dev)
    trace = res.get("trace")
    if args.trace and trace and device["platform"] == "tpu":
        dev.update(busy_s=trace["busy_s"], window_s=trace["window_s"])
        line["breakdown"] = dict(device_ops=trace["device_ops"],
                                 idle_gaps=trace["idle_gaps"])
    line["checks"] = checks
    return line


def context(args, cell, device, out) -> Dict[str, Any]:
    """What the per-layer readers read: the configuration's architecture
    module (``arch``) and its sizes (``dims``), program counters at the
    window's edges, the trace's reduction (a chip run only), and the
    client's token counts and context lengths inside the window."""
    arch = resolve(out["wcfg"])
    w0, w1 = out["w0"], out["w1"]
    decode_ctx, prompts = [], []
    for s in out["all"]:
        j = 0
        for t, n in s.events:
            for _ in range(n):
                if j > 0 and w0 <= t < w1:
                    decode_ctx.append(len(s.prompt_ids) + j)
                elif j == 0 and w0 <= t < w1:
                    prompts.append(len(s.prompt_ids))
                j += 1
    on_chip = device["platform"] == "tpu"
    return dict(arch=arch, dims=arch.dims(out["wcfg"]),
                c0=out["m0"], c1=out["m1"],
                window_s=out["m1"]["t"] - out["m0"]["t"],
                trace=out["res"].get("trace") if on_chip else None,
                peaks=peaks(device["kind"]) if on_chip else None,
                decode_ctx=decode_ctx, first_token_prompts=prompts)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="run on the CPU at the reduced size (tests only)")
    ap.add_argument("--control", action="store_true",
                    help="judge the int8 control's tokens in place of the "
                    "served ones (a run that has to come out not correct)")
    ap.add_argument("--fault", choices=("token",),
                    help="break the timed path (tests of the check)")
    ap.add_argument("--rate", type=float,
                    help="offer this rate instead of the mix's (a sweep)")
    args = ap.parse_args(argv)
    cell = resolve_cell(args.workload)
    resolve(cell["config"])          # an unknown model_type: no child, no run
    t_spawn = time.monotonic()
    spec = dict(config=cell["config"], settings=cell["settings"],
                seed=args.seed, trace=args.trace,
                chips=cell["entry"]["chips"],
                rehearse_cpu=args.rehearse_cpu, control=args.control,
                fault=args.fault)
    child = ChildProc(spec)
    try:
        device = child.expect("device", CHILD_START_TIMEOUT_S)
        out = asyncio.run(drive(args, cell, child, t_spawn))
    except Exception as e:                  # no result without a whole run
        log(f"chipbench: {e!r}; no result")
        child.proc.kill()
        child.close()
        if not isinstance(e, ChildFailed):
            raise
        return 1
    code = child.close()
    if code != 0:
        log(f"chipbench: child exited {code}; no result")
        return 1
    device = dict(platform=device["platform"], kind=device["kind"],
                  count=device["count"])
    line = report(args, cell, device, out)
    res = out["res"]
    window_new = sorted(set(res["shapes"].get("window", {}))
                        - set(res["shapes"].get("warmup", {}))
                        - set(res["shapes"].get("preroll", {})))
    m0, m1 = out["m0"], out["m1"]
    log("chipbench: program counters over the window: " + ", ".join(
        f"{k} {m1[k] - m0[k]}" for k in (
            "iterations", "active_rotations", "passive_preemptions",
            "prefill_tokens", "decode_tokens", "d2h_rows", "h2d_rows",
            "d2d_rows") if m1.get(k) is not None))
    log(f"chipbench: padded buckets met by phase: "
        f"{json.dumps(res['shapes'], sort_keys=True)}")
    c0, c1 = out["m0"]["compile"], out["m1"]["compile"]
    hits = c1["cache_hits"] - c0["cache_hits"]
    p0, p1 = c0.pop("programs"), c1.pop("programs")
    res["compile"].pop("programs")
    log(f"chipbench: in the window: {c1['traces'] - c0['traces']} traces, "
        f"{c1['compiles'] - c0['compiles'] - hits} compiles, "
        f"{hits} persistent-cache loads "
        f"{ {k: n - p0.get(k, 0) for k, n in p1.items() if n > p0.get(k, 0)} }"
        f"; shapes not met before it: {window_new}; whole run: "
        f"{res['compile']}")
    log(f"chipbench: client numbers {json.dumps(out['e2e'])}; setup_s "
        f"{out['setup_s']:.6f}")
    for k, v in line["checks"].items():
        log(f"check {k} = {v['value']} (limit {v['limit']})")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
