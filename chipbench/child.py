"""The process that holds the chip: it serves the program's own HTTP entry
point (``serving.server.serve_main``) on an ephemeral port with the
benchmark's weights, answers the load generator's commands (counter
snapshots, trace start and stop), and once the window has closed frees the
program and runs the reference.

Protocol: the first line on standard input is the run's spec (JSON); each
further line is a command. Replies go to standard output as lines that
start with ``@@``; everything else a library prints is left to the parent
to ignore. Exit code 3 means no chip (or too few chips) was found.
"""
from __future__ import annotations

import asyncio
import functools
import gc
import json
import shutil
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent))

from arch import resolve                          # noqa: E402
from common import SRC, log                       # noqa: E402

RESERVE_BYTES = 2 << 30      # HBM left free of the pool: activations, temps


def emit(msg: str, **kw) -> None:
    print("@@" + json.dumps(dict(kw, msg=msg)), flush=True)


def fill_blocks(arch, cfg, bytes_limit: int) -> int:
    """Pool blocks that fill the chip's memory the weights and the reserve
    leave (a ``"fill"`` pool)."""
    m = arch.dims(cfg)
    return int((bytes_limit - arch.weight_bytes(m) - RESERVE_BYTES)
               // arch.row_bytes(m))


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


class CompileMeter:
    """Traces, backend compiles and persistent-cache hits, counted from
    ``jax.monitoring`` events (the engine compiles on its driver thread).
    JAX records a backend-compile event for a cache hit too, so the
    compiles that missed the cache are ``compiles - cache_hits``."""

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.n = dict(traces=0, compiles=0, cache_hits=0, seconds=0.0)
        self.programs = {}             # backend compiles (or loads) by name
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, fun_name="?", **_):
        with self._lock:
            if event == "/jax/core/compile/jaxpr_trace_duration":
                self.n["traces"] += 1
            elif event == "/jax/core/compile/backend_compile_duration":
                self.n["compiles"] += 1
                self.programs[fun_name] = self.programs.get(fun_name, 0) + 1
            else:
                return
            self.n["seconds"] += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.n["cache_hits"] += 1

    def snapshot(self):
        with self._lock:
            return dict(self.n, programs=dict(self.programs))


def _wrap(cls, name: str, make):
    """Replace ``cls.name`` by ``make(original)``; a missing target is
    skipped (its time then reads as "other")."""
    orig = getattr(cls, name, None)
    if orig is None:
        log(f"chipbench: {cls.__name__}.{name} not found; not wrapped")
        return
    setattr(cls, name, functools.wraps(orig)(make(orig)))


class Child:
    def __init__(self, spec, meter):
        self.spec = spec
        self.meter = meter
        self.cfg = spec["config"]
        self.arch = resolve(self.cfg)
        self.rehearse = spec["rehearse_cpu"]
        self.wcfg = dict(self.cfg, **self.cfg["rehearsal"]) if self.rehearse \
            else self.cfg
        self.shapes = {}                 # phase -> {bucket: count}
        self.phase = "setup"
        self.rotated = set()
        self.trace_dir = None
        self.window_span = None
        self._install()

    # ---------------------------------------------------------- wrappers
    def _install(self):
        import jax
        from repro.core.duplexkv import DuplexKV
        from repro.core.types import Request
        from repro.serving.core import EngineCore
        from repro.serving.paged_runner import PagedKVStore, PagedModelRunner
        child = self

        def rotate_out(orig):
            def f(req, *a, **k):
                child.rotated.add(req.req_id)
                return orig(req, *a, **k)
            return f
        _wrap(Request, "rotate_out", rotate_out)

        def execute(orig):
            def f(runner, plan, requests):
                child._record(plan, requests)
                out = orig(runner, plan, requests)
                if child.spec.get("fault") == "token" and out.tokens:
                    v = child.wcfg["vocab_size"]
                    out.tokens = {r: (t + 1) % v for r, t in
                                  out.tokens.items()}
                return out
            return f
        _wrap(PagedModelRunner, "execute", execute)

        if self.spec["trace"]:
            def annotate(label):
                def make(orig):
                    def f(*a, **k):
                        with jax.profiler.TraceAnnotation(label):
                            return orig(*a, **k)
                    return f
                return make
            for cls, name in ((EngineCore, "step"),
                              (DuplexKV, "plan_iteration"),
                              (PagedKVStore, "run_d2h"),
                              (PagedKVStore, "run_h2d"),
                              (PagedModelRunner, "execute")):
                _wrap(cls, name, annotate(f"{cls.__name__}.{name}"))

    def _record(self, plan, requests):
        """Which padded buckets each iteration meets, by phase (a check that
        the warm-up covered the window; not a metric)."""
        seen = self.shapes.setdefault(self.phase, {})
        dec = [requests[r] for r in plan.decode_reqs if r in requests]
        if dec:
            key = "d%dx%d" % (_pow2(len(dec)), _pow2(max(
                -(-r.total_len // 16) for r in dec)))
            seen[key] = seen.get(key, 0) + 1
        for rid, take in plan.prefill_chunks:
            r = requests.get(rid)
            if r is None:
                continue
            take = min(take, r.prompt_len - r.prefill_pos)
            key = "p%dx%d" % (_pow2(take),
                              _pow2(-(-(r.prefill_pos + take) // 16)))
            seen[key] = seen.get(key, 0) + 1

    # ------------------------------------------------------------- server
    def pool_blocks(self) -> int:
        s = self.spec["settings"]
        if self.rehearse:
            return int(s["rehearsal"]["hbm_blocks"])
        if s["hbm_blocks"] != "fill":
            return int(s["hbm_blocks"])
        import jax
        return fill_blocks(self.arch, self.cfg,
                           jax.devices()[0].memory_stats()["bytes_limit"])

    def inject(self, eng):
        """Serve the benchmark's weights: drop the ones the runner made,
        make ours from the seed in one jitted call, check every leaf's shape
        and dtype against what the runner held, and hand them over."""
        import weights
        run = eng.executor

        def sig(tree):
            return [{k: (tuple(v.shape), str(v.dtype)) for k, v in d.items()}
                    for d in tree]
        want = sig(run._layers) + sig([run._head])
        run._layers = run._head = None
        gc.collect()
        layers, head = weights.make(self.wcfg, self.spec["seed"])
        got = sig(layers) + sig([head])
        if got != want:
            raise RuntimeError(f"benchmark weights do not match the runner's:"
                               f" {got[:1]} vs {want[:1]}")
        run._layers, run._head = layers, head

    @staticmethod
    def warm_store(eng):
        """The KV store's own small programs meet sizes that the traffic
        draws at random: a staging readback per D2H chunk length, a row copy
        and an upload per pow2 chunk. Run each once before any request, on
        rows no request holds (staging; padded copy lanes aimed at the trash
        row, as the store pads them), so that none compiles in the window."""
        import jax.numpy as jnp
        import numpy as np
        store = getattr(eng.executor, "store", None)
        if store is None or store.quantized:
            return
        for n in range(1, store.d2h_chunk + 1):
            np.asarray(store.pool[store.nb:store.nb + n])
        k = 1
        while k <= max(store.d2h_chunk, store.h2d_chunk):
            store._copy_rows([-1] * k, [store.trash_row] * k)
            if k <= store.h2d_chunk:
                store.pool = store._jit_upload(
                    store.pool, jnp.zeros((k,) + store.row_shape,
                                          store.pool.dtype),
                    jnp.asarray(store.h2d_base, np.int32))
            k *= 2
        store.pool.block_until_ready()

    def counters(self, eng):
        st, run = eng.stats, eng.executor
        store = getattr(run, "store", None)
        out = dict(t=time.monotonic(), compile=self.meter.snapshot(),
                   rotated=sorted(self.rotated))
        for k in ("iterations", "active_rotations", "passive_preemptions",
                  "prefill_tokens"):
            out[k] = getattr(st, k, None)
        for k in self.arch.RUNNER_COUNTERS:
            out[k] = getattr(run, k, None)
        for k in ("d2h_rows", "h2d_rows", "d2d_rows", "copy_launches"):
            out[k] = getattr(store, k, None)
        # the program's host spans and queue wait (None where the engine
        # has no flight recorder, or one without them)
        tel = getattr(eng, "telemetry", None)
        out["host"] = (tel.host_counters() if hasattr(tel, "host_counters")
                       else None)
        return out

    async def run(self) -> int:
        from repro.serving.server import ServerConfig, serve_main
        hbm = self.pool_blocks()
        scfg = ServerConfig(
            port=0, model=self.cfg["program_model"], hw="tpu-v5e",
            paged_runner=True, hbm_blocks=hbm, pace=False,
            seed=self.spec["seed"] % (1 << 31),
            **self.arch.server_kwargs(self.cfg, self.rehearse)).validate()
        box, ready = {}, asyncio.Event()

        def on_ready(server, service):
            box.update(server=server, service=service)
            ready.set()

        task = asyncio.create_task(serve_main(scfg, install_signals=False,
                                              ready_cb=on_ready))
        waiter = asyncio.create_task(ready.wait())
        await asyncio.wait({task, waiter},
                           return_when=asyncio.FIRST_COMPLETED)
        if not ready.is_set():
            waiter.cancel()
            task.result()
            raise RuntimeError("server exited during start-up")
        server, service = box["server"], box["service"]
        await service.call(self.inject)
        await service.call(self.warm_store)
        emit("ready", port=server.port, hbm_blocks=hbm)
        loop = asyncio.get_running_loop()
        cmds: asyncio.Queue = asyncio.Queue()

        def read_stdin():
            try:
                for line in sys.stdin:
                    loop.call_soon_threadsafe(cmds.put_nowait,
                                              json.loads(line))
                loop.call_soon_threadsafe(cmds.put_nowait, None)
            except RuntimeError:          # the loop closed: run is over
                pass
        threading.Thread(target=read_stdin, daemon=True).start()
        while True:
            cmd = await cmds.get()
            if cmd is None:                       # parent went away
                server.request_shutdown()
                await task
                return 1
            op = cmd["cmd"]
            if op == "phase":
                self.phase = cmd["phase"]
            elif op == "trace_start":
                await loop.run_in_executor(None, self._trace_start)
            elif op == "mark":
                snap = await service.call(self.counters)
                self._window_span(cmd["at"])
                self.phase = cmd["at"]
                emit("mark", **snap)
                if cmd["at"] == "drain" and self.trace_dir:
                    stopping = loop.run_in_executor(None, self._trace_stop)
            elif op == "finish":
                if self.trace_dir:
                    await stopping
                res = await self.finish(server, service, task, cmd)
                emit("result", **res)
                return 0

    def _trace_start(self):
        import jax
        self.trace_dir = tempfile.mkdtemp(prefix="chipbench_trace_")
        # no Python tracer (it records every call, slows the host it is
        # meant to observe and bloats the trace), no HLO protos, and host
        # events at the level of the TraceAnnotation spans only (nothing
        # reads the runtime's own, and they lengthen stopping the trace)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        opts.enable_hlo_proto = False
        jax.profiler.start_trace(self.trace_dir, profiler_options=opts)

    def _trace_stop(self):
        import jax
        t0 = time.monotonic()
        jax.profiler.stop_trace()
        log(f"chipbench: trace stopped in {time.monotonic() - t0:.3f} s")

    def _window_span(self, at):
        import jax
        if not self.spec["trace"]:
            return
        if at == "window":
            self.window_span = jax.profiler.TraceAnnotation(
                "chipbench.window")
            self.window_span.__enter__()
        elif self.window_span is not None:
            self.window_span.__exit__(None, None, None)
            self.window_span = None

    # ------------------------------------------------------------- finish
    async def finish(self, server, service, task, cmd):
        import jax
        summary = None
        peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in jax.local_devices())
        server.request_shutdown()
        code = await task
        run = service.engine.executor       # free the program's HBM
        run._layers = run._head = None
        if run.store is not None:
            run.store.pool = run.store.scales = None
            run.store.host.clear()
        gc.collect()
        if self.trace_dir:
            summary = self._reduce()
        gaps = self.reference(cmd["samples"])
        return dict(memory_peak_bytes=peak, server_exit=code, trace=summary,
                    gaps=gaps, shapes=self.shapes,
                    compile=self.meter.snapshot(),
                    in_use_after_release=(jax.local_devices()[0].memory_stats()
                                          or {}).get("bytes_in_use"))

    def _reduce(self):
        import trace_reduce
        t0 = time.monotonic()
        paths = sorted(Path(self.trace_dir).rglob("*.xplane.pb"))
        summary = (trace_reduce.reduce(trace_reduce.extract(str(paths[-1])),
                                       kernels=self.arch.KERNELS)
                   if paths else None)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        log(f"chipbench: trace read and reduced in "
            f"{time.monotonic() - t0:.3f} s")
        return summary

    def reference(self, samples):
        """The reference's gaps for each sampled request, from weights it
        makes again from the seed (after the program's are freed)."""
        import reference
        import weights
        layers, head = weights.make(self.wcfg, self.spec["seed"])
        ref = self.spec["settings"]["reference"]
        if self.rehearse:
            ref = self.spec["settings"]["rehearsal"]["reference"]
        out = []
        t0 = time.monotonic()
        for s in samples:
            out.append(reference.gaps(
                self.wcfg, layers, head, s["prompt_ids"], s["token_ids"],
                pad_len=ref["pad_len"], n_out=ref["n_out"],
                control=bool(self.spec.get("control")),
                forward=self.arch.logits))
        log(f"chipbench: reference over {len(samples)} requests took "
            f"{time.monotonic() - t0:.3f} s (host clock)")
        return out


def main() -> int:
    spec = json.loads(sys.stdin.readline())
    sys.path.insert(0, str(SRC))
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" and not spec["rehearse_cpu"]:
        log(f"chipbench: no TPU (JAX found {devs[0].platform}); no result")
        return 3
    if len(devs) < spec["chips"]:
        log(f"chipbench: cell needs {spec['chips']} chips, JAX found "
            f"{len(devs)}; no result")
        return 3
    from repro.launch.hostenv import enable_compile_cache
    enable_compile_cache()
    # cache every program, however fast it compiles: a program first met
    # inside the window must load, not compile
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    meter = CompileMeter()
    emit("device", platform=devs[0].platform, kind=devs[0].device_kind,
         count=len(devs))
    return asyncio.run(Child(spec, meter).run())


if __name__ == "__main__":
    sys.exit(main())
