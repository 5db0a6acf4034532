#!/usr/bin/env python3
"""On-chip smoke test: the real serving path at qwen2.5-32b's published widths.

    python chip_smoke.py                # one TPU chip: phases A-D
    python chip_smoke.py --four-chips   # v5e 2x2: tp=4 against tp=1 only

Runs in ONE process (a chip belongs to one process at a time) and drives the
entry points a user calls: ``ServerConfig.build_engine`` with the paged
runner at ``runner_layers=6`` (every width as published, bf16, random
weights from ``--seed``), the asyncio HTTP server of ``serving/server.py``
on an ephemeral port, and the engine's own API.

* A  8 concurrent ``/v1/generate`` streams (prompts of 256-2048 seeded
     ids, 64 tokens each, two SLO classes) on a KV pool that fills the HBM
     the weights leave. Every stream must end ``length`` with 64 tokens,
     and the pool must hold only finite K/V afterwards.
* B  the same requests on a 256-block pool with the pipelined engine, so
     DuplexKV rotation moves rows through the host tier; prints the share
     of tokens that agree with A.
* C  the store's D2H -> host tier -> H2D and D2D paths on a padded copy
     batch, bf16 and int8 (values and scales): moved rows come back
     bit-exact, every other row (row 0 included) keeps its bits.
* D  4 requests on the int8 KV tier, run to completion.

Times are host-clock seconds (compile seconds counted apart), not device
metrics. Exits non-zero without a result line when JAX finds no TPU, when
the repository is not beside this file, or when any phase fails. The last
line of stdout is ``{"ok": true, "device": {...}}``.
"""
from __future__ import annotations

import argparse
import asyncio
import dataclasses
import gc
import json
import sys
import threading
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

MODEL = "qwen2.5-32b"
RUNNER_LAYERS = 6
MAX_TOKENS = 64
PROMPT_LENS = (256, 512, 768, 1024, 1280, 1536, 1792, 2048)
SLO_CLASSES = ("interactive", "batch")
RESERVE_BYTES = 2 << 30        # HBM kept free of the pool: activations, temps
ROTATION_BLOCKS = 256          # phase B pool: forces rotation
STREAM_TIMEOUT_S = 600.0       # per phase: a stuck engine fails, not hangs


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


class CompileMeter:
    """Host seconds JAX spends tracing, lowering and compiling (or fetching
    from the persistent cache), summed from ``jax.monitoring`` events; the
    engine compiles on its driver thread, hence the lock."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self._lock = threading.Lock()
        self.seconds = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event in self.EVENTS:
            with self._lock:
                self.seconds += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with self._lock:
                self.cache_hits += 1


@dataclasses.dataclass
class Smoke:
    """What the phases share: the executed model, its requests and pool
    sizes, and the meters."""
    model: str
    runner_layers: int
    prompt_lens: tuple
    max_tokens: int
    seed: int
    meter: CompileMeter

    def __post_init__(self):
        import numpy as np
        from repro.configs import get_config, runner_config
        from repro.core.duplexkv import block_bytes_of
        self.cfg = runner_config(get_config(self.model), self.runner_layers)
        rng = np.random.default_rng(self.seed)
        lens = rng.permutation(np.asarray(self.prompt_lens))
        self.bodies = [
            {"prompt_ids": [int(t) for t in
                            rng.integers(1, self.cfg.vocab_size, int(n))],
             "max_tokens": self.max_tokens,
             "slo_class": SLO_CLASSES[i % len(SLO_CLASSES)]}
            for i, n in enumerate(lens)]
        self.block_bytes = {kv: block_bytes_of(self.cfg, 16, kv_dtype=kv)[0]
                            for kv in ("bf16", "int8")}

    def pool_blocks(self, kv_dtype: str) -> int:
        """Blocks that fill the HBM the weights leave on device 0."""
        import jax
        stats = jax.devices()[0].memory_stats() or {}
        limit = stats.get("bytes_limit")
        check(limit is not None, "device reports no bytes_limit")
        weights = self.cfg.param_count() * self.cfg.dtype_bytes()
        free = limit - weights - RESERVE_BYTES
        check(free > 0, f"weights ({weights / 1e9:.2f} GB) leave no HBM")
        return int(free // self.block_bytes[kv_dtype])

    def server_config(self, **kw):
        from repro.serving.server import ServerConfig
        base = dict(port=0, model=self.model, hw="tpu-v5e",
                    paged_runner=True, runner_layers=self.runner_layers,
                    pace=False, seed=self.seed)
        base.update(kw)
        return ServerConfig(**base).validate()


def memory_line() -> str:
    import jax
    parts = []
    for d in jax.local_devices():
        s = d.memory_stats() or {}
        parts.append(f"dev{d.id} peak_bytes_in_use={s.get('peak_bytes_in_use')}"
                     f" bytes_in_use={s.get('bytes_in_use')}")
    return " ".join(parts)


def release(engine) -> None:
    """Drop a finished engine's weights, pool and host tier before the next
    phase builds its own: two copies of the weights do not fit one chip."""
    from repro.serving.server import engine_cores
    for core in engine_cores(engine):
        runner = core.executor
        runner._layers = runner._head = None
        if runner.store is not None:
            runner.store.pool = runner.store.scales = None
            runner.store.host.clear()
    gc.collect()


# ------------------------------------------------------------------- HTTP
async def _generate(port: int, body: dict) -> dict:
    """POST /v1/generate and read the SSE stream to its end; returns the
    final event."""
    reader, writer = await asyncio.open_connection("127.0.0.1", port)
    payload = json.dumps(body).encode()
    writer.write(f"POST /v1/generate HTTP/1.1\r\nHost: smoke\r\n"
                 f"Content-Type: application/json\r\n"
                 f"Content-Length: {len(payload)}\r\n\r\n".encode() + payload)
    await writer.drain()
    raw = await reader.read()
    writer.close()
    await writer.wait_closed()
    head, _, rest = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    check(status == 200, f"/v1/generate answered {status}: {rest[:200]!r}")
    events, i = [], 0
    while (s := rest.find(b"data: ", i)) != -1:
        e = rest.find(b"\n\n", s)
        events.append(json.loads(rest[s + 6:e]))
        i = e + 2
    check(bool(events) and events[-1]["finished"],
          f"stream ended without a final event ({len(events)} events)")
    return events[-1]


def serve_requests(smoke: Smoke, label: str, scfg, bodies, *,
                   check_pool=False):
    """Serve ``bodies`` over HTTP with a fresh server built from ``scfg``,
    check every stream, release the engine; returns (streams, stats)."""
    import jax
    import jax.numpy as jnp
    from repro.serving.server import engine_cores, serve_main

    async def run(box):
        ready = asyncio.Event()

        def on_ready(server, service):
            box.update(server=server, service=service)
            ready.set()

        c0 = smoke.meter.seconds
        t0 = time.perf_counter()
        task = asyncio.create_task(
            serve_main(scfg, install_signals=False, ready_cb=on_ready))
        waiter = asyncio.create_task(ready.wait())
        await asyncio.wait({task, waiter},
                           return_when=asyncio.FIRST_COMPLETED)
        if not ready.is_set():
            waiter.cancel()
            task.result()                   # raises the startup failure
            raise SmokeFailure(f"{label}: server exited during startup")
        build_s = time.perf_counter() - t0
        try:
            finals = await asyncio.wait_for(asyncio.gather(
                *[_generate(box["server"].port, b) for b in bodies]),
                STREAM_TIMEOUT_S)
            finite = None
            if check_pool:              # jitted: fused, no pool-sized temp
                all_finite = jax.jit(lambda p: jnp.all(jnp.isfinite(p)))
                finite = await box["service"].call(lambda eng: bool(
                    all_finite(engine_cores(eng)[0].executor.store.pool)))
        finally:
            box["server"].request_shutdown()
            code = await task
        return finals, dict(
            wall_s=time.perf_counter() - t0, build_s=build_s,
            compile_s=smoke.meter.seconds - c0, exit_code=code,
            pool_finite=finite)

    box = {}
    try:
        finals, st = asyncio.run(run(box))
        cores = engine_cores(box["service"].engine)
        stores = [c.executor.store for c in cores]
        st.update(rows_moved=sum(s.d2h_rows + s.h2d_rows + s.d2d_rows
                                 for s in stores),
                  pool_blocks=cores[0].kv.table.num_hbm_blocks,
                  pool_shard_bytes=[(sh.device.id, sh.data.nbytes) for sh in
                                    stores[0].pool.addressable_shards])
    finally:
        if "service" in box:
            release(box.pop("service").engine)
        box.clear()
        gc.collect()
    check(st["exit_code"] == 0, f"{label}: server drain exit {st['exit_code']}")
    streams = []
    for body, f in zip(bodies, finals):
        check(f["finish_reason"] == "length",
              f"{label}: finish_reason {f['finish_reason']!r}")
        ids = f["token_ids"]
        check(len(ids) == body["max_tokens"],
              f"{label}: {len(ids)} tokens, wanted {body['max_tokens']}")
        check(all(0 <= t < smoke.cfg.vocab_size for t in ids),
              f"{label}: token id outside the vocabulary")
        streams.append(ids)
    st["tokens"] = sum(map(len, streams))
    print(f"phase {label}: host-clock wall_s={st['wall_s']:.3f} "
          f"(build_s={st['build_s']:.3f}, compile_s={st['compile_s']:.3f}, "
          f"persistent-cache hits so far={smoke.meter.cache_hits}) "
          f"kv_dtype={scfg.kv_dtype} tp={scfg.tp} requests={len(streams)} "
          f"tokens={st['tokens']} pool_blocks={st['pool_blocks']} "
          f"kv_rows_moved={st['rows_moved']} pool_finite={st['pool_finite']}"
          f" {memory_line()}", flush=True)
    if check_pool:
        check(st["pool_finite"], f"{label}: non-finite K/V in the pool")
    return streams, st


def agreement(a, b) -> float:
    """Share of b's tokens equal to a's at the same request and position."""
    same = sum(x == y for sa, sb in zip(a, b) for x, y in zip(sa, sb))
    return same / max(sum(map(len, b)), 1)


# ----------------------------------------------------------------- phases
def phase_a(smoke: Smoke):
    streams, _ = serve_requests(
        smoke, "A", smoke.server_config(hbm_blocks=smoke.pool_blocks("bf16")),
        smoke.bodies, check_pool=True)
    return streams


def phase_b(smoke: Smoke, streams_a):
    streams, st = serve_requests(
        smoke, "B", smoke.server_config(hbm_blocks=ROTATION_BLOCKS,
                                        pipeline=True), smoke.bodies)
    check(st["rows_moved"] > 0, "B: DuplexKV moved no rows")
    print(f"phase B: token agreement with A = {agreement(streams_a, streams)}"
          f" ({st['tokens']} tokens)", flush=True)


def phase_c(smoke: Smoke):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.configs import ServingConfig
    from repro.core.blocktable import TransferDesc
    from repro.serving.paged_runner import PagedKVStore
    rng = np.random.default_rng(smoke.seed)
    nb = 64
    src, dst = [3, 17, 42, 5, 60], [7, 20, 33, 50, 61]  # 5 lanes: padded
    forks = [(9, 1), (11, 2), (13, 63)]                 # 3 lanes: padded
    for kv in ("bf16", "int8"):
        t0 = time.perf_counter()
        sv = ServingConfig(num_hbm_blocks=nb, kv_dtype=kv)
        store = PagedKVStore(smoke.cfg, sv, jnp.bfloat16, staging=16,
                             kv_dtype=kv)
        shape = store.pool.shape
        if kv == "int8":
            vals = rng.integers(-127, 128, shape, dtype=np.int8)
            store.scales = jnp.asarray(rng.random(store.scales.shape,
                                                  dtype=np.float32))
        else:
            vals = rng.standard_normal(shape, dtype=np.float32)
        store.pool = jnp.asarray(vals, store.pool.dtype)

        def rows():
            arrays = [store.pool] + ([store.scales] if kv == "int8" else [])
            return [np.asarray(a)[:nb] for a in arrays]

        before = rows()
        store.run_d2h([TransferDesc(i, -1, "d2h", s, 1000 + i, 0, 1)
                       for i, s in enumerate(src)])
        store.run_h2d([TransferDesc(i, -1, "h2d", 1000 + i, d, 0, 1)
                       for i, d in enumerate(dst)])
        store.run_d2d(forks)
        jax.block_until_ready(store.pool)
        wall = time.perf_counter() - t0
        after = rows()
        moved = list(zip(src, dst)) + forks
        exact = all(np.array_equal(aft[d], bef[s])
                    for bef, aft in zip(before, after) for s, d in moved)
        others = [r for r in range(nb) if r not in {d for _, d in moved}]
        kept = all(np.array_equal(aft[others], bef[others])
                   for bef, aft in zip(before, after))
        print(f"phase C {kv}: host-clock wall_s={wall:.3f} rows moved "
              f"d2h={store.d2h_rows} h2d={store.h2d_rows} d2d={store.d2d_rows}"
              f" copy_launches={store.copy_launches} bit_exact={exact} "
              f"untouched_rows_bit_identical={kept} (row 0 among them: "
              f"{0 in others})", flush=True)
        check(exact, f"C {kv}: a moved row did not come back bit-exact")
        check(kept and 0 in others, f"C {kv}: a row outside the copy "
              f"batch changed")
        del store
        gc.collect()


def phase_d(smoke: Smoke, streams_a):
    streams, _ = serve_requests(
        smoke, "D", smoke.server_config(hbm_blocks=smoke.pool_blocks("int8"),
                                        kv_dtype="int8"), smoke.bodies[:4])
    print(f"phase D: int8 token agreement with bf16 phase A = "
          f"{agreement(streams_a, streams)}", flush=True)


def four_chips(smoke: Smoke):
    """tp=4 over a v5e 2x2 against tp=1 on the same requests and pool."""
    import jax
    blocks = smoke.pool_blocks("bf16")
    ref, st1 = serve_requests(smoke, "tp1", smoke.server_config(
        hbm_blocks=blocks), smoke.bodies)
    out, st4 = serve_requests(smoke, "tp4", smoke.server_config(
        hbm_blocks=blocks, tp=4), smoke.bodies)
    (_, pool1), = st1["pool_shard_bytes"]
    shards = dict(st4["pool_shard_bytes"])
    print(f"four chips: token agreement tp4 vs tp1 = {agreement(ref, out)}; "
          f"tp1 pool bytes={pool1}; tp4 pool bytes per device={shards}",
          flush=True)
    check(sorted(shards) == sorted(d.id for d in jax.devices()[:4]),
          "tp4 pool is not spread over four devices")
    check(all(n * 4 == pool1 for n in shards.values()),
          "tp4 pool shard is not a quarter of the tp1 pool")


# ------------------------------------------------------------------- main
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only tp=4 against tp=1 (needs 4 chips)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    from repro.launch.hostenv import enable_compile_cache
    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    need = 4 if args.four_chips else 1
    if len(devices) < need:
        print(f"chip_smoke: needs {need} chips, JAX found {len(devices)}",
              file=sys.stderr)
        return 2
    print(f"device: {dev.platform} {dev.device_kind} x{len(devices)}; "
          f"compile cache: {enable_compile_cache()}", flush=True)
    smoke = Smoke(MODEL, RUNNER_LAYERS, PROMPT_LENS, MAX_TOKENS, args.seed,
                  CompileMeter())
    c = smoke.cfg
    print(f"model: {c.name} layers={c.num_layers} d_model={c.d_model} "
          f"heads={c.num_heads}/{c.num_kv_heads} head_dim={c.head_dim} "
          f"d_ff={c.d_ff} vocab={c.vocab_size} dtype={c.dtype}", flush=True)

    try:
        if args.four_chips:
            four_chips(smoke)
        else:
            streams_a = phase_a(smoke)
            phase_b(smoke, streams_a)
            phase_c(smoke)
            phase_d(smoke, streams_a)
    except Exception as e:       # device state unknown: stop at the first
        traceback.print_exc()
        print(f"chip_smoke FAILED: {type(e).__name__}: {e}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
