"""Async serving front door, end-to-end over a real localhost socket.

The server under test runs ``serving.server.serve_main`` on a background
thread (``install_signals=False`` — asyncio signal handlers need the main
thread; the SIGTERM path is exercised by the CI smoke job through
``launch.server_main``). Covers:

  * config validation,
  * submit -> stream -> result over HTTP, including token-id parity with
    the offline engine at the same seed (paged runner: argmax ids are
    batching/timing-independent, established in test_paged_runner.py),
  * concurrent clients,
  * mid-stream client disconnect aborts the request and returns the
    HBM/DRAM pools to their idle level,
  * /readyz flipping to 503 during drain while open streams keep
    delivering, and the drain-timeout path (exit code 1, leftover stream
    ends with finish_reason "aborted"),
  * the exclusive-driver claim: the blocking pump/drain surfaces raise
    while the async driver owns the engine.
"""
import asyncio
import json
import socket
import threading
import time

import numpy as np
import pytest

from repro.serving.server import (InferenceServer, ServerConfig, serve_main)

pytestmark = pytest.mark.filterwarnings("ignore::ResourceWarning")


# ------------------------------------------------------------------ harness
class ServerUnderTest:
    """serve_main on a daemon thread; exposes port/loop/service/exit code."""

    def __init__(self, **cfg_kw):
        cfg_kw.setdefault("port", 0)
        cfg_kw.setdefault("model", "llama3-8b")
        cfg_kw.setdefault("hbm_blocks", 256)
        cfg_kw.setdefault("dram_blocks", 2048)
        self.cfg = ServerConfig(**cfg_kw).validate()
        self.code = None
        self.server = None
        self.service = None
        self.loop = None
        self._ready = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)

    def _run(self):
        def ready_cb(server, service):
            self.server, self.service = server, service
            self.loop = asyncio.get_running_loop()
            self._ready.set()
        try:
            self.code = asyncio.run(
                serve_main(self.cfg, install_signals=False,
                           ready_cb=ready_cb))
        finally:
            self._ready.set()       # unblock start() on startup failure

    def __enter__(self):
        self._thread.start()
        assert self._ready.wait(60), "server did not start"
        assert self.server is not None, "serve_main died during startup"
        return self

    def __exit__(self, *exc):
        if self._thread.is_alive():
            self.loop.call_soon_threadsafe(self.server.request_shutdown)
            self._thread.join(60)
        assert not self._thread.is_alive(), "server failed to shut down"

    @property
    def port(self):
        return self.server.port

    @property
    def engine(self):
        return self.service.engine

    def stop(self):
        """Request drain and wait; returns the exit code."""
        self.__exit__()
        return self.code


def http(port, method, path, body=None, timeout=30.0):
    """One blocking HTTP exchange (Connection: close); parses the body."""
    payload = b"" if body is None else json.dumps(body).encode()
    head = (f"{method} {path} HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode()
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.sendall(head + payload)
        raw = b""
        while chunk := s.recv(65536):
            raw += chunk
    head, _, rest = raw.partition(b"\r\n\r\n")
    status = int(head.split(b" ", 2)[1])
    return status, rest


def parse_events(raw):
    """Decode `data: {...}` events out of a chunked SSE body."""
    out = []
    i = 0
    while (s := raw.find(b"data: ", i)) != -1:
        e = raw.find(b"\n\n", s)
        if e == -1:
            break
        out.append(json.loads(raw[s + 6:e]))
        i = e + 2
    return out


def stream_events(port, body, stop_after=None, timeout=60.0):
    """POST /v1/generate and read events as they arrive; closing early
    (stop_after) models a client disconnect. Returns the events read."""
    payload = json.dumps(body).encode()
    head = (f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
            f"Content-Length: {len(payload)}\r\n\r\n").encode()
    events, buf = [], b""
    with socket.create_connection(("127.0.0.1", port), timeout=timeout) as s:
        s.sendall(head + payload)
        while True:
            chunk = s.recv(65536)
            if not chunk:
                break
            buf += chunk
            events = parse_events(buf)
            if events and events[-1]["finished"]:
                break
            if stop_after is not None and len(events) >= stop_after:
                break           # context exit closes the socket mid-stream
    return events


# ------------------------------------------------------------------- config
def test_config_validation():
    with pytest.raises(ValueError, match="unknown ServerConfig keys"):
        ServerConfig.from_dict({"bogus": 1})
    with pytest.raises(ValueError) as ei:
        ServerConfig(model="nope", scheduler="nope", replicas=0,
                     drain_timeout=-1).validate()
    msg = str(ei.value)             # every problem reported in one error
    for frag in ("unknown arch", "scheduler", "replicas", "drain_timeout"):
        assert frag in msg
    cfg = ServerConfig.from_dict({"port": 0, "replicas": 2})
    assert cfg.validate() is cfg
    with pytest.raises(ValueError, match="runner_layers needs paged_runner"):
        ServerConfig(runner_layers=6).validate()
    assert ServerConfig(runner_layers=6, paged_runner=True).validate()


def test_launcher_import_loads_no_jax():
    """The supervisor must stay off JAX: a parent that has touched JAX holds
    the chip, and its server child could then not reach it."""
    import os
    import subprocess
    import sys
    from pathlib import Path
    src = str(Path(__file__).resolve().parents[1] / "src")
    env = dict(os.environ, PYTHONPATH=src)
    code = ("import sys, repro.launch.server_main; "
            "sys.exit(1 if 'jax' in sys.modules else 0)")
    assert subprocess.run([sys.executable, "-c", code], env=env,
                          timeout=120).returncode == 0


def test_compile_cache_dir_follows_env(monkeypatch, tmp_path):
    """JAX_COMPILATION_CACHE_DIR, when set, is the only cache directory;
    unset, the entry points use the fixed .jax_cache/ of the checkout."""
    import jax
    from repro.launch import hostenv
    before = jax.config.jax_compilation_cache_dir
    try:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert hostenv.enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        path = hostenv.enable_compile_cache()
        assert path == jax.config.jax_compilation_cache_dir
        assert path.endswith(".jax_cache")
        assert (hostenv.CHECKOUT_CACHE_DIR.parent / "src").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


# ---------------------------------------------------------------- endpoints
def test_stream_health_metrics_and_clean_drain():
    with ServerUnderTest(pace=False) as sut:
        status, body = http(sut.port, "GET", "/healthz")
        assert status == 200 and json.loads(body)["status"] == "ok"
        status, body = http(sut.port, "GET", "/readyz")
        assert status == 200 and json.loads(body)["ready"] is True

        evts = stream_events(sut.port, {"prompt_len": 64, "max_tokens": 12,
                                        "slo_class": "interactive"})
        assert evts[-1]["finished"]
        assert evts[-1]["finish_reason"] == "length"
        assert evts[-1]["tokens_generated"] == 12
        assert sum(e["new_tokens"] for e in evts) == 12
        assert evts[-1]["slo_class"] == "interactive"
        assert evts[-1]["ttft_s"] is not None

        status, body = http(sut.port, "GET", "/v1/metrics")
        row = json.loads(body)
        assert status == 200 and row["n"] >= 1
        assert "ttft_attainment" in row
        assert row["server"]["streams_started"] == 1
        assert row["server"]["engine_steps"] > 0

        # bad requests are 400s, not stream responses
        for bad in ({"max_tokens": 4},                       # no prompt
                    {"prompt_len": 4, "prompt_ids": [1, 2]},  # both
                    {"prompt_len": 4, "wat": 1}):             # unknown field
            status, body = http(sut.port, "POST", "/v1/generate", bad)
            assert status == 400, body
        status, _ = http(sut.port, "GET", "/nope")
        assert status == 404
        status, _ = http(sut.port, "POST", "/healthz")
        assert status == 405
    assert sut.stop() == 0          # nothing in flight: clean drain


def test_probe_keepalive_reuses_one_socket():
    """GET probe endpoints honor an explicit ``Connection: keep-alive``:
    sequential /healthz, /readyz and /v1/metrics exchanges ride ONE socket,
    and a final probe without the header closes it (the default)."""

    def recv_response(s):
        """Read exactly one Content-Length-framed response off the socket."""
        raw = b""
        while b"\r\n\r\n" not in raw:
            chunk = s.recv(65536)
            assert chunk, "server closed mid-response"
            raw += chunk
        head, _, body = raw.partition(b"\r\n\r\n")
        headers = dict(
            line.split(b": ", 1) for line in head.split(b"\r\n")[1:])
        clen = int(headers[b"Content-Length"])
        while len(body) < clen:
            chunk = s.recv(65536)
            assert chunk, "server closed mid-body"
            body += chunk
        status = int(head.split(b" ", 2)[1])
        return status, headers, json.loads(body)

    with ServerUnderTest(pace=False) as sut:
        with socket.create_connection(("127.0.0.1", sut.port),
                                      timeout=30.0) as s:
            for path, key in (("/healthz", "status"), ("/readyz", "ready"),
                              ("/v1/metrics", "server"), ("/healthz", None)):
                s.sendall((f"GET {path} HTTP/1.1\r\nHost: t\r\n"
                           f"Connection: keep-alive\r\n\r\n").encode())
                status, headers, obj = recv_response(s)
                assert status == 200
                assert headers[b"Connection"] == b"keep-alive"
                if key is not None:
                    assert key in obj
            # the server's request counter saw all 4 over one connection
            assert sut.server.http_requests >= 4
            # no keep-alive header -> one-shot semantics, socket closes
            s.sendall(b"GET /healthz HTTP/1.1\r\nHost: t\r\n\r\n")
            status, headers, _ = recv_response(s)
            assert status == 200
            assert headers[b"Connection"] == b"close"
            assert s.recv(65536) == b""          # server closed its end
    assert sut.stop() == 0


def test_generate_keepalive_reuses_one_socket():
    """``POST /v1/generate`` with ``Connection: keep-alive``: two complete
    streams ride ONE socket — the server answers with a keep-alive header,
    ends each stream at its terminal chunk, and parses the next request
    from the same connection (including one pipelined mid-stream, whose
    bytes the disconnect watcher must hand back)."""

    def send_generate(s, max_tokens, keep=True):
        payload = json.dumps({"prompt_len": 24,
                              "max_tokens": max_tokens}).encode()
        conn = "keep-alive" if keep else "close"
        s.sendall((f"POST /v1/generate HTTP/1.1\r\nHost: t\r\n"
                   f"Connection: {conn}\r\n"
                   f"Content-Length: {len(payload)}\r\n\r\n").encode()
                  + payload)

    def recv_stream(s, buf):
        """Read one chunked SSE stream through its terminal chunk; returns
        (events, header bytes, leftover buffer)."""
        while b"\r\n\r\n" not in buf:
            chunk = s.recv(65536)
            assert chunk, "server closed before response head"
            buf += chunk
        head, _, buf = buf.partition(b"\r\n\r\n")
        while (k := buf.find(b"0\r\n\r\n")) == -1:
            chunk = s.recv(65536)
            assert chunk, "server closed before terminal chunk"
            buf += chunk
        body, buf = buf[:k], buf[k + 5:]
        return parse_events(body), head, buf

    with ServerUnderTest(pace=False) as sut:
        with socket.create_connection(("127.0.0.1", sut.port),
                                      timeout=60.0) as s:
            buf = b""
            send_generate(s, 4)
            # pipeline the second request while the first stream runs: its
            # bytes may be swallowed by the disconnect watcher and must be
            # pushed back for the next parse
            send_generate(s, 6)
            evts1, head1, buf = recv_stream(s, buf)
            assert b"Connection: keep-alive" in head1
            assert evts1[-1]["finished"]
            assert evts1[-1]["tokens_generated"] == 4
            evts2, head2, buf = recv_stream(s, buf)
            assert evts2[-1]["finished"]
            assert evts2[-1]["tokens_generated"] == 6
            assert evts2[-1]["req_id"] != evts1[-1]["req_id"]
            # third exchange without the header: one-shot semantics
            send_generate(s, 3, keep=False)
            while b"0\r\n\r\n" not in buf:
                chunk = s.recv(65536)
                if not chunk:
                    break
                buf += chunk
            head3, _, body3 = buf.partition(b"\r\n\r\n")
            assert b"Connection: close" in head3
            assert parse_events(body3)[-1]["finished"]
            assert s.recv(65536) == b""          # server closed its end
        assert sut.server.streams_started == 3
    assert sut.stop() == 0


def test_concurrent_clients():
    n = 8
    with ServerUnderTest(pace=False, replicas=2, pipeline=True) as sut:
        results = [None] * n

        def worker(i):
            results[i] = stream_events(
                sut.port, {"prompt_len": 32 + i, "max_tokens": 6 + i})
        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(n)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        rids = set()
        for i, evts in enumerate(results):
            assert evts is not None and evts[-1]["finished"]
            assert evts[-1]["tokens_generated"] == 6 + i
            rids.add(evts[-1]["req_id"])
        assert len(rids) == n       # cluster-unique ids across replicas
    assert sut.code == 0


# ------------------------------------------------------------------- parity
def test_token_parity_with_offline_engine():
    """Same prompt_ids, same seed => the HTTP stream's final token_ids match
    the offline engine byte for byte (paged runner argmax ids are
    batching/timing-independent)."""
    kw = dict(model="llama3-8b", paged_runner=True, seed=7,
              hbm_blocks=256, dram_blocks=2048, pace=False)
    rng = np.random.default_rng(11)
    prompts = [[int(x) for x in rng.integers(1, 256, int(rng.integers(8, 20)))]
               for _ in range(3)]
    max_toks = [6, 9, 12]

    # offline reference: identical engine, blocking result() path
    offline = ServerConfig(port=0, **kw).build_engine()
    want = []
    for ids, mt in zip(prompts, max_toks):
        h = offline.add_request(prompt_ids=ids, sampling_params=_sp(mt))
        want.append(h.result().token_ids)

    with ServerUnderTest(**kw) as sut:
        for ids, mt, ref in zip(prompts, max_toks, want):
            evts = stream_events(sut.port, {"prompt_ids": ids,
                                            "max_tokens": mt})
            assert evts[-1]["finish_reason"] == "length"
            assert evts[-1]["token_ids"] == ref
            # per-event deltas re-assemble to the same stream
            got = [t for e in evts for t in e["new_token_ids"]]
            assert got == ref


def _sp(max_tokens):
    from repro.core.types import SamplingParams
    return SamplingParams(max_tokens=max_tokens)


# -------------------------------------------------------------- disconnect
def test_disconnect_aborts_and_frees_blocks():
    with ServerUnderTest(pace=True) as sut:
        core = sut.engine
        hbm0, dram0 = core.kv.hbm_free_blocks, core.kv.table.dram_free
        evts = stream_events(sut.port,
                             {"prompt_len": 256, "max_tokens": 100000},
                             stop_after=2)
        assert len(evts) >= 2 and not evts[-1]["finished"]
        deadline = time.monotonic() + 30
        while time.monotonic() < deadline:
            if (not core.has_work
                    and core.kv.hbm_free_blocks == hbm0
                    and core.kv.table.dram_free == dram0):
                break
            time.sleep(0.05)
        assert not core.has_work, "abort-on-disconnect never landed"
        assert core.kv.hbm_free_blocks == hbm0
        assert core.kv.table.dram_free == dram0
        assert sut.server.aborted_on_disconnect == 1
    assert sut.code == 0


# ------------------------------------------------------------------- drain
def test_readyz_flips_and_drain_timeout_aborts_leftovers():
    """A wall-paced request that cannot finish inside drain_timeout:
    readiness flips to 503 the moment drain starts (probed over a
    connection accepted before the listener closes), the open stream keeps
    receiving events during the drain and ends with "aborted", and the
    server exits 1 (dirty drain)."""
    sut = ServerUnderTest(pace=True, drain_timeout=1.0)
    with sut:
        # pre-open the probe connection (handlers already accepted keep
        # being served after the listener closes)
        probe = socket.create_connection(("127.0.0.1", sut.port), timeout=30)

        got = {"events": []}
        def client():
            got["events"] = stream_events(
                sut.port, {"prompt_len": 64, "max_tokens": 100000},
                timeout=60)
        t = threading.Thread(target=client, daemon=True)
        t.start()
        deadline = time.monotonic() + 30
        while not sut.engine.has_work and time.monotonic() < deadline:
            time.sleep(0.02)
        assert sut.engine.has_work

        sut.loop.call_soon_threadsafe(sut.server.request_shutdown)
        time.sleep(0.1)             # let the drain machinery engage
        probe.sendall(b"GET /readyz HTTP/1.1\r\nHost: t\r\n"
                      b"Content-Length: 0\r\n\r\n")
        raw = b""
        while chunk := probe.recv(65536):
            raw += chunk
        probe.close()
        assert b" 503 " in raw.split(b"\r\n", 1)[0]
        assert b"draining" in raw

        t.join(60)
        evts = got["events"]
        assert evts, "stream got nothing during drain"
        assert evts[-1]["finished"]
        assert evts[-1]["finish_reason"] == "aborted"
    assert sut.code == 1            # leftovers were cut off

    # and new submissions during drain are refused with 503 — covered by
    # the admission check; exercised here post-exit for the socket error
    with pytest.raises(OSError):
        http(sut.port, "GET", "/healthz", timeout=2)


# ----------------------------------------------------------- driver claim
def test_exclusive_driver_claim_blocks_sync_surfaces():
    from repro.configs import GH200, ServingConfig, get_config
    from repro.serving.core import EngineCore

    core = EngineCore(get_config("llama3-8b"),
                      ServingConfig(num_hbm_blocks=256, num_dram_blocks=2048),
                      GH200)

    async def scenario():
        from repro.serving.async_engine import AsyncServingEngine
        svc = AsyncServingEngine(core, pace=False)
        await svc.start()
        try:
            h = await svc.submit(prompt_len=32, sampling_params=_sp(4))
            # the engine is claimed: blocking surfaces must refuse loudly
            with pytest.raises(RuntimeError, match="AsyncServingEngine"):
                core.drain()
            # result() pumps only while unfinished; the pace=False driver
            # may have finished the request already, making it a cached
            # read. Either way it must never step the claimed engine.
            try:
                cached = h._handle.result()
            except RuntimeError as e:
                assert "AsyncServingEngine" in str(e)
            else:
                assert cached.finished
            out = await h.result()          # async path still works
            assert out.finished and out.tokens_generated == 4
        finally:
            left = await svc.shutdown(drain_timeout_s=30)
        assert left == []
        # claim released: the legacy blocking API works again
        h2 = core.add_request(prompt_len=16, sampling_params=_sp(3))
        assert h2.result().tokens_generated == 3

    asyncio.run(scenario())
