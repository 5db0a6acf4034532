"""PagedModelRunner: batched real execution over a pooled block-first KV
cache, wired to the Pallas kernels and DuplexKV (paper §4.3).

The engine's logical block decisions ARE the physical layout here: one
pooled ``(rows, L, 2, P, Hkv, D)`` device buffer holds every layer of one
logical KV block contiguously per row (block-first, segments_per_block==1),
and rows are addressed by the ``TwoTierBlockTable``'s ``hbm_slot``s — the
same integers the scheduler budgets with. Consequences:

* **Decode** is ONE batched ``paged_attention_tpu`` launch per layer per
  iteration (scalar-prefetched block tables do the indirection), not N
  Python-loop model calls — the launch count is independent of batch size.
* **Chunked prefill** scatters each chunk's K/V into the request's assigned
  rows and attends over the gathered block context, so prefill resumes
  mid-prompt after a rotation with no recompute.
* **Rotation and prefix-cache demotion are physical row movement**: every
  ``TransferDesc`` the DuplexKV times is also executed by ``PagedKVStore``
  — a batched ``kv_copy_tpu`` launch gathers the rows into a contiguous
  staging region (the cudaMemcpyBatchAsync analogue), then one contiguous
  host transfer moves them to/from a numpy DRAM tier.
* **Prefix-cache + real execution compose** (PR 3's incompatibility): a
  cache-hit block is a genuinely shared pool row — a new request's block
  table simply points at it, and attention reads the KV another request
  prefilled (RoPE is position-absolute, so shared prefixes agree).
* **Tensor parallelism** (``ServingConfig.tp > 1``): the pool's KV-HEAD
  dim shards over a 1-D ``("model",)`` mesh — per-shard row shape
  ``(L, 2, P, Hkv/TP, D)`` — while the row dim (the block table's slot
  ids) stays GLOBAL, so DuplexKV / RotaSched / prefix-cache logic is
  untouched. Weights shard per ``distributed.tp.layer_pspecs``; decode
  stays one (shard_map'd) launch per layer per iteration, with a psum
  after the wo and w_down contractions. ``tp == 1`` takes none of these
  branches and stays bit-identical to the single-chip runner.

Pallas kernels compile to Mosaic on a TPU backend and run in the Pallas
interpreter elsewhere (the CPU tests); ``repro.kernels.resolve_interpret``
makes that choice for every launch. See DESIGN.md §Execution layer.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Mapping, Optional, Sequence, Tuple

import numpy as np

from repro.configs.base import (GH200, HardwareProfile, ModelConfig,
                                ServingConfig)
from repro.serving.executor import (ExecutionResult, Executor,
                                    PendingExecution, SimExecutor)
from repro.serving.telemetry import (HS_KV_D2H, HS_KV_D2H_READBACK,
                                     HS_KV_H2D, HS_KV_H2D_STAGE,
                                     HS_RUNNER_LAUNCH, HS_RUNNER_PREPARE,
                                     HS_RUNNER_SYNC, host_span)


def _pow2(n: int) -> int:
    """Smallest power of two >= n (>= 1): bounds jit retraces to O(log)."""
    return 1 << max(n - 1, 0).bit_length()


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _unstack_layers(program, segments: list) -> List[dict]:
    """Per-layer param dicts in execution order (segment -> repeat ->
    pattern position), unstacking the scan-over-layers stacks of
    ``LM.init``'s ``segments`` (consumed). Each stacked leaf is dropped as
    soon as it is sliced, so peak device memory is the weights plus one
    leaf's stack rather than two copies of every layer."""
    import jax
    out: List[dict] = []
    for si, seg in enumerate(program):
        leaves, treedef = jax.tree.flatten(segments[si])
        segments[si] = None
        if seg.repeat == 1:
            out.extend(jax.tree.unflatten(treedef, leaves))
            continue
        cols = []
        for j in range(len(leaves)):
            cols.append([leaves[j][r] for r in range(seg.repeat)])
            leaves[j] = None
        for r in range(seg.repeat):
            out.extend(jax.tree.unflatten(treedef, [c[r] for c in cols]))
    return out


class PagedKVStore:
    """Physical two-tier KV storage behind the block table's slot numbers.

    Device tier: one jnp pool of ``num_hbm_blocks`` rows plus a staging
    region (``staging`` rows) and one trash row (scatter target for padded
    batch lanes). Host tier: a numpy dict keyed by DRAM slot. Implements
    the DuplexKV data-backend protocol (``run_d2d``/``run_d2h``/
    ``run_h2d``): each direction is a batched ``kv_copy_tpu`` launch
    through staging plus one contiguous host copy.
    """

    def __init__(self, cfg: ModelConfig, serving: ServingConfig, dtype,
                 *, staging: int = 64,
                 double_buffer: bool = False, tp_plan=None, mesh=None,
                 kv_dtype: str = "bf16", telemetry=None):
        import jax
        import jax.numpy as jnp
        if staging < 1 or staging & (staging - 1):
            # chunk padding rounds up to a power of two; a non-pow2 staging
            # region would let a padded upload spill past it and
            # dynamic_update_slice would clamp — silently overwriting live
            # block rows
            raise ValueError(f"staging must be a power of two, got {staging}")
        if double_buffer and staging < 4:
            raise ValueError(
                f"double_buffer splits staging into an H2D half and two D2H "
                f"gather buffers; needs staging >= 4, got {staging}")
        L = cfg.num_layers
        P = serving.block_size
        self.nb = serving.num_hbm_blocks
        self.staging = staging
        self.double_buffer = double_buffer
        self.trash_row = self.nb + staging
        # Staging layout. Single-buffer (sync engine): both directions use
        # the whole region, one chunk at a time, host readback immediately
        # after each gather. Double-buffer (pipelined engine): H2D owns the
        # TOP half so an upload/scatter for iteration N+1 never aliases a
        # D2H gather still draining from iteration N; the BOTTOM half splits
        # into two alternating gather buffers so chunk i's gather launch is
        # issued before chunk i-1's host readback forces a sync (a software
        # pipeline over the copy stream).
        if double_buffer:
            self.h2d_base = self.nb + staging // 2
            self.h2d_chunk = staging // 2
            self.d2h_chunk = staging // 4
        else:
            self.h2d_base = self.nb
            self.h2d_chunk = staging
            self.d2h_chunk = staging
        self.row_shape = (L, 2, P, cfg.num_kv_heads, cfg.head_dim)
        pool_shape = (self.nb + staging + 1,) + self.row_shape
        # Quantized tier (serving.kv_dtype == "int8"): the pool stores int8
        # values and a parallel fp32 scale array — one scale per (row,
        # layer, K/V side, kv head) — rides every row-movement path with
        # the SAME slot indexing (staging, double-buffer, host tier, D2D).
        self.quantized = kv_dtype == "int8"
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype must be 'bf16' or 'int8', "
                             f"got {kv_dtype!r}")
        if self.quantized:
            from repro.kernels.quant import kv_scale_shape
            dtype = jnp.int8
            self.scale_row_shape = kv_scale_shape(self.row_shape)
            scale_shape = (pool_shape[0],) + self.scale_row_shape
        else:
            self.scale_row_shape = None
            scale_shape = None
        # Tensor parallelism: the kv-head dim shards over the ("model",)
        # mesh — pool rows keep their GLOBAL slot numbering (the row dim is
        # never sharded), so the block table and every transfer descriptor
        # stay tp-agnostic. mesh is None on the single-chip path, which
        # stays bit-identical (plain single-device pool, unwrapped jits).
        self.tp_plan = tp_plan
        self.mesh = mesh
        self.scales = None
        if mesh is not None:
            from jax.sharding import NamedSharding
            from repro.distributed.tp import pool_pspec, scale_pspec
            self._pool_spec = pool_pspec(tp_plan)
            sharding = NamedSharding(mesh, self._pool_spec)
            self.pool = jnp.zeros(pool_shape, dtype, device=sharding)
            self._scale_spec = scale_pspec(tp_plan)
            if self.quantized:
                self.scales = jnp.zeros(
                    scale_shape, jnp.float32,
                    device=NamedSharding(mesh, self._scale_spec))
        else:
            self._pool_spec = self._scale_spec = None
            self.pool = jnp.zeros(pool_shape, dtype)
            if self.quantized:
                self.scales = jnp.zeros(scale_shape, jnp.float32)
        # dram_slot -> row array (bf16) | (int8 row, fp32 scale row) tuple
        self.host: Dict[int, np.ndarray] = {}
        # counters (benchmarks / tests)
        self.copy_launches = 0
        self.d2d_rows = 0
        self.d2h_rows = 0
        self.h2d_rows = 0
        # the engine's flight recorder (host spans), None when it is off
        self.telemetry = telemetry

        from repro.kernels.kv_copy import kv_copy_tpu

        def _copy(pool, src, dst):
            # the pool keeps its (rows, L, 2, P, Hkv, D) layout: flattening
            # rows would relayout (copy) the whole pool on a TPU
            return kv_copy_tpu(pool, src, dst)

        def _upload(pool, rows, base):   # contiguous write into staging
            idx = (base,) + (0,) * (pool.ndim - 1)
            return jax.lax.dynamic_update_slice(pool, rows.astype(pool.dtype),
                                                idx)

        # Quantized variants move the scale array through the SAME batched
        # launch / staging path as the int8 rows — a scale row is part of
        # the block's payload, so every direction (D2D fork, D2H gather,
        # H2D scatter) carries both or the dequant would read stale scales.
        def _copy_q(pool, scales, src, dst):
            return kv_copy_tpu(pool, src, dst), kv_copy_tpu(scales, src, dst)

        def _upload_q(pool, scales, rows, srows, base):
            idx = (base,) + (0,) * (pool.ndim - 1)
            pool = jax.lax.dynamic_update_slice(pool, rows.astype(pool.dtype),
                                                idx)
            sidx = (base,) + (0,) * (scales.ndim - 1)
            scales = jax.lax.dynamic_update_slice(
                scales, srows.astype(scales.dtype), sidx)
            return pool, scales

        if mesh is not None:
            from jax.sharding import PartitionSpec as Pspec
            ps = self._pool_spec
            ss = self._scale_spec
            # check_vma=False: pallas calls inside shard_map can't prove
            # replication; correctness is covered by the tp parity tests
            smap = functools.partial(jax.shard_map, mesh=mesh,
                                     check_vma=False)
            _copy = smap(_copy, in_specs=(ps, Pspec(), Pspec()),
                         out_specs=ps)
            _upload = smap(_upload, in_specs=(ps, ps, Pspec()),
                           out_specs=ps)
            _copy_q = smap(_copy_q, in_specs=(ps, ss, Pspec(), Pspec()),
                           out_specs=(ps, ss))
            _upload_q = smap(_upload_q, in_specs=(ps, ss, ps, ss, Pspec()),
                             out_specs=(ps, ss))

        # donate the pool: the caller always rebinds to the returned array,
        # and without donation every launch would deep-copy the whole pool,
        # defeating kv_copy_tpu's input_output_aliases (backends that cannot
        # donate just ignore the hint; sharded lowerings record it as
        # jax.buffer_donor instead of tf.aliasing_output — see
        # launch/audit_donation.py)
        self._jit_copy = jax.jit(_copy, donate_argnums=(0,))
        self._jit_upload = jax.jit(_upload, donate_argnums=(0,))
        if self.quantized:
            # the scale array is donated too: half-row-sized, same rebinding
            self._jit_copy_q = jax.jit(_copy_q, donate_argnums=(0, 1))
            self._jit_upload_q = jax.jit(_upload_q, donate_argnums=(0, 1))

    @property
    def pool_shard_bytes(self) -> int:
        """Bytes ONE device holds: global/kv_shards when the kv-head dim is
        sharded, the full pool when replicated or single-chip. Includes the
        scale array in quantized mode — it is part of the KV footprint."""
        n = self.pool.addressable_shards[0].data.nbytes
        if self.quantized:
            n += self.scales.addressable_shards[0].data.nbytes
        return n

    @property
    def pool_global_bytes(self) -> int:
        n = self.pool.nbytes
        if self.quantized:
            n += self.scales.nbytes
        return n

    def _copy_rows(self, src: Sequence[int], dst: Sequence[int]) -> None:
        """One batched row-copy launch: pool[dst[i]] = pool[src[i]].
        Padded to a power of two with no-op descriptors (src < 0) aimed at
        the trash row: the chip writes back every visited output block, so
        a padded lane must never name a live row."""
        import jax.numpy as jnp
        n = len(src)
        np2 = _pow2(n)
        s = np.full(np2, -1, np.int32)
        d = np.full(np2, self.trash_row, np.int32)
        s[:n], d[:n] = src, dst
        if self.quantized:
            self.pool, self.scales = self._jit_copy_q(
                self.pool, self.scales, jnp.asarray(s), jnp.asarray(d))
        else:
            self.pool = self._jit_copy(self.pool, jnp.asarray(s),
                                       jnp.asarray(d))
        self.copy_launches += 1

    # -- DuplexKV data-backend protocol ------------------------------------
    def run_d2d(self, pairs: Sequence[Tuple[int, int]]) -> None:
        """Intra-pool row copies (copy-on-write forks)."""
        if not pairs:
            return
        self._copy_rows([p[0] for p in pairs], [p[1] for p in pairs])
        self.d2d_rows += len(pairs)

    def _readback(self, base: int, chunk) -> None:
        """Materialize gathered staging rows into the host tier. Forces a
        host sync on the pool — in double-buffer mode this is deferred one
        chunk so the next gather launch is already in the dispatch queue."""
        n = len(chunk)
        with host_span(self.telemetry, HS_KV_D2H_READBACK):
            data = np.asarray(self.pool[base:base + n])
            # the host tier stores (int8 row, fp32 scale row) — the D2H
            # transfer the DuplexKV timed is the ~half-size int8 payload
            sdata = (np.asarray(self.scales[base:base + n])
                     if self.quantized else None)
        if self.quantized:
            for j, d in enumerate(chunk):
                self.host[d.dst_slot] = (np.array(data[j]),
                                         np.array(sdata[j]))
        else:
            for j, d in enumerate(chunk):
                self.host[d.dst_slot] = np.array(data[j])
        self.d2h_rows += n

    def run_d2h(self, descs) -> None:
        """Device rows -> host tier: batched gather into staging (one
        ``kv_copy_tpu`` launch), then ONE contiguous device->host copy.
        Double-buffer mode alternates two gather buffers, reading chunk
        i-1 back only after chunk i's gather is dispatched."""
        with host_span(self.telemetry, HS_KV_D2H):
            self._run_d2h(descs)

    def _run_d2h(self, descs) -> None:
        q = self.d2h_chunk
        pending = None                      # (base, chunk) awaiting readback
        for i in range(0, len(descs), q):
            chunk = descs[i:i + q]
            base = self.nb + (q if self.double_buffer and (i // q) % 2
                              else 0)
            self._copy_rows([d.src_slot for d in chunk],
                            list(range(base, base + len(chunk))))
            if not self.double_buffer:
                self._readback(base, chunk)
                continue
            if pending is not None:
                self._readback(*pending)
            pending = (base, chunk)
        if pending is not None:
            self._readback(*pending)

    def run_h2d(self, descs) -> None:
        """Host tier -> device rows: one contiguous host->device upload into
        staging (the H2D half, in double-buffer mode), then a batched
        ``kv_copy_tpu`` scatter into place."""
        with host_span(self.telemetry, HS_KV_H2D):
            self._run_h2d(descs)

    def _run_h2d(self, descs) -> None:
        import jax.numpy as jnp
        for i in range(0, len(descs), self.h2d_chunk):
            chunk = descs[i:i + self.h2d_chunk]
            n = len(chunk)
            rows = []
            for d in chunk:
                row = self.host.get(d.src_slot)
                if row is None:
                    raise RuntimeError(
                        f"h2d for block {d.block_id}: DRAM slot "
                        f"{d.src_slot} holds no data (lost copy)")
                rows.append(row)
            np2 = _pow2(n)
            with host_span(self.telemetry, HS_KV_H2D_STAGE):
                vals = [r[0] for r in rows] if self.quantized else rows
                buf = np.zeros((np2,) + self.row_shape, vals[0].dtype)
                buf[:n] = np.stack(vals)
                if self.quantized:
                    sbuf = np.zeros((np2,) + self.scale_row_shape,
                                    np.float32)
                    sbuf[:n] = np.stack([r[1] for r in rows])
            if self.quantized:
                self.pool, self.scales = self._jit_upload_q(
                    self.pool, self.scales, jnp.asarray(buf),
                    jnp.asarray(sbuf), jnp.asarray(self.h2d_base, np.int32))
            else:
                self.pool = self._jit_upload(
                    self.pool, jnp.asarray(buf),
                    jnp.asarray(self.h2d_base, np.int32))
            self._copy_rows(list(range(self.h2d_base, self.h2d_base + n)),
                            [d.dst_slot for d in chunk])
            self.h2d_rows += n


class PagedModelRunner(Executor):
    """Batched real execution against the pooled block-first KV cache.

    ``model_cfg`` is the config actually executed (``runner_config``: a
    ``reduced()`` tiny LM for the CPU tests, or the published widths cut in
    depth); iteration wall-time still comes from a ``SimExecutor`` — pass
    ``timing_cfg`` to keep timing calibrated to the full-size model while
    executing the reduced one. The runner binds to the engine's DuplexKV
    (``bind``), sizing the device pool to the block table and attaching its
    ``PagedKVStore`` as the table's physical data backend.
    """

    supports_prefix_cache = True
    host_clock = True

    def __init__(self, model_cfg: ModelConfig, serving: ServingConfig,
                 hw: HardwareProfile = GH200, *, seed: int = 0,
                 sim: Optional[SimExecutor] = None,
                 timing_cfg: Optional[ModelConfig] = None):
        import jax
        from repro.kernels import resolve_interpret
        from repro.models.blocks import make_layer_spec
        from repro.models.common import dtype_of
        from repro.models.lm import LM

        unsupported = []
        if model_cfg.num_encoder_layers or model_cfg.frontend.kind != "none":
            unsupported.append("encoder/frontend stacks")
        for i in range(model_cfg.num_layers):
            sp = make_layer_spec(model_cfg, i)
            if sp.mixer != "attn" or not sp.is_global or sp.has_cross \
                    or sp.ffn != "dense":
                unsupported.append(f"layer {i} ({sp.mixer}/{sp.ffn})")
                break
        if unsupported:
            raise ValueError(
                "PagedModelRunner supports uniform dense-attention decoder "
                f"configs only; {model_cfg.name} has " + ", ".join(unsupported))

        self.cfg = model_cfg
        self.serving = serving
        self.tp = int(getattr(serving, "tp", 1) or 1)
        # Quantized KV tier: kv_dtype == "int8" switches the runner to the
        # *_impl_q jit functions below. The bf16 path keeps its own impls
        # and jit call structure, so the default jaxpr (and the golden
        # replay) is byte-identical to the unquantized runner.
        self.kv_dtype = getattr(serving, "kv_dtype", "bf16") or "bf16"
        self.quantized = self.kv_dtype == "int8"
        from repro.distributed.tp import plan_tp_sharding
        self.tp_plan = plan_tp_sharding(model_cfg, self.tp)
        self.sim = sim or SimExecutor(timing_cfg or model_cfg, hw,
                                      tp=self.tp, kv_dtype=self.kv_dtype)
        self.interpret = resolve_interpret()
        self.dtype = dtype_of(model_cfg.dtype)
        lm = LM(model_cfg)
        params = lm.init(jax.random.PRNGKey(seed))
        self._head = {k: params.pop(k) for k in
                      ("embed", "final_norm", "lm_head") if k in params}
        self._layers = _unstack_layers(lm.program, params.pop("segments"))
        self.store: Optional[PagedKVStore] = None
        self.kv = None
        self.telemetry = None          # the engine's bus, given at bind
        # psum flags are trace-time constants: at tp == 1 neither branch is
        # taken, so the jaxpr — and the golden replay — is bit-identical to
        # the single-chip runner
        self._psum_attn = self.tp_plan.shard_kv
        self._psum_mlp = self.tp_plan.shard_mlp
        if self.tp_plan.trivial:
            self.mesh = None
            if self.quantized:
                # pool + scales (args 2, 3) donated: rebound on every return
                self._jit_decode = jax.jit(self._decode_impl_q,
                                           donate_argnums=(2, 3))
                self._jit_prefill = jax.jit(self._prefill_impl_q,
                                            donate_argnums=(2, 3))
            else:
                # pool (arg 2 after layers/head) donated: rebound every return
                self._jit_decode = jax.jit(self._decode_impl,
                                           donate_argnums=(2,))
                self._jit_prefill = jax.jit(self._prefill_impl,
                                            donate_argnums=(2,))
        else:
            from jax.sharding import NamedSharding
            from jax.sharding import PartitionSpec as Pspec
            from repro.distributed.tp import (head_pspecs, layer_pspecs,
                                              pool_pspec, scale_pspec)
            from repro.launch.mesh import make_tp_mesh
            self.mesh = make_tp_mesh(self.tp)   # raises with the XLA_FLAGS
            #                                     recipe if devices are short
            lp = layer_pspecs(self.tp_plan)
            layer_specs = [{k: lp[k] for k in layer} for layer in self._layers]
            head_specs = head_pspecs(self._head)
            # shard the weights once, up front (device_put per spec); jit
            # then consumes them already laid out — no per-step resharding.
            # Leaves are replaced in place so each unsharded leaf is freed
            # as soon as it is placed: init put every weight on device 0
            for layer in self._layers:
                for k in layer:
                    layer[k] = jax.device_put(
                        layer[k], NamedSharding(self.mesh, lp[k]))
            for k in self._head:
                self._head[k] = jax.device_put(
                    self._head[k], NamedSharding(self.mesh, head_specs[k]))
            ps = pool_pspec(self.tp_plan)
            # check_vma=False: pallas calls inside shard_map can't prove
            # replication; correctness is covered by the tp parity tests
            shard_map = functools.partial(jax.shard_map, mesh=self.mesh,
                                          check_vma=False)
            if self.quantized:
                ss = scale_pspec(self.tp_plan)
                dec = shard_map(
                    self._decode_impl_q,
                    in_specs=(layer_specs, head_specs, ps, ss,
                              Pspec(), Pspec(), Pspec()),
                    out_specs=(ps, ss, Pspec()))
                pre = shard_map(
                    self._prefill_impl_q,
                    in_specs=(layer_specs, head_specs, ps, ss,
                              Pspec(), Pspec(), Pspec(), Pspec()),
                    out_specs=(ps, ss, Pspec()))
                self._jit_decode = jax.jit(dec, donate_argnums=(2, 3))
                self._jit_prefill = jax.jit(pre, donate_argnums=(2, 3))
            else:
                dec = shard_map(
                    self._decode_impl,
                    in_specs=(layer_specs, head_specs, ps,
                              Pspec(), Pspec(), Pspec()),
                    out_specs=(ps, Pspec()))
                pre = shard_map(
                    self._prefill_impl,
                    in_specs=(layer_specs, head_specs, ps,
                              Pspec(), Pspec(), Pspec(), Pspec()),
                    out_specs=(ps, Pspec()))
                self._jit_decode = jax.jit(dec, donate_argnums=(2,))
                self._jit_prefill = jax.jit(pre, donate_argnums=(2,))
        # counters (benchmarks / tests): decode launch count is per-layer,
        # INDEPENDENT of batch size — the whole point of the batched path
        self.decode_batches = 0
        self.decode_tokens = 0
        self.attn_launches = 0
        # block slots of the padded (batch, table) grid vs blocks the real
        # contexts occupy, over every layer: 1 - live/slots is the share
        # of the padded walk the attention kernel skips
        self.attn_block_slots = 0
        self.attn_blocks_live = 0
        self.prefill_chunks_run = 0

    # ------------------------------------------------------------- binding
    def bind(self, kv, telemetry=None) -> None:
        """Attach to the engine's DuplexKV: allocate the device pool sized
        to its block table and register as the physical data backend.
        ``telemetry``: the engine's flight recorder, whose host spans the
        runner and its store then record."""
        self.kv = kv
        self.telemetry = telemetry
        self.store = PagedKVStore(
            self.cfg, self.serving, self.dtype,
            double_buffer=bool(getattr(self.serving, "pipeline", False)),
            tp_plan=None if self.tp_plan.trivial else self.tp_plan,
            mesh=self.mesh, kv_dtype=self.kv_dtype, telemetry=telemetry)
        kv.attach_data_backend(self.store)

    # ------------------------------------------------------ executor protocol
    def step_time(self, plan) -> float:
        return self.sim.step_time(plan)

    def plan_time(self, plan) -> float:
        return self.sim.plan_time(plan)

    def execute(self, plan, requests) -> ExecutionResult:
        from repro.core.types import RequestState
        if self.store is None:
            raise RuntimeError("PagedModelRunner.bind(kv) was never called")
        out = ExecutionResult()
        for rid, take in plan.prefill_chunks:
            r = requests.get(rid)
            if r is None or r.prompt_ids is None:
                continue
            tok = self._run_prefill_chunk(r, take)
            if tok is not None:
                out.tokens[rid] = tok
        dec = []
        for rid in plan.decode_reqs:
            r = requests.get(rid)
            if (r is None or r.state != RequestState.RUNNING
                    or not r.generated_ids):
                continue
            dec.append(r)
        if dec:
            out.tokens.update(self._run_decode_batch(dec))
        return out

    def execute_async(self, plan, requests) -> PendingExecution:
        """Dispatch every launch of the iteration without a host sync: the
        prefill-chunk argmaxes and the batched decode output stay on device
        (JAX async dispatch keeps the queue full), and ``wait()`` pulls them
        back in ONE ``device_get`` — the iteration's single sync point —
        instead of one ``int()``/``np.asarray`` per chunk."""
        import jax
        from repro.core.types import RequestState
        if self.store is None:
            raise RuntimeError("PagedModelRunner.bind(kv) was never called")
        pre: List[Tuple[int, object]] = []     # (req_id, device argmax)
        for rid, take in plan.prefill_chunks:
            r = requests.get(rid)
            if r is None or r.prompt_ids is None:
                continue
            tok = self._run_prefill_chunk(r, take, defer=True)
            if tok is not None:
                pre.append((rid, tok))
        dec = []
        for rid in plan.decode_reqs:
            r = requests.get(rid)
            if (r is None or r.state != RequestState.RUNNING
                    or not r.generated_ids):
                continue
            dec.append(r)
        nxt = self._run_decode_batch(dec, defer=True) if dec else None

        def waiter() -> ExecutionResult:
            out = ExecutionResult()
            with host_span(self.telemetry, HS_RUNNER_SYNC):
                toks, arr = jax.device_get(([t for _, t in pre], nxt))
            for (rid, _), tok in zip(pre, toks):
                out.tokens[rid] = int(tok)
            if arr is not None:
                out.tokens.update(
                    {r.req_id: int(arr[i]) for i, r in enumerate(dec)})
            return out

        return PendingExecution(waiter)

    # rotation data movement rides the DuplexKV transfer descriptors (the
    # PagedKVStore backend); there is no per-request device state to move
    def swap_out(self, req_id: int) -> None:
        pass

    def swap_in(self, req_id: int) -> None:
        pass

    def drop(self, req_id: int) -> None:
        pass

    # ---------------------------------------------------------- device work
    def _rows(self, req_id: int) -> List[int]:
        """HBM pool rows of the request's blocks, in position order — the
        physical block table handed to the kernels."""
        from repro.core.blocktable import BlockLoc
        rows = []
        for b in self.kv.table.blocks_of(req_id):
            if b.hbm_slot is None or b.loc == BlockLoc.DRAM:
                raise RuntimeError(
                    f"block {b.block_id} of scheduled request {req_id} is "
                    f"not HBM-resident ({b.loc})")
            rows.append(b.hbm_slot)
        return rows

    def _run_prefill_chunk(self, r, take: int, defer: bool = False):
        import jax.numpy as jnp
        P = self.serving.block_size
        start = r.prefill_pos
        take = min(take, r.prompt_len - start)
        if take <= 0:
            return None
        with host_span(self.telemetry, HS_RUNNER_PREPARE):
            ids = r.prompt_ids[start:start + take]
            rows = self._rows(r.req_id)
            nb_ctx = _cdiv(start + take, P)
            if len(rows) < nb_ctx:
                raise RuntimeError(
                    f"req {r.req_id}: {len(rows)} blocks assigned, prefill "
                    f"needs {nb_ctx}")
            tp, mbp = _pow2(take), _pow2(nb_ctx)
            ids_p = np.zeros(tp, np.int32)
            ids_p[:take] = ids
            rows_p = np.full(mbp, self.store.trash_row, np.int32)
            rows_p[:min(len(rows), mbp)] = rows[:mbp]
            args = (jnp.asarray(ids_p), jnp.asarray(start, jnp.int32),
                    jnp.asarray(take, jnp.int32), jnp.asarray(rows_p))
        with host_span(self.telemetry, HS_RUNNER_LAUNCH):
            if self.quantized:
                self.store.pool, self.store.scales, tok = self._jit_prefill(
                    self._layers, self._head, self.store.pool,
                    self.store.scales, *args)
            else:
                self.store.pool, tok = self._jit_prefill(
                    self._layers, self._head, self.store.pool, *args)
        self.prefill_chunks_run += 1
        if start + take >= r.prompt_len and r.tokens_generated == 0:
            if defer:
                return tok                  # device array, no sync
            with host_span(self.telemetry, HS_RUNNER_SYNC):
                return int(tok)
        return None

    def _run_decode_batch(self, dec, defer: bool = False):
        import jax.numpy as jnp
        P = self.serving.block_size
        with host_span(self.telemetry, HS_RUNNER_PREPARE):
            cls = [r.total_len - 1 for r in dec]
            rows = [self._rows(r.req_id) for r in dec]
            for r, cl, rw in zip(dec, cls, rows):
                if len(rw) < _cdiv(cl + 1, P):
                    raise RuntimeError(
                        f"req {r.req_id}: {len(rw)} blocks assigned, decode "
                        f"at context {cl + 1} needs {_cdiv(cl + 1, P)}")
            mbp = _pow2(max(_cdiv(cl + 1, P) for cl in cls))
            bp = _pow2(len(dec))
            toks = np.zeros(bp, np.int32)
            cl_p = np.zeros(bp, np.int32)
            bt = np.full((bp, mbp), self.store.trash_row, np.int32)
            for i, r in enumerate(dec):
                toks[i] = r.generated_ids[-1]
                cl_p[i] = cls[i]
                k = min(len(rows[i]), mbp)
                bt[i, :k] = rows[i][:k]
            args = (jnp.asarray(toks), jnp.asarray(bt), jnp.asarray(cl_p))
        with host_span(self.telemetry, HS_RUNNER_LAUNCH):
            if self.quantized:
                self.store.pool, self.store.scales, nxt = self._jit_decode(
                    self._layers, self._head, self.store.pool,
                    self.store.scales, *args)
            else:
                self.store.pool, nxt = self._jit_decode(
                    self._layers, self._head, self.store.pool, *args)
        self.decode_batches += 1
        self.decode_tokens += len(dec)
        self.attn_launches += len(self._layers)
        self.attn_block_slots += bp * mbp * len(self._layers)
        self.attn_blocks_live += sum(_cdiv(cl + 1, P)
                                     for cl in cls) * len(self._layers)
        if defer:
            return nxt                          # device array, no host sync
        with host_span(self.telemetry, HS_RUNNER_SYNC):
            nxt = np.asarray(nxt)
        return {r.req_id: int(nxt[i]) for i, r in enumerate(dec)}

    # ------------------------------------------------------- jitted kernels
    def _logits(self, head, h):
        import jax.numpy as jnp
        from repro.models.common import rms_norm
        h = rms_norm(h, head["final_norm"], self.cfg.rms_eps)
        if self.cfg.tie_embeddings:
            return jnp.einsum("...d,vd->...v", h, head["embed"])
        return jnp.einsum("...d,dv->...v", h, head["lm_head"])

    def _decode_impl(self, layers, head, pool, toks, bt, cl):
        """One batched decode iteration. toks/cl: (B,); bt: (B, MB) pool
        rows (trash row on padded lanes/slots). Per layer: scatter the new
        token's K/V into the tail block row, then one paged-attention
        launch over the whole batch."""
        import jax
        import jax.numpy as jnp
        from repro.kernels.paged_attention import paged_attention_tpu
        from repro.models.common import apply_rope, rms_norm, swiglu
        cfg = self.cfg
        P = self.serving.block_size
        MB = bt.shape[1]
        x = jnp.take(head["embed"], toks, axis=0)            # (B, d)
        pos = cl[:, None]                                    # (B, 1)
        blk = jnp.clip(cl // P, 0, MB - 1)
        wrow = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
        woff = cl % P
        zeros, ones = jnp.zeros_like(wrow), jnp.ones_like(wrow)
        for li, p in enumerate(layers):
            h = rms_norm(x[:, None], p["ln1"], cfg.rms_eps)  # (B, 1, d)
            q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            lrow = jnp.full_like(wrow, li)
            pool = pool.at[wrow, lrow, zeros, woff].set(
                k[:, 0].astype(pool.dtype))
            pool = pool.at[wrow, lrow, ones, woff].set(
                v[:, 0].astype(pool.dtype))
            out = paged_attention_tpu(q[:, 0], pool, bt, cl + 1, layer=li,
                                      interpret=self.interpret)
            attn = jnp.einsum("bhk,hkd->bd", out, p["wo"])
            if self._psum_attn:   # partial over this shard's kv-head groups
                attn = jax.lax.psum(attn, "model")
            x = x + attn
            h2 = rms_norm(x[:, None], p["ln2"], cfg.rms_eps)
            mlp = swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])[:, 0]
            if self._psum_mlp:    # partial over this shard's d_ff slice
                mlp = jax.lax.psum(mlp, "model")
            x = x + mlp
        logits = self._logits(head, x)
        return pool, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _prefill_impl(self, layers, head, pool, ids, start, nvalid, bt):
        """One prefill chunk for one request. ids: (T,) padded chunk token
        ids; start: chunk's absolute position; nvalid: real chunk length;
        bt: (MB,) the request's pool rows. K/V scatter into assigned rows,
        attention over the gathered block context (earlier chunks and
        shared cache-hit blocks included). Returns the next-token argmax at
        the chunk tail (meaningful only when the chunk completes the
        prompt)."""
        import jax
        import jax.numpy as jnp
        from repro.models.attention import flash_attention
        from repro.models.common import apply_rope, rms_norm, swiglu
        cfg = self.cfg
        P = self.serving.block_size
        T = ids.shape[0]
        MB = bt.shape[0]
        x = jnp.take(head["embed"], ids, axis=0)[None]       # (1, T, d)
        tpos = start + jnp.arange(T)
        positions = tpos[None]
        valid = jnp.arange(T) < nvalid
        blk = jnp.clip(tpos // P, 0, MB - 1)
        wrow = jnp.where(valid, bt[blk], self.store.trash_row)
        woff = tpos % P
        zeros, ones = jnp.zeros_like(wrow), jnp.ones_like(wrow)
        for li, p in enumerate(layers):
            h = rms_norm(x, p["ln1"], cfg.rms_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            lrow = jnp.full_like(wrow, li)
            pool = pool.at[wrow, lrow, zeros, woff].set(
                k[0].astype(pool.dtype))
            pool = pool.at[wrow, lrow, ones, woff].set(
                v[0].astype(pool.dtype))
            # local kv-head count comes from the pool's (possibly sharded)
            # shape, not the config — identical at tp == 1
            hkv, hd = pool.shape[-2], pool.shape[-1]
            k_ctx = pool[bt, li, 0].reshape(1, MB * P, hkv, hd).astype(k.dtype)
            v_ctx = pool[bt, li, 1].reshape(1, MB * P, hkv, hd).astype(v.dtype)
            out = flash_attention(q, k_ctx, v_ctx, causal=True,
                                  q_offset=start)
            attn = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            if self._psum_attn:
                attn = jax.lax.psum(attn, "model")
            x = x + attn
            h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
            mlp = swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])
            if self._psum_mlp:
                mlp = jax.lax.psum(mlp, "model")
            x = x + mlp
        h_last = jax.lax.dynamic_index_in_dim(x[0], nvalid - 1, axis=0,
                                              keepdims=False)
        logits = self._logits(head, h_last)
        return pool, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    # ------------------------------------------------- quantized (int8) path
    # Separate impls (not a flag inside _decode_impl/_prefill_impl) so the
    # bf16 jaxpr — and with it the golden replay — stays byte-identical when
    # kv_dtype == "bf16". HBM traffic in this path is int8: the K/V scatter
    # writes quantized rows (running per-block scales, see kernels/quant.py)
    # and paged_attention_tpu dequantizes INSIDE the kernel (scales ride a
    # side ref through the same block-table indirection), so decode reads
    # ~half the bytes per block.

    def _decode_impl_q(self, layers, head, pool, scales, toks, bt, cl):
        import jax
        import jax.numpy as jnp
        from repro.kernels.paged_attention import paged_attention_tpu
        from repro.kernels.quant import quant_store_tokens
        from repro.models.common import apply_rope, rms_norm, swiglu
        cfg = self.cfg
        P = self.serving.block_size
        MB = bt.shape[1]
        x = jnp.take(head["embed"], toks, axis=0)            # (B, d)
        pos = cl[:, None]                                    # (B, 1)
        blk = jnp.clip(cl // P, 0, MB - 1)
        wrow = jnp.take_along_axis(bt, blk[:, None], axis=1)[:, 0]
        woff = cl % P
        for li, p in enumerate(layers):
            h = rms_norm(x[:, None], p["ln1"], cfg.rms_eps)  # (B, 1, d)
            q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
            q = apply_rope(q, pos, cfg.rope_theta)
            k = apply_rope(k, pos, cfg.rope_theta)
            lrow = jnp.full_like(wrow, li)
            pool, scales = quant_store_tokens(pool, scales, wrow, lrow, 0,
                                              woff, k[:, 0])
            pool, scales = quant_store_tokens(pool, scales, wrow, lrow, 1,
                                              woff, v[:, 0])
            out = paged_attention_tpu(q[:, 0], pool, bt, cl + 1, layer=li,
                                      kv_scales=scales,
                                      interpret=self.interpret)
            attn = jnp.einsum("bhk,hkd->bd", out, p["wo"])
            if self._psum_attn:   # partial over this shard's kv-head groups
                attn = jax.lax.psum(attn, "model")
            x = x + attn
            h2 = rms_norm(x[:, None], p["ln2"], cfg.rms_eps)
            mlp = swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])[:, 0]
            if self._psum_mlp:    # partial over this shard's d_ff slice
                mlp = jax.lax.psum(mlp, "model")
            x = x + mlp
        logits = self._logits(head, x)
        return pool, scales, jnp.argmax(logits, axis=-1).astype(jnp.int32)

    def _prefill_impl_q(self, layers, head, pool, scales, ids, start,
                        nvalid, bt):
        import jax
        import jax.numpy as jnp
        from repro.kernels.quant import quant_store_tokens
        from repro.models.attention import flash_attention
        from repro.models.common import apply_rope, rms_norm, swiglu
        cfg = self.cfg
        P = self.serving.block_size
        T = ids.shape[0]
        MB = bt.shape[0]
        x = jnp.take(head["embed"], ids, axis=0)[None]       # (1, T, d)
        tpos = start + jnp.arange(T)
        positions = tpos[None]
        valid = jnp.arange(T) < nvalid
        blk = jnp.clip(tpos // P, 0, MB - 1)
        wrow = jnp.where(valid, bt[blk], self.store.trash_row)
        woff = tpos % P
        for li, p in enumerate(layers):
            h = rms_norm(x, p["ln1"], cfg.rms_eps)
            q = jnp.einsum("bsd,dhk->bshk", h, p["wq"])
            k = jnp.einsum("bsd,dhk->bshk", h, p["wk"])
            v = jnp.einsum("bsd,dhk->bshk", h, p["wv"])
            q = apply_rope(q, positions, cfg.rope_theta)
            k = apply_rope(k, positions, cfg.rope_theta)
            lrow = jnp.full_like(wrow, li)
            pool, scales = quant_store_tokens(pool, scales, wrow, lrow, 0,
                                              woff, k[0])
            pool, scales = quant_store_tokens(pool, scales, wrow, lrow, 1,
                                              woff, v[0])
            # context gather dequantizes explicitly (prefill attends via
            # flash_attention over a dense gathered context, not the paged
            # kernel); local kv-head count comes from the (possibly sharded)
            # pool shape
            hkv, hd = pool.shape[-2], pool.shape[-1]
            k_ctx = (pool[bt, li, 0].astype(jnp.float32)
                     * scales[bt, li, 0][:, None, :, None]
                     ).reshape(1, MB * P, hkv, hd).astype(k.dtype)
            v_ctx = (pool[bt, li, 1].astype(jnp.float32)
                     * scales[bt, li, 1][:, None, :, None]
                     ).reshape(1, MB * P, hkv, hd).astype(v.dtype)
            out = flash_attention(q, k_ctx, v_ctx, causal=True,
                                  q_offset=start)
            attn = jnp.einsum("bshk,hkd->bsd", out, p["wo"])
            if self._psum_attn:
                attn = jax.lax.psum(attn, "model")
            x = x + attn
            h2 = rms_norm(x, p["ln2"], cfg.rms_eps)
            mlp = swiglu(h2, p["w_gate"], p["w_up"], p["w_down"])
            if self._psum_mlp:
                mlp = jax.lax.psum(mlp, "model")
            x = x + mlp
        h_last = jax.lax.dynamic_index_in_dim(x[0], nvalid - 1, axis=0,
                                              keepdims=False)
        logits = self._logits(head, h_last)
        return pool, scales, jnp.argmax(logits, axis=-1).astype(jnp.int32)
