"""Pallas TPU paged decode attention over a BLOCK-FIRST KV pool.

This is the paper's §4.3.2 kernel contribution adapted to TPU: the pool is
laid out (num_blocks, 2, P, Hkv, D) so one logical block's K+V is one
contiguous region (the transfer engine moves whole rows of dim 0), and the
kernel gathers a request's rows straight from HBM through the block table,
which is scalar-prefetched into SMEM.

Grid: (B,), one lane per step, sized by the real contexts. A lane walks
only its live blocks, ``ceil(context_len / P)`` of them, in tiles of ``n``
consecutive block-table entries (``blocks_per_tile``: a power of two, at
most the table's width, bounded by ``TILE_BYTES`` of VMEM). Each tile's
rows are DMA'd from HBM into one slot of a two-slot VMEM buffer while the
previous tile is computed, and the last tile of a lane prefetches the
next lane's first. Block slots past a lane's context are never read, and
a padded lane (context 1 over the trash row) costs one block.

Tile math: the tile's K and V are viewed as ``(n*P*Hkv, D)`` matrices of
(token, kv head) rows, so the scores of every query head come from ONE
MXU contraction, ``(H, D) x (n*P*Hkv, D)^T``, with a head-match mask, and
the weighted sum from one more; no per-block transposes. Scores, the
online softmax and the accumulator are float32.

Quantized KV tier (``kv_scales`` passed): the pool is int8 and HBM reads
stay int8 — only the tile in VMEM is widened, and the per-(block, layer,
K/V, head) fp32 scales are gathered through the SAME block table and
handed to the kernel tile by tile, so dequantization is fused into the
attention kernel (no dequantized copy of the pool ever exists in HBM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax import lax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30
TILE_BYTES = 1 << 20      # one VMEM buffer slot: n pool rows of one layer


def blocks_per_tile(max_blocks: int, block_bytes: int) -> int:
    """Block-table entries per tile: the largest power of two that is at
    most ``max_blocks`` and keeps ``n * block_bytes`` within TILE_BYTES."""
    n = max(1, min(max_blocks, TILE_BYTES // block_bytes))
    return 1 << (n.bit_length() - 1)


def _paged_kernel(bt_ref, cl_ref, q_ref, kv_hbm, *rest, scale: float,
                  page: int, group: int, n: int, layer: int,
                  quantized: bool):
    if quantized:
        sc_ref, o_ref, kv_buf, sem, slot_ref = rest
    else:
        o_ref, kv_buf, sem, slot_ref = rest
    b = pl.program_id(0)
    lanes = pl.num_programs(0)
    mb = bt_ref.shape[1]
    hkv = kv_buf.shape[-2]

    # integer arithmetic goes straight to lax: jnp's floor-division and
    # remainder trace a dozen ops each, and the kernel is traced and
    # lowered once per layer of every padded batch shape

    def live(lane, t):
        """Live blocks in tile t of the lane: the only ones read."""
        nblk = lax.min(pl.cdiv(cl_ref[lane], page), mb)
        return lax.clamp(0, nblk - t * n, n)

    def copy(slot, lane, t, i):
        row = bt_ref[lane, t * n + i]
        at = (row,) if layer < 0 else (row, layer)
        return pltpu.make_async_copy(kv_hbm.at[at], kv_buf.at[slot, i],
                                     sem.at[slot])

    def each(lo, hi, fn):
        def step(i, carry):
            fn(i)
            return carry
        lax.fori_loop(lo, hi, step, 0)

    def start(slot, lane, t):
        each(0, live(lane, t), lambda i: copy(slot, lane, t, i).start())

    @pl.when(b == 0)
    def _first():
        # blocks a tail tile leaves unread keep what the buffer held, and
        # 0 * NaN is NaN: let that be finite data, never raw VMEM
        kv_buf[:, :, 1] = jnp.zeros(kv_buf.shape[:2] + kv_buf.shape[3:],
                                    kv_buf.dtype)
        slot_ref[0] = 0
        start(0, 0, 0)

    first = slot_ref[0]
    cl = cl_ref[b]
    # a lane with no context still runs one tile, of no reads, and
    # writes zeros
    ntile = lax.max(pl.cdiv(lax.min(pl.cdiv(cl, page), mb), n), 1)

    q = q_ref[0].astype(jnp.float32) * scale            # (H, D)
    H, D = q.shape
    rows = n * page * hkv
    col = lax.broadcasted_iota(jnp.int32, (H, rows), 1)
    head_ok = lax.rem(col, hkv) == lax.div(
        lax.broadcasted_iota(jnp.int32, (H, rows), 0), group)
    tok = lax.div(col, hkv)                             # token in tile

    def body(t, carry):
        m_prev, l_prev, acc = carry
        slot = lax.rem(first + t, 2)
        more = t + 1 < ntile

        # prefetch the lane's next tile, or after its last the next lane's
        @pl.when(more | (b + 1 < lanes))
        def _():
            start(1 - slot, lax.select(more, b, b + 1),
                  lax.select(more, t + 1, 0))

        nlive = live(b, t)
        each(0, nlive, lambda i: copy(slot, b, t, i).wait())
        left = cl - t * (n * page)                      # tokens from tile start
        k = kv_buf[slot, :, 0].astype(jnp.float32)     # (n, P, Hkv, D)
        v = kv_buf[slot, :, 1].astype(jnp.float32)
        if quantized:
            # fused dequant: one fp32 scale per (block, K/V side, kv head)
            read = lax.broadcasted_iota(jnp.int32, (n, 2 * hkv), 0) < nlive
            sc = jnp.where(read, sc_ref[0, t], 0.0)    # (n, 2 * Hkv)
            k = k * sc[:, :hkv][:, None, :, None]
            v = v * sc[:, hkv:][:, None, :, None]
        s = lax.dot_general(q, k.reshape(rows, D), (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)
        s = jnp.where(head_ok & (tok < left), s, NEG_INF)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_new = l_prev * corr + jnp.sum(p, axis=1, keepdims=True)
        pv = lax.dot_general(p, v.reshape(rows, D), (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
        return m_new, l_new, acc * corr + pv

    init = (jnp.full((H, 1), NEG_INF, jnp.float32),
            jnp.zeros((H, 1), jnp.float32), jnp.zeros((H, D), jnp.float32))
    _, l, acc = lax.fori_loop(0, ntile, body, init)
    slot_ref[0] = lax.rem(first + ntile, 2)
    out = jnp.where(cl > 0, acc / jnp.maximum(l, 1e-30), 0.0)
    o_ref[0] = out.astype(o_ref.dtype)


def paged_attention_tpu(q: jax.Array, kv_pool: jax.Array,
                        block_tables: jax.Array, context_lens: jax.Array,
                        *, layer: int = -1,
                        kv_scales: Optional[jax.Array] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, D); kv_pool: (NB, 2, P, Hkv, D) block-first;
    block_tables: (B, MB) int32; context_lens: (B,) int32 -> (B, H, D).

    Lane b attends over its first ``context_lens[b]`` tokens; only the
    table entries they occupy are read. ``context_lens[b] == 0`` gives a
    zero output and reads nothing.

    ``layer >= 0`` addresses a multi-layer pool (NB, L, 2, P, Hkv, D) whose
    rows hold *every* layer of one logical block contiguously (the paper's
    block-first layout, segments_per_block == 1): the kernel's DMAs pick
    (block row, layer) so no per-layer slice of the pool is ever
    materialized outside the kernel.

    ``kv_scales`` enables the quantized tier: the pool is int8 and scales
    — fp32, shaped (NB, 2, Hkv) or (NB, L, 2, Hkv) when layered — are
    dequantized inside the kernel (one multiply per tile).
    """
    B, H, D = q.shape
    quantized = kv_scales is not None
    row = kv_pool.shape[2:] if layer >= 0 else kv_pool.shape[1:]
    _, P, Hkv, _ = row
    MB = block_tables.shape[1]
    n = blocks_per_tile(MB, kv_pool.dtype.itemsize * 2 * P * Hkv * D)

    kernel = functools.partial(_paged_kernel, scale=D ** -0.5, page=P,
                               group=H // Hkv, n=n, layer=layer,
                               quantized=quantized)
    lane = pl.BlockSpec((1, H, D), lambda b, bt, cl: (b, 0, 0))
    in_specs = [lane, pl.BlockSpec(memory_space=pl.ANY)]
    operands = [block_tables, context_lens, q, kv_pool]
    if quantized:
        # a (2, Hkv) slab of scales is narrower than a DMA may slice, so
        # the block table gathers each lane's scales, tile by tile
        T = pl.cdiv(MB, n)
        bts = jnp.pad(block_tables, ((0, 0), (0, T * n - MB)))
        sc = kv_scales[bts] if layer < 0 else kv_scales[bts, layer]
        in_specs.append(pl.BlockSpec((1, T, n, 2 * Hkv),
                                     lambda b, bt, cl: (b, 0, 0, 0)))
        operands.append(sc.reshape(B, T, n, 2 * Hkv))
    scratch = [pltpu.VMEM((2, n) + tuple(row), kv_pool.dtype),
               pltpu.SemaphoreType.DMA((2,)), pltpu.SMEM((1,), jnp.int32)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(B,), in_specs=in_specs,
        out_specs=lane, scratch_shapes=scratch)
    return pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, H, D), q.dtype),
        # lanes hand DMAs to each other: the grid runs in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=resolve_interpret(interpret),
    )(*operands)
