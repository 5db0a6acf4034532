"""Pallas TPU paged decode attention over a BLOCK-FIRST KV pool.

This is the paper's §4.3.2 kernel contribution adapted to TPU: the pool is
laid out (num_blocks, 2, P, Hkv, D) so one logical block's K+V is one
contiguous region (the transfer engine moves whole rows of dim 0), and the
attention kernel follows the new stride via its BlockSpec index_map — the
block table is scalar-prefetched so the index_map can do the indirection.

Grid: (B, num_blocks_per_seq) with the block dim innermost; VMEM scratch
carries the online-softmax state across a request's blocks.

Quantized KV tier (``kv_scales`` passed): the pool is int8 and HBM reads
stay int8 — only the (P, Hkv, D) tile in VMEM is widened, and the per-
(block, layer, K/V, head) fp32 scales ride as a small side ref addressed by
the SAME block-table indirection, so dequantization is fused into the
attention kernel (no dequantized copy of the pool ever exists in HBM).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret

NEG_INF = -1e30


def _paged_kernel(bt_ref, cl_ref, q_ref, kv_ref, *rest, scale: float,
                  page: int, group: int, layered: bool, quantized: bool):
    if quantized:
        sc_ref, o_ref, acc_ref, m_ref, l_ref = rest
    else:
        o_ref, acc_ref, m_ref, l_ref = rest
        sc_ref = None
    b = pl.program_id(0)
    j = pl.program_id(1)
    nb = pl.num_programs(1)

    @pl.when(j == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale            # (Hkv, G, D)
    kv = kv_ref[0, 0] if layered else kv_ref[0]
    k = kv[0].astype(jnp.float32)                       # (P, Hkv, D)
    v = kv[1].astype(jnp.float32)
    if quantized:
        # fused dequant: one fp32 scale per (K/V side, kv head) of this
        # block — the HBM tile stayed int8, only VMEM sees floats
        sc = sc_ref[0, 0] if layered else sc_ref[0]     # (2, Hkv)
        k = k * sc[0][None, :, None]
        v = v * sc[1][None, :, None]
    kt = k.transpose(1, 0, 2)                           # (Hkv, P, D)
    vt = v.transpose(1, 0, 2)

    # s: (Hkv, G, P) — batched over kv heads, contracted over D
    s = jax.lax.dot_general(q, kt, (((2,), (2,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
    pos = j * page + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
    s = jnp.where(pos < cl_ref[b], s, NEG_INF)

    m_prev, l_prev = m_ref[...], l_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=2))
    p = jnp.exp(s - m_new[..., None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=2)
    pv = jax.lax.dot_general(p, vt, (((2,), (1,)), ((0,), (0,))),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[..., None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(j == nb - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)[..., None]
                    ).astype(o_ref.dtype)


def paged_attention_tpu(q: jax.Array, kv_pool: jax.Array,
                        block_tables: jax.Array, context_lens: jax.Array,
                        *, layer: int = -1,
                        kv_scales: Optional[jax.Array] = None,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, H, D); kv_pool: (NB, 2, P, Hkv, D) block-first;
    block_tables: (B, MB) int32; context_lens: (B,) int32 -> (B, H, D).

    ``layer >= 0`` addresses a multi-layer pool (NB, L, 2, P, Hkv, D) whose
    rows hold *every* layer of one logical block contiguously (the paper's
    block-first layout, segments_per_block == 1): the BlockSpec index_map
    picks (block row, layer) so no per-layer slice of the pool is ever
    materialized outside the kernel.

    ``kv_scales`` enables the quantized tier: the pool is int8 and scales
    — fp32, shaped (NB, 2, Hkv) or (NB, L, 2, Hkv) when layered — are
    dequantized inside the kernel (one multiply per tile). Omitted (the
    default), the call is bit-identical to the unquantized kernel.
    """
    B, H, D = q.shape
    layered = layer >= 0
    quantized = kv_scales is not None
    if layered:
        NB, _, _, P, Hkv, _ = kv_pool.shape
    else:
        NB, _, P, Hkv, _ = kv_pool.shape
    MB = block_tables.shape[1]
    group = H // Hkv
    qg = q.reshape(B, Hkv, group, D)

    kernel = functools.partial(_paged_kernel, scale=D ** -0.5, page=P,
                               group=group, layered=layered,
                               quantized=quantized)
    if layered:
        kv_spec = pl.BlockSpec(
            (1, 1, 2, P, Hkv, D),
            lambda b, j, bt, cl: (bt[b, j], layer, 0, 0, 0, 0))
        sc_spec = pl.BlockSpec(
            (1, 1, 2, Hkv), lambda b, j, bt, cl: (bt[b, j], layer, 0, 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, 2, P, Hkv, D),
            lambda b, j, bt, cl: (bt[b, j], 0, 0, 0, 0))
        sc_spec = pl.BlockSpec(
            (1, 2, Hkv), lambda b, j, bt, cl: (bt[b, j], 0, 0))
    in_specs = [
        pl.BlockSpec((1, Hkv, group, D), lambda b, j, bt, cl: (b, 0, 0, 0)),
        kv_spec,
    ]
    operands = [block_tables, context_lens, qg, kv_pool]
    if quantized:
        in_specs.append(sc_spec)
        operands.append(kv_scales)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, MB),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, Hkv, group, D),
                               lambda b, j, bt, cl: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((Hkv, group, D), jnp.float32),
            pltpu.VMEM((Hkv, group), jnp.float32),
            pltpu.VMEM((Hkv, group), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, Hkv, group, D), q.dtype),
        interpret=resolve_interpret(interpret),
    )(*operands)
    return out.reshape(B, H, D)
