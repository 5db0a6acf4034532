"""Pallas TPU flash attention (forward), causal + sliding-window.

Grid: (B*H, num_q_blocks, num_kv_blocks) with the kv dimension innermost so
the VMEM scratch accumulators (running max / sum / output tile) persist
across kv iterations. BlockSpecs tile q/k/v into (block_q|block_k, D) VMEM
tiles; block sizes default to 128 to align with the MXU's 128-lane systolic
array and bf16 (8,128) native tiling.
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


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, causal: bool, window: int,
                  block_q: int, block_k: int, kv_seq: int, q_seq: int):
    qi = pl.program_id(1)
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    q = q_ref[0].astype(jnp.float32) * scale          # (bq, D)
    k = k_ref[0].astype(jnp.float32)                  # (bk, D)
    v = v_ref[0].astype(jnp.float32)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32)  # (bq, bk)

    off = kv_seq - q_seq
    qpos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) + off
    kpos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    mask = kpos < kv_seq
    if causal:
        mask &= kpos <= qpos
    if window > 0:
        mask &= kpos > qpos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]                                # (bq,)
    l_prev = l_ref[...]
    m_cur = jnp.max(s, axis=1)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_new = l_prev * corr + jnp.sum(p, axis=1)
    pv = jax.lax.dot_general(p, v, (((1,), (0,)), ((), ())),
                             preferred_element_type=jnp.float32)
    acc_ref[...] = acc_ref[...] * corr[:, None] + pv
    m_ref[...] = m_new
    l_ref[...] = l_new

    @pl.when(ki == nk - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / jnp.maximum(l, 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_tpu(q: jax.Array, k: jax.Array, v: jax.Array, *,
                        causal: bool = True, window: int = 0,
                        block_q: int = 128, block_k: int = 128,
                        interpret: Optional[bool] = None) -> jax.Array:
    """q: (B, Sq, H, D); k/v: (B, Skv, H, D) (pre-repeated GQA heads).

    Layout: internally (B*H, S, D). Returns (B, Sq, H, D).
    """
    B, Sq, H, D = q.shape
    Skv = k.shape[1]
    qf = q.transpose(0, 2, 1, 3).reshape(B * H, Sq, D)
    kf = k.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)
    vf = v.transpose(0, 2, 1, 3).reshape(B * H, Skv, D)

    bq = min(block_q, Sq)
    bk = min(block_k, Skv)
    pad_q = (-Sq) % bq
    pad_k = (-Skv) % bk
    if pad_q:
        qf = jnp.pad(qf, ((0, 0), (0, pad_q), (0, 0)))
    if pad_k:
        kf = jnp.pad(kf, ((0, 0), (0, pad_k), (0, 0)))
        vf = jnp.pad(vf, ((0, 0), (0, pad_k), (0, 0)))
    nq = (Sq + pad_q) // bq
    nk = (Skv + pad_k) // bk

    kernel = functools.partial(
        _flash_kernel, scale=D ** -0.5, causal=causal, window=window,
        block_q=bq, block_k=bk, kv_seq=Skv, q_seq=Sq)

    out = pl.pallas_call(
        kernel,
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
            pl.BlockSpec((1, bk, D), lambda b, i, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bq, D), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, Sq + pad_q, D), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, D), jnp.float32),   # acc
            pltpu.VMEM((bq,), jnp.float32),     # running max
            pltpu.VMEM((bq,), jnp.float32),     # running sum
        ],
        interpret=resolve_interpret(interpret),
    )(qf, kf, vf)

    out = out[:, :Sq]
    return out.reshape(B, H, Sq, D).transpose(0, 2, 1, 3)
