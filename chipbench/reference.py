"""The plain reference: the published decoder (Qwen2 / Llama: RMSNorm,
rotate-half RoPE, grouped-query causal attention, SwiGLU, untied head) in
float32 at ``highest`` matmul precision, straight ``jax.numpy``, with no
kernel, cache or batching. It imports nothing of the program and takes
nothing it made: its weights come from ``weights.make`` with the run's seed.

It runs layer by layer over one request at a time (prompt followed by the
tokens the server streamed back), padded to one fixed length per cell so
one compile serves every run, and reads, at each served token, by how much
that token's logit lies below the reference's best (``gaps``).

The control (``control=True``) is the same computation with every weight
matrix rounded to int8 (symmetric, one scale per output channel), the next
precision below the configuration's bfloat16: it reports the reference's
gap of the token the int8 model puts first at each of those positions.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np


HI = jax.lax.Precision.HIGHEST
QBLOCK = 256          # query rows per attention block


def _int8(w: jax.Array, axes) -> jax.Array:
    """Round to int8 with one scale per output channel (``axes`` are the
    contracted ones), returned dequantised in float32."""
    w = w.astype(jnp.float32)
    s = jnp.max(jnp.abs(w), axis=axes, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.clip(jnp.round(w / s), -127, 127) * s


def _norm(x, delta, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + delta.astype(jnp.float32))


def _rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd))
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@functools.partial(jax.jit, static_argnames=("eps", "theta", "control"))
def _layer(x, w, *, eps, theta, control):
    """One decoder layer over the whole padded sequence ``x`` (T, d)."""
    f32 = (lambda a, axes: _int8(a, axes)) if control else \
        (lambda a, axes: a.astype(jnp.float32))
    T = x.shape[0]
    pos = jnp.arange(T)
    h = _norm(x, w["ln1"], eps)
    q = jnp.einsum("td,dhk->thk", h, f32(w["wq"], (0,)), precision=HI)
    k = jnp.einsum("td,dhk->thk", h, f32(w["wk"], (0,)), precision=HI)
    v = jnp.einsum("td,dhk->thk", h, f32(w["wv"], (0,)), precision=HI)
    if "bq" in w:                           # Qwen2's q/k/v biases
        q = q + w["bq"].astype(jnp.float32)
        k = k + w["bk"].astype(jnp.float32)
        v = v + w["bv"].astype(jnp.float32)
    q = _rope(q, pos, theta)
    k = _rope(k, pos, theta)
    H, hd = q.shape[1], q.shape[2]
    kv = k.shape[1]
    g = H // kv
    qg = q.reshape(T // QBLOCK, QBLOCK, kv, g, hd)
    scale = hd ** -0.5

    def block(args):
        qb, i = args
        s = jnp.einsum("qcgk,tck->cgqt", qb, k, precision=HI) * scale
        rows = i * QBLOCK + jnp.arange(QBLOCK)
        s = jnp.where(rows[:, None] >= pos[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("cgqt,tck->qcgk", p, v, precision=HI)

    o = jax.lax.map(block, (qg, jnp.arange(T // QBLOCK)))
    o = o.reshape(T, H, hd)
    x = x + jnp.einsum("thk,hkd->td", o, f32(w["wo"], (0, 1)), precision=HI)
    h = _norm(x, w["ln2"], eps)
    gt = jnp.einsum("td,df->tf", h, f32(w["w_gate"], (0,)), precision=HI)
    up = jnp.einsum("td,df->tf", h, f32(w["w_up"], (0,)), precision=HI)
    return x + jnp.einsum("tf,fd->td", jax.nn.silu(gt) * up,
                         f32(w["w_down"], (0,)), precision=HI)


@functools.partial(jax.jit, static_argnames=("n_out", "eps", "control"))
def _head(x, head, start, *, n_out, eps, control):
    """Logits at positions ``start .. start + n_out - 1``."""
    h = jax.lax.dynamic_slice_in_dim(x, start, n_out, axis=0)
    h = _norm(h, head["final_norm"], eps)
    w = (_int8(head["lm_head"], (0,)) if control
         else head["lm_head"].astype(jnp.float32))
    return jnp.einsum("td,dv->tv", h, w, precision=HI)


@functools.partial(jax.jit, static_argnames=("control",))
def _embed(embed, ids, *, control):
    e = _int8(embed, (1,)) if control else embed.astype(jnp.float32)
    return jnp.take(e, ids, axis=0)


@jax.jit
def _gaps(ref_logits, tokens, pick_logits):
    """Reference gap below its best of ``tokens``, and of the tokens that
    ``pick_logits`` put first."""
    best = jnp.max(ref_logits, axis=-1)
    served = jnp.take_along_axis(ref_logits, tokens[:, None], 1)[:, 0]
    picked = jnp.argmax(pick_logits, axis=-1)
    alt = jnp.take_along_axis(ref_logits, picked[:, None], 1)[:, 0]
    return best - served, best - alt


def logits(cfg: Dict, layers, head, ids: np.ndarray, start: int, n_out: int,
           control: bool = False) -> jax.Array:
    eps = float(cfg["rms_norm_eps"])
    theta = float(cfg["rope_theta"])
    x = _embed(head["embed"], jnp.asarray(ids), control=control)
    for w in layers:
        x = _layer(x, w, eps=eps, theta=theta, control=control)
    return _head(x, head, jnp.asarray(start, jnp.int32), n_out=n_out,
                 eps=eps, control=control)


def gaps(cfg: Dict, layers, head, prompt: Sequence[int],
         served: Sequence[int], pad_len: int, n_out: int,
         control: bool = False, forward=logits) -> Dict[str, List[float]]:
    """Per served token: ``served`` is the reference's gap of the token the
    server sent, and with ``control`` also ``control``, the gap of the token
    the int8 model puts first at the same position. ``forward`` is the
    architecture's reference pass (``arch``'s ``logits``); this file's
    ``logits`` is the dense decoder's."""
    p, n = len(prompt), len(served)
    seq = list(prompt) + list(served[:-1])
    if p - 1 + n_out > pad_len or n > n_out:
        raise ValueError(f"sequence {len(seq)}/{n} exceeds the reference's "
                         f"padding {pad_len}/{n_out}")
    ids = np.zeros(pad_len, np.int32)
    ids[:len(seq)] = seq
    toks = np.zeros(n_out, np.int32)
    toks[:n] = served
    ref = forward(cfg, layers, head, ids, p - 1, n_out)
    pick = forward(cfg, layers, head, ids, p - 1, n_out, control=True) \
        if control else ref
    g_served, g_ctrl = jax.device_get(_gaps(ref, jnp.asarray(toks), pick))
    out = dict(served=[float(x) for x in g_served[:n]])
    if control:
        out["control"] = [float(x) for x in g_ctrl[:n]]
    return out
