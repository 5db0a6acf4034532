"""Operations and bytes the algorithm needs, from the configuration's sizes
and the client's token counts and context lengths. Never from padded
shapes or the compiler's cost analysis: padding is work the chip does but
the model does not need, so it must not count toward a share of a peak.
Imports nothing of JAX."""
from __future__ import annotations

from typing import Dict, Iterable, Tuple


def dims(cfg: Dict) -> Dict[str, int]:
    """The sizes the arithmetic needs, from a configuration file (keys as
    the published ``config.json`` names them)."""
    d = int(cfg["hidden_size"])
    h = int(cfg["num_attention_heads"])
    return dict(d=d, h=h, kv=int(cfg["num_key_value_heads"]),
                hd=int(cfg.get("head_dim", d // h)),
                f=int(cfg["intermediate_size"]), v=int(cfg["vocab_size"]),
                layers=int(cfg["num_hidden_layers"]),
                elt=2 if cfg["torch_dtype"] == "bfloat16" else 4)


def layer_matmul_params(m: Dict[str, int]) -> int:
    """Weights one token multiplies through in one decoder layer: the q, k,
    v and o projections and the gated MLP."""
    d, h, kv, hd, f = m["d"], m["h"], m["kv"], m["hd"], m["f"]
    return d * h * hd + 2 * d * kv * hd + h * hd * d + 3 * d * f


def weight_bytes(m: Dict[str, int]) -> int:
    """The weights as served: every layer's matrices and its two norms, the
    embedding and the untied head, and the final norm."""
    return (m["layers"] * (layer_matmul_params(m) + 2 * m["d"])
            + 2 * m["v"] * m["d"] + m["d"]) * m["elt"]


def attn_flops(m: Dict[str, int], ctx: int) -> int:
    """One query over ``ctx`` keys in one layer: q.k and p.v, 2 FLOPs a MAC."""
    return 4 * ctx * m["h"] * m["hd"]


def kv_bytes(m: Dict[str, int], ctx: int) -> int:
    """K and V of ``ctx`` tokens in one layer."""
    return 2 * ctx * m["kv"] * m["hd"] * m["elt"]


def decode_token_flops(m: Dict[str, int], ctx: int) -> int:
    """The model FLOPs of producing one token whose step attends over
    ``ctx`` tokens (the new one included): every layer, then the head."""
    return (m["layers"] * (2 * layer_matmul_params(m) + attn_flops(m, ctx))
            + 2 * m["d"] * m["v"])


def prefill_flops(m: Dict[str, int], plen: int) -> int:
    """The model FLOPs of a ``plen``-token prompt: every token through every
    layer, causal attention (token i sees i + 1 keys), and the head for the
    last position only, which is all that a first token needs."""
    pairs = plen * (plen + 1) // 2
    return (m["layers"] * (2 * plen * layer_matmul_params(m)
                           + 4 * pairs * m["h"] * m["hd"])
            + 2 * m["d"] * m["v"])


def decode_attention_work(m: Dict[str, int],
                          ctxs: Iterable[int]) -> Tuple[int, int]:
    """(FLOPs, bytes) the paged-attention kernel needs for decode steps at
    the given contexts, over every layer: read each context's K and V once,
    read q and write the output (one token, every query head)."""
    flops = nbytes = 0
    qo = 2 * m["h"] * m["hd"] * m["elt"]
    for c in ctxs:
        flops += m["layers"] * attn_flops(m, c)
        nbytes += m["layers"] * (kv_bytes(m, c) + qo)
    return flops, nbytes


def row_bytes(m: Dict[str, int], block: int = 16) -> int:
    """One KV block row of the pool: every layer's K and V of ``block``
    tokens."""
    return m["layers"] * kv_bytes(m, block)


def roofline_seconds(flops: float, nbytes: float,
                     peak: Dict[str, float]) -> Tuple[float, str]:
    """The least time the chip could take, and which bound sets it."""
    tc = flops / peak["bf16_flops_per_s"]
    tm = nbytes / peak["hbm_bytes_per_s"]
    return (tc, "compute") if tc >= tm else (tm, "memory")
