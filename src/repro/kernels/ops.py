"""jit'd public ops: dispatch Pallas TPU kernels on TPU, oracles elsewhere.

``force`` overrides: "pallas" (interpret mode off the TPU, as
``repro.kernels.resolve_interpret`` decides — used by tests), "ref"
(pure-jnp oracle), None (auto: pallas on TPU, ref otherwise).
"""
from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.kernels import ref as ref_ops
from repro.kernels.flash_attention import flash_attention_tpu
from repro.kernels.kv_copy import kv_copy_tpu
from repro.kernels.paged_attention import paged_attention_tpu


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("causal", "window", "force"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    force: Optional[str] = None):
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return flash_attention_tpu(q, k, v, causal=causal, window=window)
    return ref_ops.flash_attention_ref(q, k, v, causal=causal, window=window)


@functools.partial(jax.jit, static_argnames=("force",))
def paged_attention(q, kv_pool, block_tables, context_lens, *,
                    force: Optional[str] = None):
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return paged_attention_tpu(q, kv_pool, block_tables, context_lens)
    return ref_ops.paged_attention_ref(q, kv_pool, block_tables, context_lens)


@functools.partial(jax.jit, static_argnames=("force",), donate_argnums=(0,))
def kv_copy(pool, src, dst, *, force: Optional[str] = None):
    use_pallas = force == "pallas" or (force is None and _on_tpu())
    if use_pallas:
        return kv_copy_tpu(pool, src, dst)
    return ref_ops.kv_copy_ref(pool, src, dst)
