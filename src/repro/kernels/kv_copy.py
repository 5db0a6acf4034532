"""Pallas TPU batched KV-block rotation — the cudaMemcpyBatchAsync analogue.

One ``pallas_call`` moves N whole block-first pool rows (pool[dst[i]] =
pool[src[i]]) in a single launch: the descriptor table (src, dst) is
scalar-prefetched, the grid walks (descriptor, row slab), and the output
aliases the pool so untouched rows keep their contents. This merges
thousands of per-segment copies into one kernel launch, the paper's
batched-transfer remedy for launch-overhead-bound rotation.

Tiling: a block is one row's slab along dim 1 with every later dim whole —
``(1, 1, 2, P, Hkv, D)`` for a ``(NB, L, 2, P, Hkv, D)`` pool and
``(1, 1, 2, Hkv)`` for its int8 scale rows — so the block's last two dims
always equal the array's, which the TPU compiler accepts for any width. A
2-D ``(NB, F)`` array is viewed as ``(NB, 1, F)``.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import resolve_interpret


def _copy_kernel(src_ref, dst_ref, pool_ref, out_ref):
    out_ref[...] = pool_ref[...]


def kv_copy_tpu(pool: jax.Array, src: jax.Array, dst: jax.Array, *,
                interpret: Optional[bool] = None) -> jax.Array:
    """pool: (NB, ...); src/dst: (N,) int32. Returns pool with
    ``pool[dst[i]] = pool[src[i]]`` (aliased with the input: no copy of
    the pool on TPU).

    A lane with ``src[i] < 0`` is padding: it rewrites row ``dst[i]`` with
    that row's own contents, since the chip writes every visited output
    block back. Point padded lanes at a row no other lane of the batch
    writes (``PagedKVStore`` uses its trash row); row contents then never
    change.
    """
    nb = pool.shape[0]
    view = pool if pool.ndim >= 3 else pool.reshape(nb, 1, -1)
    n, slabs = src.shape[0], view.shape[1]
    block = (1, 1) + view.shape[2:]
    tail = (0,) * (view.ndim - 2)

    def src_map(i, s, src, dst):
        return (jnp.where(src[i] >= 0, src[i], dst[i]), s) + tail

    def dst_map(i, s, src, dst):
        return (dst[i], s) + tail

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n, slabs),
        in_specs=[pl.BlockSpec(block, src_map)],
        out_specs=pl.BlockSpec(block, dst_map),
    )
    out = pl.pallas_call(
        _copy_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct(view.shape, view.dtype),
        input_output_aliases={2: 0},
        interpret=resolve_interpret(interpret),
    )(src, dst, view)
    return out.reshape(pool.shape)
