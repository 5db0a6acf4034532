"""Shared model building blocks: param defs, norms, rope, init."""
from __future__ import annotations

import dataclasses
import functools
from typing import Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import shard


@dataclasses.dataclass(frozen=True)
class ParamDef:
    """Declarative parameter: shape + logical axes + init."""
    shape: Tuple[int, ...]
    logical_axes: Tuple[Optional[str], ...]
    init: str = "normal"        # "normal" | "zeros" | "ones" | "ssm_a" | "ssm_dt"
    fan_in_axis: int = 0        # axis used for 1/sqrt(fan_in) scaling


def is_param_def(x) -> bool:
    return isinstance(x, ParamDef)


@functools.partial(jax.jit, static_argnums=(1, 2))
def init_param(rng: jax.Array, d: ParamDef, dtype) -> jax.Array:
    # jitted so the float32 draw fuses into the cast: eagerly, a stacked
    # (layers, d_model, d_ff) leaf would hold two float32 copies at once
    if d.init == "zeros":
        return jnp.zeros(d.shape, dtype)
    if d.init == "ones":
        return jnp.ones(d.shape, dtype)
    if d.init == "ssm_a":
        # A_log init: log of uniform [1, 16]
        u = jax.random.uniform(rng, d.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if d.init == "ssm_dt":
        # dt bias: inverse-softplus of uniform [1e-3, 1e-1]
        u = jax.random.uniform(rng, d.shape, jnp.float32, 1e-3, 1e-1)
        return (u + jnp.log(-jnp.expm1(-u))).astype(dtype)
    fan_in = d.shape[d.fan_in_axis] if d.shape else 1
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    return (jax.random.normal(rng, d.shape, jnp.float32) * scale).astype(dtype)


def init_params(defs, rng: jax.Array, dtype) -> dict:
    leaves, treedef = jax.tree.flatten(defs, is_leaf=is_param_def)
    rngs = jax.random.split(rng, len(leaves))
    return jax.tree.unflatten(
        treedef, [init_param(r, d, dtype) for r, d in zip(rngs, leaves)])


def param_structs(defs, dtype) -> dict:
    return jax.tree.map(lambda d: jax.ShapeDtypeStruct(d.shape, dtype), defs,
                        is_leaf=is_param_def)


def param_logical_axes(defs) -> dict:
    return jax.tree.map(lambda d: (d.logical_axes, d.shape), defs,
                        is_leaf=is_param_def)


def stack_defs(defs, n: int, stack_axis_name: Optional[str] = None) -> dict:
    """Prepend a stacking dim of size n (for scan-over-layers param stacks)."""
    return jax.tree.map(
        lambda d: ParamDef((n,) + d.shape, (stack_axis_name,) + d.logical_axes,
                           d.init, d.fan_in_axis + 1),
        defs, is_leaf=is_param_def)


# ---------------------------------------------------------------------------
# Numerics
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(xf), axis=-1, keepdims=True)
    out = xf * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(x.dtype)


def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (np.arange(0, head_dim, 2, dtype=np.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., seq, heads, head_dim), positions: (..., seq)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)                        # (hd/2,)
    angles = positions[..., :, None].astype(jnp.float32) * freqs  # (..., S, hd/2)
    cos = jnp.cos(angles)[..., :, None, :]               # (..., S, 1, hd/2)
    sin = jnp.sin(angles)[..., :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(x: jax.Array, w_gate: jax.Array, w_up: jax.Array,
           w_down: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, w_gate)
    u = jnp.einsum("...d,df->...f", x, w_up)
    g = shard(g, ("batch", "seq", "mlp"))
    h = jax.nn.silu(g.astype(jnp.float32)).astype(x.dtype) * u
    out = jnp.einsum("...f,fd->...d", h, w_down)
    return shard(out, ("batch", "seq", "embed"))


def dtype_of(name: str):
    return {"bfloat16": jnp.bfloat16, "float32": jnp.float32,
            "float16": jnp.float16}[name]


@dataclasses.dataclass(frozen=True)
class ArraySpec:
    """Shape+dtype+logical-axes triple (for caches / inputs)."""
    shape: Tuple[int, ...]
    dtype: str
    logical_axes: Tuple[Optional[str], ...]

    def struct(self) -> jax.ShapeDtypeStruct:
        return jax.ShapeDtypeStruct(self.shape, dtype_of(self.dtype)
                                    if self.dtype in ("bfloat16", "float32", "float16")
                                    else np.dtype(self.dtype))


def is_array_spec(x) -> bool:
    return isinstance(x, ArraySpec)


def specs_to_structs(tree):
    return jax.tree.map(lambda s: s.struct(), tree, is_leaf=is_array_spec)


def specs_to_zeros(tree):
    return jax.tree.map(lambda s: jnp.zeros(s.shape, s.struct().dtype), tree,
                        is_leaf=is_array_spec)


def specs_logical_axes(tree):
    return jax.tree.map(lambda s: (s.logical_axes, s.shape), tree,
                        is_leaf=is_array_spec)
