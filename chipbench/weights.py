"""Random weights from ``--seed``, made on the device in one jitted call,
in the dtype they are served in. The benchmark makes them (not the program)
so that the reference can make the same ones again without taking anything
the program produced.

Shapes and spreads come from the configuration's architecture module
(``arch``); ``shapes`` and ``std`` below are the dense decoder's. Its
layout: ``layers`` is a list of per-layer dicts and ``head`` a dict, keyed
as the paged runner keys its executed weights. Norm weights are stored as
offsets from one (a norm's published weight is ``1 + ln``), which is how
the runner parametrises them. Qwen2's q/k/v biases are zero: the runner
has none (see the configuration's ``departures``).
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import jax
import jax.numpy as jnp

from arch import resolve
from flops import dims

NORM_STD = 0.1       # spread of the norm weights around one


def _key(seed: int) -> jax.Array:
    # seeds may exceed 32 bits: fold the high part in
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                              seed >> 32)


def shapes(cfg: Dict) -> Tuple[List[Dict[str, tuple]], Dict[str, tuple]]:
    m = dims(cfg)
    d, h, kv, hd, f, v = m["d"], m["h"], m["kv"], m["hd"], m["f"], m["v"]
    layer = dict(ln1=(d,), wq=(d, h, hd), wk=(d, kv, hd), wv=(d, kv, hd),
                 wo=(h, hd, d), ln2=(d,), w_gate=(d, f), w_up=(d, f),
                 w_down=(f, d))
    head = dict(embed=(v, d), final_norm=(d,), lm_head=(d, v))
    return [layer] * m["layers"], head


def std(name: str, shape: tuple) -> float:
    """Scales that keep activations near unit size: embeddings unit normal,
    projections 1/sqrt(fan-in), norms around one."""
    if name in ("ln1", "ln2", "final_norm"):
        return NORM_STD
    if name == "embed":
        return 1.0
    if name == "wo":
        return (shape[0] * shape[1]) ** -0.5
    return shape[0] ** -0.5


def make(cfg: Dict, seed: int, dtype=None):
    """(layers, head) on the default device, in one jitted call: each leaf
    normal from its own split of the seed's key, leaves in layer order and
    by name within a layer, then the head's."""
    dtype = dtype or (jnp.bfloat16 if cfg["torch_dtype"] == "bfloat16"
                      else jnp.float32)
    arch = resolve(cfg)
    lshapes, hshapes = arch.shapes(cfg)
    names = [(i, k, s) for i, lay in enumerate(lshapes)
             for k, s in sorted(lay.items())]
    names += [(-1, k, s) for k, s in sorted(hshapes.items())]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        out = []
        for k, (_, name, shape) in zip(keys, names):
            out.append((jax.random.normal(k, shape, dtype)
                        * jnp.asarray(arch.std(name, shape), dtype)))
        return out

    leaves = build(_key(seed))
    layers = [dict() for _ in lshapes]
    head: Dict[str, jax.Array] = {}
    for (i, name, _), x in zip(names, leaves):
        (head if i < 0 else layers[i])[name] = x
    return layers, head
