"""Per-architecture modules of the benchmark, one file each, chosen by the
configuration's published ``model_type``: ``arch/<model_type>.py``. A new
architecture enters the benchmark as a new file here (beside its
configuration, cells and metric files); no table lists the modules.

A module gives, for a configuration ``cfg`` (the configuration file as a
dict) and its sizes ``m = dims(cfg)``:

- ``dims(cfg)``: the sizes its arithmetic needs, as a dict (the per-layer
  readers find them in the run's context under ``dims``);
- ``shapes(cfg)``: ``(layers, head)``, a list of per-layer dicts and a
  dict of leaf shapes, keyed as the program keys its executed weights;
  ``std(name, shape)``: the spread of the random normal leaf ``name``;
- ``weight_bytes(m)``: the bytes of the weights as served, and
  ``row_bytes(m)``: one row of the KV pool (every layer's K and V of one
  block), which together size a ``"fill"`` pool;
- ``decode_token_flops(m, ctx)``, ``prefill_flops(m, plen)`` and
  ``decode_attention_work(m, ctxs)``: the operations (and, for the
  attention kernel, the bytes) the model needs, never padded work;
- ``logits(cfg, layers, head, ids, start, n_out, control)``: the plain
  reference's forward pass, logits at ``start .. start + n_out - 1`` of the
  padded ``ids``; with ``control`` the int8 control's;
- ``server_kwargs(cfg, rehearse)``: the further ``ServerConfig`` arguments
  that serve this architecture at the configuration's size (or at the
  program's reduced size in a CPU rehearsal);
- ``RUNNER_COUNTERS``: the runner's attributes each counter snapshot reads;
- ``KERNELS``: trace labels of this architecture's kernels
  (``{metric name: substring of the op name}``), matched before
  ``trace_reduce.KERNELS``.

A module is imported in the load generator too, which never imports JAX:
it imports JAX (and the weight maker and reference) inside the functions
that need it.
"""
from __future__ import annotations

import functools
import importlib.util
from pathlib import Path
from types import ModuleType
from typing import Dict, List

ARCH_DIR = Path(__file__).resolve().parent


def known() -> List[str]:
    return sorted(p.stem for p in ARCH_DIR.glob("*.py")
                  if p.stem != "__init__")


def resolve(cfg: Dict) -> ModuleType:
    """The module of ``cfg["model_type"]``, loaded from its file once a
    process. A type with no module is an error, never a default."""
    name = str(cfg["model_type"])
    if name not in known():
        raise KeyError(f"no architecture module for model_type {name!r} "
                       f"(chipbench/arch/{name}.py); known: {known()}")
    return _load(name)


@functools.lru_cache(maxsize=None)
def _load(name: str) -> ModuleType:
    spec = importlib.util.spec_from_file_location(f"arch.{name}",
                                                  ARCH_DIR / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod
