"""Host-environment tuning knobs shared by serve entry points and CI.

``enable_compile_cache()`` points JAX's persistent compilation cache at a
fixed directory. The other two concerns must act BEFORE the first ``jax``
import:

* ``ensure_host_devices(n)`` — a CPU host exposes one XLA device unless
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` is set at import
  time; the tensor-parallel paged runner needs N >= tp. This helper sets
  the flag when jax is not yet imported, and fails loudly (with the
  recipe) when it is too late.
* ``launch/env.sh`` — the shell-side counterpart capturing the tcmalloc /
  ``XLA_FLAGS`` / log-level exemplars (per the SNIPPETS.md run.sh recipes)
  so local runs and CI share one environment.

This module must never import jax at module scope.
"""
from __future__ import annotations

import os
import sys
from pathlib import Path

_FLAG = "--xla_force_host_platform_device_count"
# fixed and checkout-relative: a cache whose directory moves between runs
# (temporary, pid- or time-based) never hits
CHECKOUT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is what JAX already uses and
    nothing here overrides it. Otherwise the cache goes to ``.jax_cache/``
    at the checkout root, so a second run of the same entry point in the
    same checkout skips the compiles of the first. Call after
    ``ensure_host_devices`` (this imports jax)."""
    import jax
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE_DIR))
    return str(CHECKOUT_CACHE_DIR)


def ensure_host_devices(n: int) -> None:
    """Make sure jax will see (or already sees) at least ``n`` devices.

    Call before constructing a TP engine. No-op for ``n <= 1``. If jax is
    not imported yet, merges ``--xla_force_host_platform_device_count=n``
    into ``XLA_FLAGS`` (respecting a pre-existing, larger setting). If jax
    IS already imported with fewer devices, raises with the recipe — the
    flag cannot act retroactively.
    """
    n = int(n)
    if n <= 1:
        return
    if "jax" not in sys.modules:
        flags = os.environ.get("XLA_FLAGS", "")
        if _FLAG not in flags:
            os.environ["XLA_FLAGS"] = f"{flags} {_FLAG}={n}".strip()
        # an existing smaller count is the caller's explicit choice; the
        # device check below still runs after import and reports clearly
    import jax
    have = jax.device_count()
    if have < n:
        raise RuntimeError(
            f"need {n} devices for tp={n} but jax sees {have}. On a CPU "
            f"host, set XLA_FLAGS={_FLAG}={n} in the environment before "
            f"ANY jax import (e.g. `source launch/env.sh` with "
            f"SUPERINFER_HOST_DEVICES={n}, or export it before launching).")
