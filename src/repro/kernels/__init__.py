"""Pallas TPU kernels (``paged_attention``, ``kv_copy``, ``flash_attention``)
with pure-jnp oracles in ``ref``.

Every kernel takes ``interpret=None`` and resolves it here, so the choice
between Mosaic compilation and the Pallas interpreter is made in one place.
"""
from __future__ import annotations

from typing import Optional


def resolve_interpret(interpret: Optional[bool] = None) -> bool:
    """Interpret mode for a Pallas launch.

    ``None`` decides from ``jax.default_backend()``: compiled on ``tpu``,
    interpreted everywhere else (the CPU tests). ``True`` on a TPU backend
    is refused: the interpreter there would run the serving path orders of
    magnitude slower with no error. ``False`` always compiles, which is how
    a CPU process compiles a kernel for a described (not attached) TPU.
    """
    import jax
    on_tpu = jax.default_backend() == "tpu"
    if interpret is None:
        return not on_tpu
    if interpret and on_tpu:
        raise ValueError("Pallas interpret mode requested on a TPU backend; "
                         "pass interpret=None to compile the kernel")
    return bool(interpret)
