"""Donation/aliasing audit at the paged-runner jit boundaries.

The pipelined engine keeps several launches in flight per iteration; if any
jit boundary silently dropped pool donation, every launch would deep-copy
the whole KV pool (tens of GiB at production scale) and the "async
dispatch" would be async copies of the cache, not async compute. This tool
lowers each jitted entry point of ``PagedModelRunner``/``PagedKVStore``
with a tiny reduced config and asserts the donation marker on the pool
parameter of the StableHLO ``main`` is present — the same check a human
would do with ``.lower().as_text()``. Unsharded lowerings mark donation as
``tf.aliasing_output``; sharded (tensor-parallel shard_map) lowerings mark
it as ``jax.buffer_donor`` — both count. With >= 2 XLA devices (the tool
forces the host device count when it still can) every boundary is audited
a second time at tp=2 over the sharded pool.

The CPU backend *ignores* donation at execution time, so compiled-HLO copy
counts are reported for information only, never asserted: the lowering
marker is the contract, the backend decides what it can honor.

    PYTHONPATH=src python -m repro.launch.audit_donation [--verbose]

Exits non-zero if any expected donation marker is missing.
"""
from __future__ import annotations

import argparse
import dataclasses
import re
import sys

_ARG_RE = re.compile(r"%arg\d+: tensor<([0-9x]+)x[a-z0-9]+>")
# an argument's attribute dict may nest braces (Shardy's #sdy.sharding), so
# each argument's text runs to the next "%argN:" rather than the first "}"
_DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


def _pool_alias(lowered_text: str, pool_shape) -> tuple:
    """(pool_args_found, pool_args_aliased) over the ``main`` signature."""
    want = "x".join(str(d) for d in pool_shape)
    found = aliased = 0
    main = lowered_text.split("func.func public @main", 1)[-1]
    sig = main.split("->", 1)[0]
    for arg in re.split(r"(?=%arg\d+:)", sig):
        m = _ARG_RE.match(arg)
        if m and m.group(1) == want:
            found += 1
            if any(k in arg for k in _DONATION_MARKERS):
                aliased += 1
    return found, aliased


def _count_copies(jitted, *args) -> int:
    """copy ops in the compiled HLO — informational on CPU (no donation)."""
    try:
        txt = jitted.lower(*args).compile().as_text()
    except (RuntimeError, ValueError, NotImplementedError):
        return -1
    return sum(1 for l in txt.splitlines()
               if re.match(r"\s*%?[\w.\-]+ = [^=]*\bcopy\(", l))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--verbose", action="store_true",
                    help="dump the main-func signature of each lowering")
    args = ap.parse_args(argv)

    # the sharded (tp=2) boundaries need 2 XLA devices; force the host
    # device count while the flag can still act (before any jax import)
    try:
        from repro.launch.hostenv import ensure_host_devices
        ensure_host_devices(2)
    except RuntimeError:
        pass                         # jax already up with 1 device

    import jax
    import jax.numpy as jnp
    from repro.configs import GH200, ServingConfig, get_config
    from repro.serving.paged_runner import PagedModelRunner

    cfg = dataclasses.replace(get_config("llama3-8b").reduced(),
                              dtype="float32")
    sv = ServingConfig(num_hbm_blocks=8, num_dram_blocks=32,
                       scheduler="rotasched", block_size=4, max_model_len=64,
                       prefill_chunk=8, paged_runner=True, pipeline=True)

    class _KV:                       # bind() only needs the attach hook
        table = None

        def attach_data_backend(self, store):
            pass

    def runner_cases(tp, kv_dtype="bf16"):
        """The four pool-carrying jit boundaries of one runner. Each case
        lists every donated-buffer shape to audit — the quantized tier adds
        the scale array (its own donated parameter) to every boundary."""
        runner = PagedModelRunner(
            cfg, dataclasses.replace(sv, tp=tp, kv_dtype=kv_dtype),
            GH200, seed=0)
        runner.bind(_KV())
        store = runner.store
        pool = store.pool
        two = jnp.zeros(2, jnp.int32)
        rows = jnp.zeros((2,) + store.row_shape, pool.dtype)
        bt = jnp.zeros((2, 2), jnp.int32)
        ids = jnp.zeros(8, jnp.int32)
        zero = jnp.asarray(0, jnp.int32)
        tag = "".join((f" [tp={tp}]" if tp > 1 else "",
                       " [int8]" if store.quantized else ""))
        if store.quantized:
            sc = store.scales
            srows = jnp.zeros((2,) + store.scale_row_shape, jnp.float32)
            shapes = [pool.shape, sc.shape]
            return runner, [
                (f"PagedKVStore._jit_copy_q{tag}", store._jit_copy_q,
                 (pool, sc, two, two), True, shapes),
                (f"PagedKVStore._jit_upload_q{tag}", store._jit_upload_q,
                 (pool, sc, rows, srows, zero), True, shapes),
                (f"PagedModelRunner._jit_decode{tag}", runner._jit_decode,
                 (runner._layers, runner._head, pool, sc, two, bt, two),
                 True, shapes),
                (f"PagedModelRunner._jit_prefill{tag}", runner._jit_prefill,
                 (runner._layers, runner._head, pool, sc, ids, zero,
                  jnp.asarray(8, jnp.int32), two), True, shapes),
            ]
        return runner, [
            # (name, jitted fn, args, expect_donated, shapes)
            (f"PagedKVStore._jit_copy{tag}", store._jit_copy,
             (pool, two, two), True, [pool.shape]),
            (f"PagedKVStore._jit_upload{tag}", store._jit_upload,
             (pool, rows, zero), True, [pool.shape]),
            (f"PagedModelRunner._jit_decode{tag}", runner._jit_decode,
             (runner._layers, runner._head, pool, two, bt, two), True,
             [pool.shape]),
            (f"PagedModelRunner._jit_prefill{tag}", runner._jit_prefill,
             (runner._layers, runner._head, pool, ids, zero,
              jnp.asarray(8, jnp.int32), two), True, [pool.shape]),
        ]

    runner, cases = runner_cases(1)
    cases += runner_cases(1, kv_dtype="int8")[1]
    pool = runner.store.pool
    ps = pool.shape
    two = jnp.zeros(2, jnp.int32)
    if jax.device_count() >= 2:
        # the sharded boundaries: same global pool shape in the signature,
        # donation recorded as jax.buffer_donor
        cases += runner_cases(2)[1]
        cases += runner_cases(2, kv_dtype="int8")[1]
    else:
        print("# note: 1 XLA device — tp=2 sharded boundaries not audited "
              "(set XLA_FLAGS=--xla_force_host_platform_device_count=2)")
    # the bare kernel jitted WITHOUT donate_argnums: its internal
    # input_output_aliases cannot reach the boundary alone — a regression
    # guard that the audit detects missing donation (negative control)
    from repro.kernels.kv_copy import kv_copy_tpu
    flat = pool.reshape(ps[0], -1)
    bare = jax.jit(kv_copy_tpu)
    cases.append(("kv_copy_tpu (no donate — negative control)", bare,
                  (flat, two, two), False, [flat.shape]))

    failures = []
    print(f"{'jit boundary':48} {'buf arg':>8} {'donated':>8} "
          f"{'copies':>7}  verdict")
    for name, fn, fargs, expect, shapes in cases:
        txt = fn.lower(*fargs).as_text()
        ncopy = _count_copies(fn, *fargs)
        ok = True
        found_t = aliased_t = 0
        for shape in shapes:
            found, aliased = _pool_alias(txt, shape)
            found_t += found
            aliased_t += aliased
            ok = ok and (aliased > 0) == expect and found > 0
        verdict = "ok" if ok else "FAIL"
        if not ok:
            failures.append(name)
        print(f"{name:48} {found_t:>8} {aliased_t:>8} "
              f"{ncopy if ncopy >= 0 else 'n/a':>7}  {verdict}")
        if args.verbose:
            sig = txt.split("func.func public @main", 1)[-1]
            print("    " + sig.split("{", 1)[0].strip()[:400])

    if failures:
        print(f"# AUDIT FAILED: missing/unexpected donation on: "
              f"{', '.join(failures)}", file=sys.stderr)
        return 1
    print("# audit ok: every pool-carrying jit donates its pool "
          "(CPU backend may still copy — counts above are informational)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
