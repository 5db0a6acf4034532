import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

The two lines above MUST stay first (jax locks the device count on first
init). Usage:

    PYTHONPATH=src python -m repro.launch.dryrun --arch yi-34b --shape train_4k --mesh single
    PYTHONPATH=src python -m repro.launch.dryrun --all --mesh both      # driver, subprocess per cell
    PYTHONPATH=src python -m repro.launch.dryrun --report               # print table from cached JSON

Each cell writes results/dryrun/<arch>__<shape>__<mesh>.json (cached; use
--force to recompute). Failures are recorded in the JSON with the traceback.
"""
import argparse
import json
import subprocess
import sys
import time
import traceback
from pathlib import Path

RESULTS_DIR = Path(__file__).resolve().parents[3] / "results" / "dryrun"


def _compile_once(cfg, shape, mesh, rules, *, microbatches, unroll,
                  save_hlo_path=None, opts=None):
    """Lower+compile one step; return (rec dict, collective-bytes dict)."""
    import jax
    from jax.sharding import NamedSharding
    from repro.distributed.sharding import pspec_for, sharding_ctx
    from repro.launch import roofline
    from repro.models.api import make_step_bundle

    rec = {}
    t0 = time.time()
    with sharding_ctx(mesh, rules):
        bundle = make_step_bundle(cfg, shape, microbatches=microbatches,
                                  unroll=unroll, **(opts or {}))
        rec.update(bundle.static_meta)
        rec["kind"] = bundle.kind

        def to_sharding(leaf):
            axes, shp = leaf
            return NamedSharding(mesh, pspec_for(axes or (), mesh, rules, shp))

        in_shardings = jax.tree.map(to_sharding, bundle.args_axes,
                                    is_leaf=_axes_leaf)
        jitted = jax.jit(bundle.fn, in_shardings=in_shardings,
                         donate_argnums=bundle.donate)
        lowered = jitted.lower(*bundle.args_structs)
        rec["lower_s"] = round(time.time() - t0, 2)
        t1 = time.time()
        compiled = lowered.compile()
        rec["compile_s"] = round(time.time() - t1, 2)

    # XLA backends differ in what the compiled executable exposes: older
    # releases raise NotImplementedError/RuntimeError, interface drift shows
    # up as Attribute/Type/KeyError. Anything else (a real shape/lowering
    # bug) must propagate, not be recorded as a soft analysis failure.
    try:
        cost = compiled.cost_analysis()
        rec["cost_analysis"] = {k: float(v) for k, v in cost.items()
                                if isinstance(v, (int, float))
                                and k in ("flops", "bytes accessed",
                                          "optimal_seconds", "transcendentals")}
    except (NotImplementedError, RuntimeError, AttributeError, TypeError,
            KeyError) as e:
        print(f"[dryrun] cost_analysis unavailable "
              f"({type(e).__name__}): {e}", file=sys.stderr)
        rec["cost_analysis_error"] = repr(e)
    try:
        mem = compiled.memory_analysis()
        if mem is not None:
            rec["memory_analysis"] = {
                a: float(getattr(mem, a)) for a in dir(mem)
                if a.endswith("size_in_bytes") and not a.startswith("_")}
    except (NotImplementedError, RuntimeError, AttributeError, TypeError) as e:
        print(f"[dryrun] memory_analysis unavailable "
              f"({type(e).__name__}): {e}", file=sys.stderr)
        rec["memory_analysis_error"] = repr(e)

    hlo = compiled.as_text()
    rec["hlo_len"] = len(hlo)
    coll = roofline.collective_bytes(hlo)
    if save_hlo_path:
        save_hlo_path.write_text(hlo)
    rec["arg_bytes_per_device"] = _arg_bytes_per_device(
        bundle, mesh, rules, pspec_for)
    rec["local_bytes"] = {
        name: _group_bytes_per_device(grp, mesh, rules, pspec_for)
        for name, grp in bundle.byte_groups.items()}
    return rec, coll


def _metrics_vector(rec, coll):
    """Flatten one compile's costs into a metric dict for extrapolation."""
    ca = rec.get("cost_analysis", {})
    out = {"flops": ca.get("flops", 0.0), "bytes": ca.get("bytes accessed", 0.0)}
    for k, v in coll.items():
        out["coll:" + k] = float(v)
    return out


def _depth_variant(cfg, periods: int, period_len: int):
    import dataclasses
    L = periods * period_len
    kw = {"num_layers": L}
    if cfg.num_encoder_layers:
        kw["num_encoder_layers"] = L  # scale encoder jointly (affine in pairs)
    return dataclasses.replace(cfg, **kw)


def extrapolate_costs(cfg, shape, mesh, rules, mb_target: int,
                      opts=None) -> dict:
    """Two-point (or four-point, for train) affine extrapolation of HLO costs
    from shallow UNROLLED variants — exact per-layer/per-microbatch marginals
    that lax.scan hides from cost_analysis (see EXPERIMENTS.md §Method)."""
    from repro.models.lm import build_program
    p = len(build_program(cfg, decoder=True)[0].pattern)
    X = cfg.num_layers / p
    is_train = shape.kind == "train"

    def meas(periods, mb):
        var = _depth_variant(cfg, periods, p)
        rec, coll = _compile_once(var, shape, mesh, rules,
                                  microbatches=mb, unroll=True, opts=opts)
        return _metrics_vector(rec, coll), rec["compile_s"]

    out = {"period_len": p, "periods_full": X, "mb_target": mb_target}
    if is_train:
        (FA, tA), (FB, tB) = meas(1, 1), meas(2, 1)
        (FC, tC), (FD, tD) = meas(1, 2), meas(2, 2)
        out["aux_compile_s"] = tA + tB + tC + tD
        keys = set(FA) | set(FB) | set(FC) | set(FD)
        res = {}
        for k in keys:
            fa, fb = FA.get(k, 0.0), FB.get(k, 0.0)
            fc, fd = FC.get(k, 0.0), FD.get(k, 0.0)
            c2 = (fd - fc) - (fb - fa)
            c3 = (fb - fa) - c2
            c1 = (fc - fa) - c2
            c0 = fa - c1 - c2 - c3
            res[k] = c0 + c3 * X + mb_target * (c1 + c2 * X)
        out["metrics"] = res
    else:
        (FA, tA), (FB, tB) = meas(1, 1), meas(2, 1)
        out["aux_compile_s"] = tA + tB
        keys = set(FA) | set(FB)
        out["metrics"] = {k: FA.get(k, 0.0)
                          + (X - 1) * (FB.get(k, 0.0) - FA.get(k, 0.0))
                          for k in keys}
    return out


def run_cell(arch: str, shape_name: str, mesh_kind: str, *,
             microbatches=None, save_hlo: bool = False,
             extrapolate: bool = True, opt_flags=None) -> dict:
    opts = dict(opt_flags or {})
    import jax
    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.distributed.sharding import rules_for_shape
    from repro.launch import roofline
    from repro.launch.mesh import make_production_mesh

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    ok, why = shape_applicable(cfg, shape)
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_kind,
           "opt_flags": opt_flags or {}}
    if not ok:
        rec["status"] = "skipped"
        rec["reason"] = why
        return rec

    mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
    rec["devices"] = mesh.devices.size
    rules = rules_for_shape(shape.kind, shape.global_batch)

    # 1) FULL-config compile: proves lowering/sharding + memory analysis.
    hlo_path = (RESULTS_DIR / f"{arch}__{shape_name}__{mesh_kind}.hlo.txt"
                if save_hlo else None)
    full_rec, full_coll = _compile_once(cfg, shape, mesh, rules,
                                        microbatches=microbatches,
                                        unroll=False, save_hlo_path=hlo_path,
                                        opts=opts)
    rec.update(full_rec)
    rec["collective_detail_full_compile"] = full_coll

    # 2) roofline metrics from unrolled shallow-variant extrapolation
    #    (single-pod only; multi-pod is the sharding proof).
    if extrapolate and mesh_kind == "single":
        ex = extrapolate_costs(cfg, shape, mesh, rules,
                               rec.get("microbatches", 1), opts=opts)
        rec["extrapolation"] = {k: v for k, v in ex.items() if k != "metrics"}
        m = ex["metrics"]
        coll = {k.split(":", 1)[1]: v for k, v in m.items()
                if k.startswith("coll:")}
        cost = {"flops": m["flops"], "bytes accessed": m["bytes"]}
        lb = rec.get("local_bytes", {})
        fsdp_shards = 1
        fa = rules.fsdp
        for a in ((fa,) if isinstance(fa, str) else (fa or ())):
            if a in mesh.shape:
                fsdp_shards *= mesh.shape[a]
        data_shards = mesh.devices.size // mesh.shape["model"]
        mem_model = roofline.analytic_memory_bytes(
            cfg, shape,
            weights_local=lb.get("weights", 0.0),
            opt_local=lb.get("opt", 0.0),
            cache_local=lb.get("cache", 0.0),
            data_shards=data_shards,
            model_shards=mesh.shape["model"],
            fsdp_shards=fsdp_shards,
            microbatches=rec.get("microbatches", 1))
        rec["roofline"] = roofline.summarize(cfg, shape, mesh.devices.size,
                                             cost, coll, mem_model)
    rec["status"] = "ok"
    return rec


def _axes_leaf(x):
    return (isinstance(x, tuple) and len(x) == 2
            and isinstance(x[1], tuple)
            and all(isinstance(i, int) for i in x[1])
            and (x[0] is None or isinstance(x[0], tuple)))


def _tree_bytes_per_device(structs, axes_tree, mesh, rules, pspec_for) -> float:
    total = 0.0
    sl = jax.tree.leaves(structs)  # noqa: F821
    al = jax.tree.leaves(axes_tree, is_leaf=_axes_leaf)  # noqa: F821
    for st, ax in zip(sl, al):
        spec = pspec_for(ax[0] or (), mesh, rules, ax[1])
        shards = 1
        for part in spec:
            if part is None:
                continue
            names = (part,) if isinstance(part, str) else part
            for nm in names:
                shards *= mesh.shape[nm]
        total += st.size * st.dtype.itemsize / shards
    return total


def _arg_bytes_per_device(bundle, mesh, rules, pspec_for) -> float:
    return _tree_bytes_per_device(bundle.args_structs, bundle.args_axes,
                                  mesh, rules, pspec_for)


def _group_bytes_per_device(grp, mesh, rules, pspec_for) -> float:
    structs, axes_tree = grp
    return _tree_bytes_per_device(structs, axes_tree, mesh, rules, pspec_for)


def cell_path(arch, shape, mesh_kind, tag="") -> Path:
    sfx = f"__{tag}" if tag else ""
    return RESULTS_DIR / f"{arch}__{shape}__{mesh_kind}{sfx}.json"


def all_cells(meshes=("single", "multi")):
    from repro.configs import ARCH_IDS, SHAPES
    for arch in ARCH_IDS:
        for shape in SHAPES:
            for m in meshes:
                yield arch, shape, m


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--report", action="store_true")
    ap.add_argument("--tag", default="", help="results filename suffix")
    ap.add_argument("--microbatches", type=int, default=None)
    ap.add_argument("--remat-group", type=int, default=1)
    ap.add_argument("--moments-dtype", default="float32")
    ap.add_argument("--accum-dtype", default="float32")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--timeout", type=int, default=3000)
    args = ap.parse_args()

    RESULTS_DIR.mkdir(parents=True, exist_ok=True)

    if args.report:
        report(args.tag)
        return

    if args.all:
        meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
        todo = [(a, s, m) for a, s, m in all_cells(meshes)
                if args.force or not cell_path(a, s, m, args.tag).exists()]
        print(f"{len(todo)} cells to run")
        for i, (a, s, m) in enumerate(todo):
            t0 = time.time()
            cmd = [sys.executable, "-m", "repro.launch.dryrun", "--arch", a,
                   "--shape", s, "--mesh", m]
            if args.tag:
                cmd += ["--tag", args.tag]
            if args.microbatches:
                cmd += ["--microbatches", str(args.microbatches)]
            r = subprocess.run(cmd, capture_output=True, text=True,
                               timeout=args.timeout,
                               env={**os.environ, "PYTHONPATH": "src"})
            status = "?"
            p = cell_path(a, s, m, args.tag)
            if p.exists():
                status = json.loads(p.read_text()).get("status", "?")
            print(f"[{i+1}/{len(todo)}] {a} {s} {m}: {status} "
                  f"({time.time()-t0:.0f}s)", flush=True)
            if r.returncode != 0 and not p.exists():
                p.write_text(json.dumps({
                    "arch": a, "shape": s, "mesh": m, "status": "crashed",
                    "stderr": r.stderr[-4000:]}, indent=1))
        return

    assert args.arch and args.shape
    meshes = ("single", "multi") if args.mesh == "both" else (args.mesh,)
    for m in meshes:
        try:
            rec = run_cell(args.arch, args.shape, m,
                           microbatches=args.microbatches,
                           save_hlo=args.save_hlo,
                           opt_flags={"remat_group": args.remat_group,
                                      "moments_dtype": args.moments_dtype,
                                      "accum_dtype": args.accum_dtype})
        except (RuntimeError, ValueError, TypeError, KeyError, ImportError,
                NotImplementedError, OSError, MemoryError) as e:
            # expected compile-time failure classes (XLA RuntimeError, shape
            # ValueError, OOM, missing deps): record the full traceback in
            # the cell JSON and say so loudly — everything else (including a
            # scheduler OutOfBlocks or an AssertionError) crashes the cell
            # rather than being filed as a "skipped config"
            print(f"[dryrun] {args.arch} {args.shape} {m} failed with "
                  f"{type(e).__name__}: {e}", file=sys.stderr)
            rec = {"arch": args.arch, "shape": args.shape, "mesh": m,
                   "status": "error", "error_type": type(e).__name__,
                   "traceback": traceback.format_exc()[-6000:]}
        out = cell_path(args.arch, args.shape, m, args.tag)
        out.write_text(json.dumps(rec, indent=1))
        short = {k: rec.get(k) for k in ("status", "compile_s", "reason")}
        rl = rec.get("roofline", {})
        if rl:
            short.update({k: rl[k] for k in ("bottleneck", "roofline_fraction")})
        print(f"{args.arch} {args.shape} {m}: {short}")


def report(tag: str = ""):
    rows = []
    pat = f"*__{tag}.json" if tag else "*.json"
    for p in sorted(RESULTS_DIR.glob(pat)):
        if not tag and "__opt" in p.name:
            continue
        r = json.loads(p.read_text())
        rl = r.get("roofline", {})
        frac_hw = rl.get("roofline_fraction_hw")
        if frac_hw is None and rl:   # recompute for records saved before
            lb = rl.get("step_s_lower_bound", 0)
            frac_hw = (max(rl.get("ideal_step_s", 0), rl.get("memory_s", 0))
                       / lb) if lb else 0.0
        rows.append((r["arch"], r["shape"], r["mesh"], r.get("status"),
                     rl.get("bottleneck", "-"),
                     f"{frac_hw or 0:.3f}",
                     f"{rl.get('roofline_fraction', 0):.3f}",
                     f"{rl.get('compute_s', 0):.4f}",
                     f"{rl.get('memory_s', 0):.4f}",
                     f"{rl.get('collective_s', 0):.4f}",
                     f"{rl.get('useful_flops_ratio', 0):.2f}",
                     r.get("compile_s", "-")))
    hdr = ("arch", "shape", "mesh", "status", "bneck", "roofline_hw",
           "mfu_frac", "compute_s", "memory_s", "coll_s", "useful",
           "compile_s")
    print(",".join(hdr))
    for row in rows:
        print(",".join(str(x) for x in row))


if __name__ == "__main__":
    import jax  # noqa: F401  (after XLA_FLAGS)
    main()
else:
    import jax  # noqa: F401
