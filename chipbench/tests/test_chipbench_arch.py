"""The per-architecture modules (``arch``). Both configurations resolve to
the dense decoder, and what the harness reads through it is what the dense
functions give when called directly: weights bit for bit, reference logits,
work counts and the size of a ``"fill"`` pool. A ``model_type`` with no
module fails and names the modules there are. A new architecture enters
the benchmark as new files only, and the harness then runs through it."""
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

jax = pytest.importorskip("jax")

import arch                                       # noqa: E402
import flops                                      # noqa: E402
import reference                                  # noqa: E402
import trace_reduce                               # noqa: E402
import weights                                    # noqa: E402
from arch import dense                            # noqa: E402
from child import RESERVE_BYTES, fill_blocks      # noqa: E402
from common import BENCH_DIR, ROOT, load_json     # noqa: E402

CONFIGS = ("qwen2.5-32b", "yi-34b")
V5E_BYTES_LIMIT = 16_909_336_576     # a v5e's memory_stats()["bytes_limit"]


def config(name, rehearsal=False):
    cfg = load_json(BENCH_DIR / "configs" / f"{name}.json")
    return dict(cfg, **cfg["rehearsal"]) if rehearsal else cfg


def dense_weights(cfg, seed):
    """The weight maker as it was before ``arch``: the dense shapes and
    spreads, one split of the seed's key a leaf, in one jitted call."""
    dtype = (jax.numpy.bfloat16 if cfg["torch_dtype"] == "bfloat16"
             else jax.numpy.float32)
    lshapes, hshapes = weights.shapes(cfg)
    names = [(i, k, s) for i, lay in enumerate(lshapes)
             for k, s in sorted(lay.items())]
    names += [(-1, k, s) for k, s in sorted(hshapes.items())]

    @jax.jit
    def build(key):
        keys = jax.random.split(key, len(names))
        return [jax.random.normal(k, shape, dtype)
                * jax.numpy.asarray(weights.std(name, shape), dtype)
                for k, (_, name, shape) in zip(keys, names)]

    out = [dict() for _ in lshapes] + [dict()]
    for (i, name, _), x in zip(names, build(weights._key(seed))):
        out[i][name] = x
    return out[:-1], out[-1]


@pytest.mark.parametrize("name", CONFIGS)
def test_configurations_resolve_to_dense(name):
    mod = arch.resolve(config(name))
    assert dense.__all__
    for attr in dense.__all__:
        assert getattr(mod, attr) is getattr(dense, attr), attr
    assert mod is arch.resolve(config(name))          # loaded once


@pytest.mark.parametrize("name", CONFIGS)
def test_weights_through_the_module_are_bit_identical(name):
    cfg = config(name, rehearsal=True)
    got = weights.make(cfg, 2 ** 31 + 7)
    want = dense_weights(cfg, 2 ** 31 + 7)
    flat = jax.tree_util.tree_leaves_with_path
    assert ([p for p, _ in flat(got)] == [p for p, _ in flat(want)])
    for (p, a), (_, b) in zip(flat(got), flat(want)):
        assert a.dtype == b.dtype and np.array_equal(np.asarray(a),
                                                     np.asarray(b)), p


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_through_the_module(name):
    cfg = config(name, rehearsal=True)
    mod = arch.resolve(cfg)
    layers, head = weights.make(cfg, 23)
    ids = np.random.default_rng(1).integers(
        1, cfg["vocab_size"], 256).astype(np.int32)
    for control in (False, True):
        got = mod.logits(cfg, layers, head, ids, 39, 16, control)
        want = reference.logits(cfg, layers, head, ids, 39, 16,
                                control=control)
        assert np.array_equal(np.asarray(got), np.asarray(want))
    prompt, served = list(ids[:40]), list(ids[40:48])
    assert (reference.gaps(cfg, layers, head, prompt, served, 256, 16,
                           control=True, forward=mod.logits)
            == reference.gaps(cfg, layers, head, prompt, served, 256, 16,
                              control=True))


@pytest.mark.parametrize("name", CONFIGS)
def test_work_counts_and_pool_through_the_module(name):
    cfg = config(name)
    mod = arch.resolve(cfg)
    m = mod.dims(cfg)
    assert m == flops.dims(cfg)
    ctxs = [1, 17, 1536, 4096]
    for c in ctxs:
        assert mod.decode_token_flops(m, c) == flops.decode_token_flops(m, c)
    for p in (1, 256, 4000):
        assert mod.prefill_flops(m, p) == flops.prefill_flops(m, p)
    assert (mod.decode_attention_work(m, ctxs)
            == flops.decode_attention_work(m, ctxs))
    assert mod.row_bytes(m) == flops.row_bytes(m) == 393216
    # the "fill" pool as Child.pool_blocks sized it before the module
    weight_bytes = (m["layers"] * (flops.layer_matmul_params(m) + 2 * m["d"])
                    + 2 * m["v"] * m["d"] + m["d"]) * m["elt"]
    assert mod.weight_bytes(m) == weight_bytes
    want = (V5E_BYTES_LIMIT - weight_bytes - RESERVE_BYTES) // 393216
    assert fill_blocks(mod, cfg, V5E_BYTES_LIMIT) == want
    if name == "yi-34b":
        assert want == 15850              # the yi-34b.rag pool on a v5e


@pytest.mark.parametrize("model_type", ["mellum", "__init__", "../flops",
                                        ""])
def test_unknown_model_type_fails_and_names_the_known(model_type):
    with pytest.raises(KeyError) as e:
        arch.resolve({"model_type": model_type})
    for known in ("dense", "llama", "qwen2"):
        assert repr(known) in str(e.value)


def test_an_architecture_kernel_table_is_matched_first():
    dev = "/device:TPU:0"
    ev = dict(ops=[["_decode_impl [kernel]", 0, 2000, dev],
                   ["_decode_impl_experts [kernel]", 2000, 3000, dev]],
              modules=[], spans=[])
    dense_only = trace_reduce.reduce(ev)["kernels"]
    assert dense_only == {"paged_attention": pytest.approx(5e-6)}
    own = trace_reduce.reduce(ev, kernels={"experts": "_experts"})["kernels"]
    assert own == {"paged_attention": pytest.approx(2e-6),
                   "experts": pytest.approx(3e-6)}


TOY = '''"""The dense decoder, recording each call of its functions."""
from pathlib import Path

from arch import dense
from arch.dense import *  # noqa: F401,F403

LOG = Path(__file__).with_name("toyqwen.calls")


def _recorded(name):
    f = getattr(dense, name)

    def g(*a, **k):
        with open(LOG, "a") as out:
            out.write(name + "\\n")
        return f(*a, **k)
    return g


for _name in ("dims", "shapes", "std", "logits", "server_kwargs"):
    globals()[_name] = _recorded(_name)
'''


def test_an_architecture_is_added_as_files_only(tmp_path):
    """A copy of the benchmark with one new file, ``arch/toyqwen.py``, and
    the qwen configuration's ``model_type`` pointed at it: the rehearsal
    runs through the new module and is correct."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    shutil.copytree(BENCH_DIR, tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    (tmp_path / "src").symlink_to(ROOT / "src")
    bench = tmp_path / "chipbench"
    (bench / "arch" / "toyqwen.py").write_text(TOY)
    conf = bench / "configs" / "qwen2.5-32b.json"
    conf.write_text(json.dumps(dict(json.loads(conf.read_text()),
                                    model_type="toyqwen")))
    p = subprocess.run(
        [sys.executable, str(bench / "run.py"), "--workload",
         "qwen2.5-32b.longdoc", "--seed", str(2 ** 31 + 19), "--seconds",
         "4", "--trace", "1", "--rehearse-cpu"], cwd=tmp_path,
        env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
        text=True, timeout=240)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True, p.stderr[-3000:]
    calls = set((bench / "arch" / "toyqwen.calls").read_text().split())
    assert calls == {"dims", "shapes", "std", "logits", "server_kwargs"}
    assert not (BENCH_DIR / "arch" / "toyqwen.py").exists()
