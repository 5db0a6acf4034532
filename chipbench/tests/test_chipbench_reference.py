"""The reference and its control at the reduced size a test can hold: the
reference agrees with itself to the last bit, the int8 control does not
(it would fail the rehearsal's limit), and the weight maker is a function
of the seed alone."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")

import reference                                  # noqa: E402
import weights                                    # noqa: E402
from common import BENCH_DIR, load_json           # noqa: E402


@pytest.fixture(scope="module")
def small():
    cfg = load_json(BENCH_DIR / "configs" / "qwen2.5-32b.json")
    cfg = dict(cfg, **cfg["rehearsal"])
    st = load_json(BENCH_DIR / "workloads" /
                   "qwen2.5-32b.longdoc.json")["rehearsal"]
    return cfg, st


def test_weights_follow_the_seed(small):
    cfg, _ = small
    a = weights.make(cfg, 2 ** 31 + 3)
    b = weights.make(cfg, 2 ** 31 + 3)
    c = weights.make(cfg, 3)
    leaf = lambda w: np.asarray(w[0][0]["wq"])          # noqa: E731
    assert np.array_equal(leaf(a), leaf(b))
    assert not np.array_equal(leaf(a), leaf(c))
    assert a[1]["lm_head"].shape == (cfg["hidden_size"], cfg["vocab_size"])


def test_reference_own_tokens_read_zero_and_control_fails(small):
    cfg, st = small
    layers, head = weights.make(cfg, 17)
    rng = np.random.default_rng(0)
    pad, n_out = st["reference"]["pad_len"], st["reference"]["n_out"]
    worst_ctrl = 0.0
    for _ in range(3):
        prompt = list(rng.integers(1, cfg["vocab_size"], 40))
        seq = list(prompt)
        served = []
        for _ in range(n_out):                       # greedy under the ref
            ids = np.zeros(pad, np.int32)
            ids[:len(seq)] = seq
            lg = reference.logits(cfg, layers, head, ids, len(seq) - 1, 1)
            served.append(int(np.argmax(np.asarray(lg)[0])))
            seq.append(served[-1])
        g = reference.gaps(cfg, layers, head, prompt, served, pad, n_out,
                           control=True)
        assert max(g["served"]) == 0.0
        worst_ctrl = max(worst_ctrl, max(g["control"]))
    assert worst_ctrl > st["correct"]["max_logit_gap"]
