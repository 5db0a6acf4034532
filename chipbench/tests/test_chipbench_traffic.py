"""The generator: every seed gets the same requests at the same times (only
the token ids differ), and the warm-up meets every padded shape a mix can
reach."""
import pytest

import traffic
from common import load_json, BENCH_DIR

MIXES = ["longdoc", "rag"]


def mix(name):
    return load_json(BENCH_DIR / "traffic" / f"{name}.json")


def plan_shapes(stages):
    """Prefill buckets the warm-up's prompts meet."""
    shapes = set()
    for groups in stages:
        for g in groups:
            if len(g) == 2 and g[0]["max_tokens"] == 1:
                shapes |= traffic._pair_shapes(len(g[0]["prompt_ids"]),
                                               len(g[1]["prompt_ids"]))
            else:
                for r in g:
                    shapes |= traffic._single_shapes(len(r["prompt_ids"]))
    return shapes


def shape(reqs):
    return [(r["due"], len(r["prompt_ids"]), r["max_tokens"], r["slo_class"])
            for r in reqs]


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work_and_its_order(name):
    tr = dict(mix(name), preroll_s=5)
    a = traffic.schedule(tr, 1, 10, 1000)
    b = traffic.schedule(tr, 2 ** 31 + 77, 10, 1000)
    for phase in ("preroll", "window"):
        assert shape(a[phase]) == shape(b[phase])
        assert a[phase][0]["prompt_ids"] != b[phase][0]["prompt_ids"]
    assert a == traffic.schedule(tr, 1, 10, 1000)
    # the window holds the mix's lengths, not one repeated size
    assert len({len(r["prompt_ids"]) for r in a["window"]}) > 5


@pytest.mark.parametrize("name", MIXES)
def test_gaps_are_the_mix_rate(name):
    tr = mix(name)
    plan = traffic.schedule(tr, 9, 40, 1000)
    dues = [r["due"] for r in plan["window"]]
    mean_gap = (dues[-1] - plan["window_start"]) / len(dues)
    assert abs(mean_gap * tr["rate_rps"] - 1) < 0.02


@pytest.mark.parametrize("name", MIXES)
def test_window_is_one_stream_after_the_preroll(name):
    tr = mix(name)
    plan = traffic.schedule(tr, 5, 40, 1000)
    assert len(plan["window"]) == round(tr["rate_rps"] * 40)
    assert plan["window_start"] == tr["preroll_s"]
    assert all(plan["window_start"] <= r["due"] < tr["preroll_s"] + 40
               for r in plan["window"])


@pytest.mark.parametrize("name", MIXES)
def test_lengths_follow_the_mix(name):
    tr = mix(name)
    p = traffic.lengths(tr["prompt"], 1001)
    lo, hi = traffic.length_bounds(tr["prompt"])
    assert min(p) >= lo and max(p) <= hi
    assert all(x % tr["prompt"]["multiple"] == 0 for x in p)
    med = p[500]
    assert abs(med - tr["prompt"]["median"]) <= tr["prompt"]["multiple"]
    assert traffic.classes(tr["slo_mix"], 10) == (
        ["batch"] * 2 + ["interactive"] * 3 + ["standard"] * 5)


@pytest.mark.parametrize("name,cell", [("longdoc",
                                        "qwen2.5-32b.longdoc"),
                                       ("rag", "yi-34b.rag")])
def test_warmup_meets_every_prefill_shape(name, cell):
    tr = mix(name)
    st = load_json(BENCH_DIR / "workloads" / f"{cell}.json")
    stages = traffic.warmup_plan(tr, st, 3, 1000)
    want = traffic.prefill_shapes(tr)
    assert want <= plan_shapes(stages)
    blocks = traffic.decode_blocks(tr)
    ladders = [g for g in stages if len(g) > 1]
    assert len(ladders) == len(blocks)
    for groups, b in zip(ladders, blocks):
        anchor = groups[0][0]
        ctx = len(anchor["prompt_ids"]) + 1
        assert b // 2 < -(-ctx // 16) <= b
        assert -(-(ctx + anchor["max_tokens"]) // 16) <= b
        assert sum(len(g) for g in groups) == traffic.WARM_MAX_BATCH
    # a cell whose traffic runs more requests at once sets its own bound
    more = traffic.warmup_plan(tr, dict(st, warm_max_batch=64), 3, 1000)
    assert max(sum(len(g) for g in s) for s in more) == 64


def test_pair_shapes_split_the_budget():
    # 896 then 256: the second prompt starts on the 128 tokens the first
    # leaves, then runs its other 128 from position 128
    assert traffic._pair_shapes(896, 256) == {(512, 32), (512, 64),
                                              (128, 8), (128, 16)}
