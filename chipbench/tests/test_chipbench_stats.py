"""The client-side metric arithmetic."""
import pytest

import stats
from client import Stream


def stream(due, events, cls="standard", finish=None):
    s = Stream(prompt_ids=[1] * 8, max_tokens=4, slo_class=cls, due=due)
    s.events = list(events)
    s.finish_reason = finish
    return s


LIMITS = {"interactive": 1.0, "standard": 5.0, "batch": 30.0}


def test_ttft_is_timed_from_the_due_time_and_failures_sit_on_top():
    ok = stream(10.0, [(10.5, 1), (10.6, 1)])
    late = stream(11.0, [(17.0, 1)])
    never = stream(12.0, [])
    assert stats.ttfts([ok, late, never], drain_end=100.0) == \
        pytest.approx([0.5, 6.0, 88.0])


def test_attainment_counts_failures_as_misses_per_class():
    a = stream(0.0, [(0.9, 1)], "interactive")        # met 1 s
    b = stream(0.0, [(1.1, 1)], "interactive")        # missed 1 s
    c = stream(0.0, [(4.0, 1)], "standard")           # met 5 s
    d = stream(0.0, [], "batch")                       # no token: a miss
    e2e = stats.end_to_end([a, b, c, d], [a, b, c, d], 0.0, 10.0, 20.0,
                           LIMITS)
    assert e2e["ttft_attainment"] == 0.5


def test_gaps_and_tokens_count_only_inside_the_window():
    s = stream(0.0, [(0.5, 1), (1.5, 1), (2.5, 2), (3.5, 1), (4.5, 0)])
    assert stats.tbt_gaps([s], 1.0, 3.0) == pytest.approx([1.0, 1.0])
    assert stats.window_tokens([s], 1.0, 3.0) == 3
    assert stats.window_tokens([s], 0.0, 10.0) == 5


def test_percentiles_interpolate_like_numpy():
    vals = list(range(1, 101))
    assert stats.percentile(vals, 90) == pytest.approx(90.1)
    assert stats.percentile(vals, 99) == pytest.approx(99.01)
    with pytest.raises(ValueError):
        stats.percentile([], 50)


def test_end_to_end_rate_and_tails():
    streams = [stream(i * 0.1, [(i * 0.1 + 0.2 + 0.05 * k, 1)
                                for k in range(5)]) for i in range(50)]
    e2e = stats.end_to_end(streams, streams, 0.0, 10.0, 10.0, LIMITS)
    assert e2e["ttft_p90_s"] == pytest.approx(0.2)
    assert e2e["tbt_p99_s"] == pytest.approx(0.05)
    assert e2e["output_tokens_per_s"] == pytest.approx(250 / 10.0)
    assert e2e["n_ttft"] == 50 and e2e["n_gaps"] == 200
