"""One general generator for every traffic mix, driven by the mix's data
file (``traffic/<name>.json``). Imports nothing of JAX.

Steadiness comes first: every seed gets the same requests at the same
times, and ``--seed`` only draws the prompt token ids (and the weights).
Sizes are stratified quantiles of the mix's clipped lognormals (prompt and
output lengths, as in the repository's ``serving/workload.py`` profiles),
paired with each other and with an SLO class, and put in order with the
gaps, by the mix's fixed ``pairing_seed``; gaps are stratified quantiles of
the exponential at the mix's rate (Poisson arrivals). In an open loop near
the knee the order of arrivals sets the queue, so an order drawn from the
seed would change the work from run to run.

The warm-up plan (``warmup_plan``) drives every padded shape the mix can
reach through the server before anything is timed: the paged runner pads
a decode batch to (pow2 batch, pow2 blocks) and a prefill chunk to
(pow2 tokens, pow2 blocks), so each of those buckets that the traffic can
meet compiles (or loads from the persistent cache) during set-up.
"""
from __future__ import annotations

import math
import random
import statistics
from typing import Any, Dict, List

BLOCK = 16              # tokens per KV block (the server's default)
CHUNK = 512             # prefill token budget per iteration (server default)
# The decode warm-up fills batches up to this many requests: the largest
# pow2 batch that the windows and pre-rolls of both cells met (16 in most
# runs, 32 in a few, never 64). Fillers are short, so that each one costs
# one prefill chunk and they stay in the batch while it grows.
WARM_MAX_BATCH = 32
FILLER_PROMPT = 128
FILLER_TOKENS = 40


def _pow2(n: int) -> int:
    return 1 << max(n - 1, 0).bit_length()


def _cdiv(a: int, b: int) -> int:
    return -(-a // b)


def _quantiles(n: int) -> List[float]:
    return [(i + 0.5) / n for i in range(n)]


def lengths(spec: Dict[str, Any], n: int) -> List[int]:
    """``n`` stratified quantiles of a clipped lognormal, ascending:
    ``median``, ``sigma``, ``min``, ``max`` and an optional ``multiple``
    that every length is rounded up to (within ``[min, max]``)."""
    nd = statistics.NormalDist()
    mult = int(spec.get("multiple", 1))
    lo = _cdiv(int(spec["min"]), mult) * mult
    hi = int(spec["max"]) // mult * mult
    out = []
    for u in _quantiles(n):
        x = spec["median"] * math.exp(spec["sigma"] * nd.inv_cdf(u))
        x = _cdiv(int(math.ceil(x)), mult) * mult
        out.append(min(max(x, lo), hi))
    return out


def length_bounds(spec: Dict[str, Any]) -> tuple:
    mult = int(spec.get("multiple", 1))
    return (_cdiv(int(spec["min"]), mult) * mult,
            int(spec["max"]) // mult * mult)


def gaps(rate: float, n: int) -> List[float]:
    """``n`` stratified quantiles of the exponential of mean ``1/rate``."""
    return [-math.log(1.0 - u) / rate for u in _quantiles(n)]


def classes(mix: Dict[str, float], n: int) -> List[str]:
    """``n`` class labels in the mix's proportions (largest remainders)."""
    names = sorted(mix)
    total = sum(mix.values())
    exact = [mix[k] / total * n for k in names]
    counts = [int(e) for e in exact]
    order = sorted(range(len(names)), key=lambda i: exact[i] - counts[i],
                   reverse=True)
    for i in order[:n - sum(counts)]:
        counts[i] += 1
    return [k for k, c in zip(names, counts) for _ in range(c)]


def _phase(traffic: Dict[str, Any], n: int, seed: int, tag: int,
           vocab: int, t0: float) -> List[Dict[str, Any]]:
    """``n`` requests due from ``t0`` on: the fixed multiset of
    (prompt, output, class) triples and gaps, in the mix's fixed order,
    with prompt ids from ``seed``."""
    if n <= 0:
        return []
    fixed = random.Random(traffic["pairing_seed"] * 1000 + tag)
    p = lengths(traffic["prompt"], n)
    o = lengths(traffic["output"], n)
    c = classes(traffic["slo_mix"], n)
    fixed.shuffle(o)
    fixed.shuffle(c)
    triples = list(zip(p, o, c))
    g = gaps(traffic["rate_rps"], n)
    fixed.shuffle(triples)
    fixed.shuffle(g)
    rng = random.Random(seed * 7919 + tag)
    out, t = [], t0
    for (plen, olen, cls), gap in zip(triples, g):
        t += gap
        out.append(dict(due=t, prompt_ids=[rng.randrange(1, vocab)
                                           for _ in range(plen)],
                        max_tokens=olen, slo_class=cls))
    return out


def schedule(traffic: Dict[str, Any], seed: int, seconds: float,
             vocab: int) -> Dict[str, List[Dict[str, Any]]]:
    """The pre-roll (not measured; lets the KV pool reach its steady
    occupancy) and the measured window, as one continuous arrival stream.
    ``due`` is in seconds from the start of the pre-roll."""
    rate = traffic["rate_rps"]
    pre_n = int(round(rate * traffic["preroll_s"]))
    win_n = int(round(rate * seconds))
    pre = _phase(traffic, pre_n, seed, 1, vocab, 0.0)
    t_win = float(traffic["preroll_s"])
    win = _phase(traffic, win_n, seed, 2, vocab, t_win)
    return dict(preroll=pre, window=win, window_start=t_win)


# ------------------------------------------------------------------ warm-up
def prefill_shapes(traffic: Dict[str, Any]) -> set:
    """(pow2 chunk tokens, pow2 context blocks) buckets a mix's prefill
    chunks can reach: chunks are cut from a 512-token budget shared by the
    prompts in flight, so with every prompt a multiple of ``m`` each chunk
    and each chunk start is a multiple of ``m``."""
    m = int(traffic["prompt"].get("multiple", 1))
    _, hi = length_bounds(traffic["prompt"])
    shapes = set()
    for take in range(m, CHUNK + 1, m):
        for start in range(0, hi - take + 1, m):
            shapes.add((_pow2(take), _pow2(_cdiv(start + take, BLOCK))))
    return shapes


def decode_blocks(traffic: Dict[str, Any]) -> List[int]:
    """pow2 block counts a decode batch's longest context can reach."""
    lo, hi = length_bounds(traffic["prompt"])
    _, ohi = length_bounds(traffic["output"])
    first = _pow2(_cdiv(lo + 1, BLOCK))
    last = _pow2(_cdiv(hi + ohi, BLOCK))
    out, b = [], first
    while b <= last:
        out.append(b)
        b *= 2
    return out


def _single_shapes(plen: int) -> set:
    """Prefill buckets one prompt meets when it runs alone."""
    shapes, start = set(), 0
    while start < plen:
        take = min(CHUNK, plen - start)
        shapes.add((_pow2(take), _pow2(_cdiv(start + take, BLOCK))))
        start += take
    return shapes


def _pair_shapes(a: int, b: int) -> set:
    """Prefill buckets of prompts ``a`` then ``b`` submitted together: the
    first iteration gives ``a`` the whole budget, and ``b`` starts on what
    ``a`` leaves of the next one."""
    shapes, pos, first = set(), {0: 0, 1: 0}, True
    lens = (a, b)
    while pos[0] < a or pos[1] < b:
        budget = CHUNK
        for i in (0, 1):
            if first and i == 1:
                break
            take = min(budget, lens[i] - pos[i])
            if take <= 0:
                continue
            shapes.add((_pow2(take), _pow2(_cdiv(pos[i] + take, BLOCK))))
            pos[i] += take
            budget -= take
            if budget <= 0:
                break
        first = False
    return shapes


def warmup_plan(traffic: Dict[str, Any], settings: Dict[str, Any], seed: int,
                vocab: int) -> List[List[List[Dict[str, Any]]]]:
    """Stages of groups of requests. The driver submits a group, waits for
    every request in it to return its first token, submits the next group,
    and lets a stage finish before the next one starts.

    * Prefill: prompts run alone, then pairs that split the chunk budget,
      chosen greedily until every bucket of ``prefill_shapes`` is met.
    * Decode: per block bucket, one anchor request whose context sits in
      that bucket, then fillers in groups of 1, 2, 4, ... so the batch
      passes every pow2 size up to ``WARM_MAX_BATCH`` (or the cell's own
      ``warm_max_batch``, for traffic that runs more requests at once).
    Every request is deterministic in its sizes; ids come from the seed."""
    max_batch = int(settings.get("warm_max_batch", WARM_MAX_BATCH))
    rng = random.Random(seed * 31 + 5)
    m = int(traffic["prompt"].get("multiple", 1))
    lo, hi = length_bounds(traffic["prompt"])

    def req(plen: int, tokens: int) -> Dict[str, Any]:
        return dict(prompt_ids=[rng.randrange(1, vocab) for _ in range(plen)],
                    max_tokens=tokens, slo_class="batch")

    stages: List[List[List[Dict[str, Any]]]] = []
    want = prefill_shapes(traffic)
    cands = [((p,), _single_shapes(p)) for p in range(m, hi + 1, m)]
    cands += [((a, b), _pair_shapes(a, b))
              for a in range(CHUNK + m, 2 * CHUNK, m)
              for b in range(m, CHUNK + 1, m)]
    met: set = set()
    while want - met:
        best = max(cands, key=lambda c: (len((c[1] & want) - met), -sum(c[0])))
        gain = (best[1] & want) - met
        if not gain:
            break
        met |= best[1]
        stages.append([[req(p, 1) for p in best[0]]])
    for blocks in decode_blocks(traffic):
        anchor = next((p for p in range(lo, hi + 1, m)
                       if _cdiv(p + 1, BLOCK) > blocks // 2), hi)
        room = blocks * BLOCK - anchor - 1
        groups = [[req(anchor, max(min(room, 2 * FILLER_TOKENS), 1))]]
        size = 1
        while size < max_batch:
            groups.append([req(FILLER_PROMPT, FILLER_TOKENS)
                           for _ in range(size)])
            size *= 2
        stages.append(groups)
    return stages
