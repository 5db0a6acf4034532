"""The arithmetic of the per-layer metrics. Each metric is a file of its
own under ``metrics/`` (found by its name in ``BENCHMARK.json``) that
names one of these readers, so a later cell whose quantity moves another
end-to-end metric can name the same reader under a name of its own.

A reader takes the run's context (``run.context``): the configuration's
architecture module (``arch``, whose work counts the readers use) and its
sizes (``dims``), program counters at the window's edges, the trace's
reduction (a chip run only, else None), the chip's peaks, and the client's
decode contexts and first-token prompts inside the window. It returns None
where it finds nothing to read, never 0 for a share of a roofline or a
peak."""
from __future__ import annotations

from flops import roofline_seconds

ROWS = ("d2h_rows", "h2d_rows", "d2d_rows")


def _delta(ctx, *keys):
    if any(ctx["c1"].get(k) is None for k in keys):
        return None
    return sum(ctx["c1"][k] - ctx["c0"][k] for k in keys)


def iteration_ms(ctx):
    """Engine + RotaSched: mean host-clock milliseconds per engine
    iteration over the window (``EngineStats.iterations``)."""
    n = _delta(ctx, "iterations")
    return ctx["window_s"] / n * 1e3 if n else None


def rotations_per_s(ctx):
    """Engine + RotaSched: requests moved out of HBM per second, active
    rotations and passive preemptions together (``EngineStats``)."""
    n = _delta(ctx, "active_rotations", "passive_preemptions")
    return None if n is None else n / ctx["window_s"]


def rows_moved_per_s(ctx):
    """Block table + DuplexKV: KV block rows moved per second, device to
    host, host to device and device to device (``PagedKVStore``)."""
    n = _delta(ctx, *ROWS)
    return None if n is None else n / ctx["window_s"]


def _program(ctx, name):
    tr = ctx["trace"]
    p = tr and tr["programs"].get(name)
    return p if p and p["calls"] else None


def decode_step_ms(ctx):
    """Runner: device milliseconds of one decode-step program (every layer
    of one batched decode iteration), from the trace."""
    p = _program(ctx, "decode")
    return p["seconds"] / p["calls"] * 1e3 if p else None


def prefill_ms_per_ktok(ctx):
    """Runner: device milliseconds of prefill-chunk programs per 1,000
    prompt tokens executed in the window (``EngineStats.prefill_tokens``)."""
    p = _program(ctx, "prefill")
    n = _delta(ctx, "prefill_tokens")
    return p["seconds"] * 1e3 / (n / 1e3) if p and n else None


def paged_attention_roofline(ctx):
    """Kernels: percent of its roofline the paged-attention kernel reaches:
    the least time the chip needs for the K/V reads and FLOPs of the decode
    tokens the client received in the window (their contexts, every layer)
    over the kernel's device time."""
    tr = ctx["trace"]
    t = tr and tr["kernels"].get("paged_attention")
    if not t or not ctx["decode_ctx"]:
        return None
    flops, nbytes = ctx["arch"].decode_attention_work(ctx["dims"],
                                                      ctx["decode_ctx"])
    return roofline_seconds(flops, nbytes, ctx["peaks"])[0] / t * 100.0


def kv_copy_roofline(ctx):
    """Kernels: percent of HBM bandwidth the KV row-copy kernel reaches:
    every row the store moved in the window read once and written once,
    over the kernel's device time."""
    tr = ctx["trace"]
    t = tr and tr["kernels"].get("kv_copy")
    rows = _delta(ctx, *ROWS)
    if not t or not rows:
        return None
    nbytes = 2 * rows * ctx["arch"].row_bytes(ctx["dims"])
    return roofline_seconds(0, nbytes, ctx["peaks"])[0] / t * 100.0


def attn_live_share(ctx):
    """Kernels: percent of the block slots of the padded block tables over
    which the paged-attention kernel was launched that held a live KV block
    (``attn_blocks_live`` / ``attn_block_slots``, every layer, over the
    window): the work the kernel skips is the rest."""
    live = _delta(ctx, "attn_blocks_live")
    slots = _delta(ctx, "attn_block_slots")
    return live / slots * 100.0 if slots else None


def idle_share(ctx):
    """Device: percent of the traced window in which no operation ran on
    the chip."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    return (1.0 - tr["busy_s"] / tr["window_s"]) * 100.0


def step_mfu(ctx):
    """Device: model FLOPs utilisation of the whole serving step, percent:
    the FLOPs the model needs for the prompts whose first token, and for
    the decode tokens, the client received in the window, over the window
    times the chip's bf16 peak."""
    tr = ctx["trace"]
    if not tr or tr["window_s"] <= 0:
        return None
    a, m = ctx["arch"], ctx["dims"]
    flops = (sum(a.prefill_flops(m, p) for p in ctx["first_token_prompts"])
             + sum(a.decode_token_flops(m, c) for c in ctx["decode_ctx"]))
    if not flops:
        return None
    return flops / (tr["window_s"] * ctx["peaks"]["bf16_flops_per_s"]) * 100
