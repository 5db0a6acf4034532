"""The dense decoder the benchmark serves (Qwen2, Llama): grouped-query
global attention and a SwiGLU MLP in every layer, an untied head. Its
arithmetic lives in ``flops.py``, ``weights.py`` and ``reference.py``; this
module hands it to the harness under the names ``arch`` documents."""
from __future__ import annotations

from typing import Dict

from flops import (decode_attention_work, decode_token_flops, dims,  # noqa: F401
                   prefill_flops, row_bytes, weight_bytes)

__all__ = ["dims", "shapes", "std", "weight_bytes", "row_bytes",
           "decode_token_flops", "prefill_flops", "decode_attention_work",
           "logits", "server_kwargs", "RUNNER_COUNTERS", "KERNELS"]

RUNNER_COUNTERS = ("decode_tokens", "decode_batches", "prefill_chunks_run",
                   "attn_block_slots", "attn_blocks_live")
KERNELS: Dict[str, str] = {}      # trace_reduce.KERNELS names this decoder's


def shapes(cfg):
    import weights
    return weights.shapes(cfg)


def std(name, shape):
    import weights
    return weights.std(name, shape)


def logits(cfg, layers, head, ids, start, n_out, control=False):
    import reference
    return reference.logits(cfg, layers, head, ids, start, n_out,
                            control=control)


def server_kwargs(cfg: Dict, rehearse: bool) -> Dict[str, int]:
    """The program's uniform paged decoder at the configuration's depth;
    in a rehearsal, its own reduced model (``runner_layers=0``)."""
    return dict(runner_layers=0 if rehearse
                else int(cfg["num_hidden_layers"]))
