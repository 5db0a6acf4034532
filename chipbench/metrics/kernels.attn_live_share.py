"""Kernels: percent of the paged-attention kernel's block slots that held a
live KV block; moves tbt_p99_s."""
from layer_metrics import attn_live_share as read  # noqa: F401
