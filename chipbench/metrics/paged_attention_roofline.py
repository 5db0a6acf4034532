"""Kernels: percent of its roofline the paged-attention kernel reaches; moves
tbt_p99_s."""
from layer_metrics import paged_attention_roofline as read  # noqa: F401
