"""Kernels: percent of HBM bandwidth the KV row-copy kernel reaches; moves
tbt_p99_s."""
from layer_metrics import kv_copy_roofline as read  # noqa: F401
