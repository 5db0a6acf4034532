"""Device: model FLOPs utilisation of the whole serving step; moves tbt_p99_s."""
from layer_metrics import step_mfu as read  # noqa: F401
