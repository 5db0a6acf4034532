"""Engine + RotaSched: host-clock milliseconds per engine iteration; moves
tbt_p99_s."""
from layer_metrics import iteration_ms as read  # noqa: F401
