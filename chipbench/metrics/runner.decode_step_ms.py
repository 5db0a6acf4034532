"""Runner: device milliseconds of one decode-step program; moves tbt_p99_s."""
from layer_metrics import decode_step_ms as read  # noqa: F401
