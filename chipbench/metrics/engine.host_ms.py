"""Engine + RotaSched: host milliseconds per iteration in the engine's step
outside planning and execute; moves tbt_p99_s."""
from span_metrics import engine_host_ms as read  # noqa: F401
