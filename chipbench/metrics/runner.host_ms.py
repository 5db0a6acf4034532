"""Runner: host milliseconds per iteration in execute outside its waits on
the device; moves tbt_p99_s."""
from span_metrics import runner_host_ms as read  # noqa: F401
