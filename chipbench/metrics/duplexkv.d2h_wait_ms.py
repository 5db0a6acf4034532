"""Block table + DuplexKV: host milliseconds per iteration blocked on the KV
store's device-to-host readback; moves tbt_p99_s."""
from span_metrics import d2h_wait_ms as read  # noqa: F401
