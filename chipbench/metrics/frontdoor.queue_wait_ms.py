"""HTTP front door: mean host-clock wait from receipt to admission of the
requests admitted in the window; moves ttft_p90_s."""
from span_metrics import queue_wait_ms as read  # noqa: F401
