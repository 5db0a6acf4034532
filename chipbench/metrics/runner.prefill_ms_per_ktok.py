"""Runner: prefill device milliseconds per 1,000 prompt tokens; moves
ttft_p90_s."""
from layer_metrics import prefill_ms_per_ktok as read  # noqa: F401
