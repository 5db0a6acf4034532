"""Block table + DuplexKV: KV rows moved per second (D2H, H2D, D2D); moves
ttft_p90_s."""
from layer_metrics import rows_moved_per_s as read  # noqa: F401
