"""Device: percent of the traced window with no op on the chip; moves
tbt_p99_s."""
from layer_metrics import idle_share as read  # noqa: F401
