"""Engine + RotaSched: requests rotated or preempted out of HBM per second;
moves ttft_p90_s."""
from layer_metrics import rotations_per_s as read  # noqa: F401
