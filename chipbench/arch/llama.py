"""``model_type`` llama: the dense decoder (``arch/dense.py``)."""
from arch.dense import *  # noqa: F401,F403
