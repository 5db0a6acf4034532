"""``model_type`` qwen2: the dense decoder (``arch/dense.py``)."""
from arch.dense import *  # noqa: F401,F403
