"""``train.moe_gmm_roofline``: read by ``benchmark/afmoe_metrics.py``."""
from benchmark.afmoe_metrics import moe_gmm_roofline as read  # noqa: F401
