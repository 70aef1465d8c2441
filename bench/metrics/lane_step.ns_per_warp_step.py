"""Lane-step device time per useful warp-step.  See ``bench/readers.py``."""
from bench.readers import lane_step_ns_per_warp_step as read  # noqa: F401
