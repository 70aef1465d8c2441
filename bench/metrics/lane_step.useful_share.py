"""% of lane-step row-iterations that execute a useful step.  See ``bench/recorder.py``."""
from bench.recorder import before  # noqa: F401
from bench.recorder import lane_step_useful_share as read  # noqa: F401
