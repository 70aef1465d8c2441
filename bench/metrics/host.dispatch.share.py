"""% of the window in the façade, planner and executable lookup.  See ``bench/recorder.py``."""
from bench.recorder import before  # noqa: F401
from bench.recorder import dispatch_share as read  # noqa: F401
