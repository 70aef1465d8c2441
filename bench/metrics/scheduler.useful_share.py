"""% of scanned scheduler slots that issue.  See ``bench/recorder.py``."""
from bench.recorder import before  # noqa: F401
from bench.recorder import scheduler_useful_share as read  # noqa: F401
