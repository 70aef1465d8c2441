"""% of the window assembling results on the host.  See ``bench/recorder.py``."""
from bench.recorder import before  # noqa: F401
from bench.recorder import assemble_share as read  # noqa: F401
