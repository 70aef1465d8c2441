"""% of the window packing operands.  See ``bench/recorder.py``."""
from bench.recorder import before  # noqa: F401
from bench.recorder import pack_share as read  # noqa: F401
