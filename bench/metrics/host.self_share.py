"""% of the window outside the program's device calls.  See ``bench/readers.py``."""
from bench.readers import host_self_share as read  # noqa: F401
