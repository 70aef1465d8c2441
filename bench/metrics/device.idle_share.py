"""% of the traced window with no program on the device.  See ``bench/readers.py``."""
from bench.readers import idle_share as read  # noqa: F401
