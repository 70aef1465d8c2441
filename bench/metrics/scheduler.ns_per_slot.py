"""Issue-scheduler device time per issued SM slot.  See ``bench/readers.py``."""
from bench.readers import scheduler_ns_per_slot as read  # noqa: F401
