"""Simulated warp instructions per second of the window.  See ``bench/readers.py``."""
from bench.readers import warp_instr_per_s as read  # noqa: F401
