"""Seconds from process start to the start of the window.  See ``bench/readers.py``."""
from bench.readers import setup_s as read  # noqa: F401
