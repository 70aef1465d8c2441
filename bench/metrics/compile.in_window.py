"""Compiles inside the window.  See ``bench/readers.py``."""
from bench.readers import compiles_before as before  # noqa: F401
from bench.readers import compiles_in_window as read  # noqa: F401
