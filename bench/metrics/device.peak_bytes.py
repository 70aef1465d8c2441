"""Peak device memory in use after the window.  See ``bench/readers.py``."""
from bench.readers import peak_bytes as read  # noqa: F401
