"""In-process spans and counters: where the simulator's host time goes, and
how much of the device work it launches is useful.

Off by default.  A span always times itself (``.seconds``), which is how
the engines take their ``wall_time_s`` and ``compile_time_s``; only while
the recorder is enabled does it also

- enter a ``jax.profiler.TraceAnnotation`` of the same name, so the span
  lands in a profiler trace on the device programs' clock (where ``jax`` is
  already imported: a jax-free process stays jax-free), and
- add its count, total time and self time (its duration less what its
  child spans on the same thread cover) to in-memory totals.

:func:`count` adds to a named counter while enabled.  :func:`snapshot` is
the operator's view::

    from repro import obs
    obs.reset(); obs.enable()
    ...                                # run the simulator
    obs.disable()
    obs.snapshot()  # {"spans": {name: {"n", "total_s", "self_s"}},
                    #  "counters": {name: n}}

Nothing is written to disk; a running profiler writes the annotations when
its trace stops.  Span and counter names are listed in
``docs/mechanisms.md`` ("Observability").
"""
from __future__ import annotations

import sys
import threading
import time

__all__ = ["span", "count", "enable", "disable", "enabled", "reset",
           "snapshot"]

_clock = time.perf_counter_ns
_on = False
_lock = threading.Lock()
_local = threading.local()
_spans: dict = {}          # name -> [n, total_ns, self_ns]
_counters: dict = {}       # name -> int


def _stack() -> list:
    try:
        return _local.stack
    except AttributeError:
        _local.stack = []
        return _local.stack


class span:
    """``with span(name, **meta) as s: ...``; ``s.seconds`` afterwards."""

    __slots__ = ("name", "meta", "seconds", "_start", "_child_ns", "_ann",
                 "_recorded")

    def __init__(self, name: str, **meta):
        self.name = name
        self.meta = meta
        self.seconds = 0.0

    def __enter__(self) -> "span":
        self._recorded = _on
        if self._recorded:
            self._child_ns = 0
            _stack().append(self)
            profiler = sys.modules.get("jax.profiler")
            self._ann = None if profiler is None else \
                profiler.TraceAnnotation(self.name, **self.meta)
            if self._ann is not None:
                self._ann.__enter__()
        self._start = _clock()
        return self

    def __exit__(self, *exc) -> bool:
        ns = _clock() - self._start
        self.seconds = ns / 1e9
        if self._recorded:
            if self._ann is not None:
                self._ann.__exit__(*exc)
            stack = _stack()
            stack.pop()
            if stack:
                stack[-1]._child_ns += ns
            with _lock:
                tot = _spans.setdefault(self.name, [0, 0, 0])
                tot[0] += 1
                tot[1] += ns
                tot[2] += ns - self._child_ns
        return False


def count(name: str, n: int = 1) -> None:
    """Add ``n`` to counter ``name`` while the recorder is enabled."""
    if _on:
        with _lock:
            _counters[name] = _counters.get(name, 0) + int(n)


def enable() -> None:
    global _on
    _on = True


def disable() -> None:
    global _on
    _on = False


def enabled() -> bool:
    """Whether spans and counters are being recorded: callers skip work
    done only to feed a counter."""
    return _on


def reset() -> None:
    """Drop every total and counter."""
    with _lock:
        _spans.clear()
        _counters.clear()


def snapshot() -> dict:
    """The totals so far: spans by name (calls, total and self seconds)
    and counters."""
    with _lock:
        return {"spans": {name: {"n": n, "total_s": total / 1e9,
                                 "self_s": own / 1e9}
                          for name, (n, total, own) in _spans.items()},
                "counters": dict(_counters)}
