"""The in-process recorder (``repro.obs``): self time under nesting,
counters, reset and snapshot, a stack per thread, the disabled path, and a
process that imports it staying jax-free."""
from __future__ import annotations

import os
import subprocess
import sys
import threading

import pytest

from repro import obs

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def fresh_recorder():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


def _clock(monkeypatch, ticks):
    """Make the recorder's clock return ``ticks`` (ns), one per read."""
    it = iter(ticks)
    monkeypatch.setattr(obs, "_clock", lambda: next(it))


def test_self_time_excludes_child_spans(monkeypatch):
    # outer [0, 100] holds a [10, 40] and b [50, 60], which holds a [52, 55]
    _clock(monkeypatch, [0, 10, 40, 50, 52, 55, 60, 100])
    obs.enable()
    with obs.span("outer") as outer:
        with obs.span("a"):
            pass
        with obs.span("b") as b:
            with obs.span("a"):
                pass
    spans = obs.snapshot()["spans"]
    assert outer.seconds == pytest.approx(100e-9)
    assert b.seconds == pytest.approx(10e-9)
    want = {"outer": (1, 100, 60), "a": (2, 33, 33), "b": (1, 10, 7)}
    assert set(spans) == set(want)
    for name, (n, total, own) in want.items():
        assert spans[name]["n"] == n
        assert spans[name]["total_s"] == pytest.approx(total * 1e-9)
        assert spans[name]["self_s"] == pytest.approx(own * 1e-9)


def test_counters_add_while_enabled():
    obs.count("rows", 5)                  # off: ignored
    obs.enable()
    obs.count("rows", 3)
    obs.count("rows")
    obs.count("slots", 7)
    obs.disable()
    obs.count("rows", 100)                # off again: ignored
    assert obs.snapshot()["counters"] == {"rows": 4, "slots": 7}


def test_reset_and_snapshot():
    obs.enable()
    with obs.span("s"):
        pass
    obs.count("c", 2)
    obs.disable()
    snap = obs.snapshot()
    assert snap["spans"]["s"]["n"] == 1 and snap["counters"] == {"c": 2}
    snap["counters"]["c"] = 99            # a copy: the totals keep theirs
    assert obs.snapshot()["counters"] == {"c": 2}
    obs.reset()
    assert obs.snapshot() == {"spans": {}, "counters": {}}


def test_threads_nest_on_their_own_stacks():
    """Two threads hold their outer spans open at once and open their inner
    spans in turn: each outer span's self time leaves out its own inner span
    alone."""
    obs.enable()
    turn = threading.Barrier(2, timeout=10)
    errors = []

    def work(tag):
        try:
            with obs.span(f"{tag}.outer"):
                turn.wait()
                for _ in range(3):
                    with obs.span(f"{tag}.inner"):
                        turn.wait()
                    turn.wait()
        except Exception as exc:          # reported by the main thread
            errors.append(exc)

    threads = [threading.Thread(target=work, args=(t,)) for t in "xy"]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30)
    assert not any(t.is_alive() for t in threads) and not errors
    spans = obs.snapshot()["spans"]
    for tag in "xy":
        outer, inner = spans[f"{tag}.outer"], spans[f"{tag}.inner"]
        assert outer["n"] == 1 and inner["n"] == 3
        assert inner["self_s"] == pytest.approx(inner["total_s"], rel=1e-9)
        assert outer["self_s"] == pytest.approx(
            outer["total_s"] - inner["total_s"], rel=1e-9)


def test_disabled_records_nothing_and_enters_no_annotation(monkeypatch):
    import jax.profiler
    entered = []

    class Annotation:
        def __init__(self, name, **meta):
            self.args = (name, meta)

        def __enter__(self):
            entered.append(self.args)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    with obs.span("off", call=1) as s:
        pass
    obs.count("c", 1)
    assert s.seconds > 0
    assert entered == []
    assert obs.snapshot() == {"spans": {}, "counters": {}}
    obs.enable()
    with obs.span("on", call=2):
        pass
    assert entered == [("on", {"call": 2})]


def test_the_recorder_keeps_a_process_jax_free():
    code = ("import sys\n"
            "from repro import obs\n"
            "with obs.span('off'):\n"
            "    pass\n"
            "obs.enable()\n"
            "with obs.span('on'):\n"
            "    obs.count('c')\n"
            "assert obs.snapshot()['counters'] == {'c': 1}\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    env = dict(os.environ, PYTHONPATH=os.path.join(ROOT, "src"))
    done = subprocess.run([sys.executable, "-c", code], env=env,
                          capture_output=True, text=True, timeout=60)
    assert done.returncode == 0, done.stderr
