"""The benchmark's readings of the program's own spans and counters
(``bench/recorder.py``), in tiny runs on the CPU.

- an untraced run leaves the recorder off and its snapshot empty;
- a traced run reports the host shares and useful shares its cell lists
  and ends with the recorder off;
- on a program without the recorder every reading is ``None`` and none
  raises.
"""
from __future__ import annotations

import contextlib
import os
import sys

import pytest

from bench import harness
from bench import recorder
from repro import obs
from tests.bench.test_bench_runs import CELLS, ROOT, _run, tiny  # noqa: F401

HOST = ("host.dispatch.share", "host.pack.share", "host.assemble.share")
USEFUL = ("lane_step.useful_share", "scheduler.useful_share")
RECORDED = {"t-suite": HOST + USEFUL[:1], "t-distinct": HOST + USEFUL,
            "t-replicated": HOST + USEFUL}


@pytest.fixture
def recorder_off():
    obs.disable()
    obs.reset()
    yield
    obs.disable()
    obs.reset()


@pytest.mark.parametrize("cell", CELLS)
def test_an_untraced_run_leaves_the_recorder_off(tiny, capsys,  # noqa: F811
                                                 recorder_off, cell):
    result = _run(tiny, capsys, cell)
    assert result["correct"] is True
    assert not obs.enabled()
    assert obs.snapshot() == {"spans": {}, "counters": {}}


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reads_the_recorder_then_turns_it_off(
        tiny, capsys, monkeypatch, recorder_off, cell):  # noqa: F811
    from bench import trace
    fixture = os.path.join(ROOT, "tests", "bench", "trace_tiny.xplane.pb")
    monkeypatch.setattr(harness, "profiled",
                        lambda directory: contextlib.nullcontext())
    monkeypatch.setattr(harness, "read_trace", lambda directory, chips:
                        trace.summarize(fixture, chips=chips))
    result = _run(tiny, capsys, cell, trace="1")
    assert result["correct"] is True
    assert not obs.enabled()
    got = result["metrics"]
    # the recorder's readings that the cell lists, and at least these in
    # the cells of the paper's suite and grids
    listed = {m["name"] for m in harness.cell_spec(
        harness.load_manifest(tiny), cell)["per_layer"]}
    listed |= set(RECORDED.get(cell, ()))
    host = [n for n in HOST if n in listed]
    useful = [n for n in USEFUL if n in listed]
    assert set(host) | set(useful) <= set(got)
    for name in host + useful:
        assert got[name]["unit"] == "%"
    assert all(got[n]["value"] >= 0 for n in host)
    if host:
        assert 0 < sum(got[n]["value"] for n in host) < 100
    assert all(0 < got[n]["value"] <= 100 for n in useful)


def test_without_the_recorder_nothing_is_read(monkeypatch, recorder_off):
    import repro
    monkeypatch.delattr(repro, "obs")
    monkeypatch.setitem(sys.modules, "repro.obs", None)  # import fails
    ctx = harness.Context(device={}, window_s=10.0)
    recorder.before(ctx)
    for read in (recorder.dispatch_share, recorder.pack_share,
                 recorder.assemble_share, recorder.lane_step_useful_share,
                 recorder.scheduler_useful_share):
        assert read(ctx) is None
