"""The reduction from a profiler trace to the benchmark's device numbers.

``trace_tiny.xplane.pb`` was recorded on one TPU v5e by a traced run of a
tiny grid cell (two SMs of two warps, 64 steps of fuel) through the
harness: it holds the harness's ``bench.*`` host spans and one device event
per XLA program.  The reduction is checked against a plain recomputation
from the same events, and on hand-made intervals.
"""
from __future__ import annotations

import os

import pytest

from bench import trace

FIXTURE = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                       "trace_tiny.xplane.pb")


@pytest.fixture(scope="module")
def data():
    from jax.profiler import ProfileData
    return ProfileData.from_file(FIXTURE)


def _events(data, plane_prefix, line_name=None):
    for plane in data.planes:
        if not plane.name.startswith(plane_prefix):
            continue
        for line in plane.lines:
            if line_name is None or line.name == line_name:
                for ev in line.events:
                    yield ev


def test_union_and_clip():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [(0, 3), (5, 8)]
    assert trace.clip([(0, 3), (5, 8)], 2, 6) == [(2, 3), (5, 6)]
    assert trace.module_name("jit_one(5210897963021604052)") == "jit_one"


def test_fixture_is_small_and_has_both_clocks(data):
    assert os.path.getsize(FIXTURE) < 1 << 20
    names = {p.name for p in data.planes}
    assert "/device:TPU:0" in names
    spans = {ev.name for ev in _events(data, "/host:")}
    assert {"bench.window", "bench.generate", "bench.call",
            "bench.results"} <= spans


def test_reduction_matches_a_plain_recomputation(data):
    got = trace.summarize(data, chips=1)
    window = next(ev for ev in _events(data, "/host:")
                  if ev.name == "bench.window")
    lo, hi = window.start_ns, window.start_ns + window.duration_ns
    progs = sorted((ev.start_ns, ev.start_ns + ev.duration_ns)
                   for ev in _events(data, "/device:TPU:0", "XLA Modules"))
    busy_ns, end = 0.0, lo
    for s, e in progs:               # sweep: count each covered ns once
        s, e = max(s, end, lo), min(e, hi)
        if e > s:
            busy_ns += e - s
            end = e
    assert got.window_s == pytest.approx((hi - lo) / 1e9, abs=1e-12)
    assert got.busy_s == pytest.approx(busy_ns / 1e9, abs=1e-12)
    assert 0 < got.busy_s <= got.window_s
    assert {"jit_one", "jit_schedule"} <= set(got.modules)
    assert sum(got.modules.values()) == pytest.approx(got.busy_s, rel=1e-9)
    idle = sum(s for _, s in got.idle_gaps)
    assert idle == pytest.approx(got.window_s - got.busy_s, rel=1e-9,
                                 abs=1e-12)
    for name, seconds in got.idle_gaps:
        assert name.startswith("bench.") or name == "outside bench spans"
        assert seconds > 0
    lengths = [s for _, s in got.idle_gaps]
    assert lengths == sorted(lengths, reverse=True)


def test_breakdown_shape(data):
    got = trace.summarize(data, chips=1).breakdown()
    assert set(got) == {"device_ops", "idle_gaps"}
    for key in got:
        assert len(got[key]) <= 10
        for name, seconds in got[key]:
            assert isinstance(name, str) and seconds > 0


def test_a_trace_without_the_window_span_is_refused(data):
    class Stripped:
        planes = [p for p in data.planes if not p.name.startswith("/host:")]
    with pytest.raises(ValueError, match="bench.window"):
        trace.summarize(Stripped(), chips=1)


def test_every_metric_file_reads_a_traced_context(data):
    """Each metric file named in the manifest loads by its name and reads a
    context built from the fixture, or returns nothing where it finds
    nothing to read."""
    from types import SimpleNamespace

    from bench.harness import Context, load_manifest, load_metric
    root = os.path.dirname(os.path.dirname(os.path.dirname(FIXTURE)))
    summary = trace.summarize(data, chips=1)
    unit = SimpleNamespace(warp_instr=1000, useful_warp_steps=400,
                           issued_slots=1000,
                           device_call_s=summary.busy_s / 2)
    ctx = Context(device={"memory_peak_bytes": 1234}, setup_s=5.0,
                  window_s=summary.window_s, outcomes=[unit, unit],
                  trace=summary)
    manifest = load_manifest(root)
    readings = {}
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        module = load_metric(root, m["name"])
        if hasattr(module, "before"):
            module.before(ctx)
        readings[m["name"]] = module.read(ctx)
    assert readings["setup_s"] == 5.0
    assert readings["warp_instr_per_s"] == pytest.approx(
        2000 / summary.window_s)
    assert readings["device.peak_bytes"] == 1234
    assert readings["lane_step.ns_per_warp_step"] == pytest.approx(
        1e9 * summary.modules["jit_one"] / 800)
    assert readings["scheduler.ns_per_slot"] == pytest.approx(
        1e9 * summary.modules["jit_schedule"] / 2000)
    assert readings["device.idle_share"] == pytest.approx(
        100 * (1 - summary.busy_s / summary.window_s))
    assert readings["host.self_share"] == pytest.approx(
        100 * (summary.window_s - summary.busy_s) / summary.window_s)
    assert readings["compile.in_window"] == 0
    for name, value in readings.items():
        if name.endswith(".batch"):
            assert value == readings[name[:-len(".batch")]]
    ctx.trace = None                   # an untraced run: nothing to read
    assert load_metric(root, "device.idle_share").read(ctx) is None
    assert load_metric(root, "lane_step.ns_per_warp_step").read(ctx) is None
