"""One run of one benchmark cell.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own that this module finds by the name ``BENCHMARK.json`` gives:

- the configuration: the ``file`` its manifest entry names;
- the traffic mix: ``bench/traffic/<traffic>.json``, read by
  :mod:`bench.generator`, and the driver its ``entry`` names,
  ``bench/entries/<entry>.py``;
- each metric: ``bench/metrics/<name>.py``, a reader with ``read(ctx)`` that
  returns a number, or ``None`` where it finds nothing to read, and
  optionally ``before(ctx)``, called just before the window opens.

A run places JAX's compile cache, refuses to run without the chips the cell
asks for, builds every input from the seed, warms up on unit 0 of the
cell's own shapes (counted as set-up), then runs whole units back to back
until ``--seconds`` have passed.  With ``--trace 1`` the window runs under
the profiler and the cell's per-layer metrics are reported; with
``--trace 0`` its end-to-end metrics.  After the window the reference
checks the units (see :mod:`bench.check`), and the last line of standard
output is the result as one JSON object.
"""
from __future__ import annotations

import argparse
import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time
from dataclasses import dataclass, field

from . import peaks
from .check import Checker, sample_units
from .generator import Mix

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
#: added to ``LIBTPU_INIT_ARGS`` in traced runs only (see :func:`main`)
TRACE_FLAGS = "--xla_enable_hlo_trace=false"


# ---------------------------------------------------------------------------
# the manifest and the files it names
# ---------------------------------------------------------------------------

def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def load_manifest(root: str = ROOT) -> dict:
    return load_json(os.path.join(root, "BENCHMARK.json"))


def metric_applies(metric: dict, cell: dict, e2e_names) -> bool:
    """Whether ``metric`` is reported in ``cell``."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    return metric["moves"] in e2e_names


def cell_spec(manifest: dict, name: str) -> dict:
    """The cell ``name`` with its configuration entry and its metrics."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    configs = {c["name"]: c for c in manifest["configs"]}
    e2e = [m for m in manifest["end_to_end"]
           if "workloads" not in m or name in m["workloads"]]
    names = {m["name"] for m in e2e}
    layer = [m for m in manifest["per_layer"]
             if metric_applies(m, cell, names)]
    return {"cell": cell, "config": configs[cell["config"]],
            "end_to_end": e2e, "per_layer": layer}


def load_metric(root: str, name: str):
    path = os.path.join(root, "bench", "metrics", f"{name}.py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def require_device(chips: int) -> dict:
    """The accelerator this run measures; exits non-zero, printing no
    result, where JAX finds no TPU or fewer chips than the cell asks for."""
    import jax
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu" or len(devices) < chips:
        print(f"bench: the cell needs {chips} TPU chip(s); JAX found "
              f"{len(devices)} {platform} device(s)", file=sys.stderr)
        raise SystemExit(2)
    kind = devices[0].device_kind
    peaks.lookup(kind)
    return {"platform": platform, "kind": kind, "count": len(devices)}


def memory_peak_bytes(chips: int) -> "int | None":
    import jax
    peak = None
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peak = max(peak or 0, int(stats["peak_bytes_in_use"]))
    return peak


# ---------------------------------------------------------------------------
# the window
# ---------------------------------------------------------------------------

@dataclass
class Context:
    """What a metric reader reads."""

    device: dict
    setup_s: float = 0.0
    window_s: float = 0.0
    outcomes: list = field(default_factory=list)
    trace: object = None            # bench.trace.TraceSummary, traced runs
    store: dict = field(default_factory=dict)   # for readers' before()


def _span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def run_unit(mix: Mix, seed: int, k: int):
    with _span("bench.generate"):
        unit = mix.unit(seed, k)
    with _span("bench.call"):
        raw = mix.call(unit)
    with _span("bench.results"):
        return mix.account(unit, raw)


def run_window(mix: Mix, seed: int, seconds: float) -> tuple:
    """Units 1, 2, ... back to back until ``seconds`` have passed, or the
    window of the mix's own driver where it has one;
    ``(outcomes, window_s, attempted, failed)``."""
    outcomes, attempted, failed = [], 0, 0
    k = 1
    with _span("bench.window"):
        if mix.window is not None:
            return mix.window(mix, seed, seconds, run_unit)
        start = time.perf_counter()
        while True:
            attempted += 1
            try:
                outcomes.append(run_unit(mix, seed, k))
            except Exception as exc:          # a unit the program failed
                failed += 1
                print(f"bench: unit {k} failed: {exc!r}", file=sys.stderr)
            k += 1
            if time.perf_counter() - start >= seconds:
                break
        window_s = time.perf_counter() - start
    return outcomes, window_s, attempted, failed


@contextlib.contextmanager
def profiled(directory: str):
    import jax
    shutil.rmtree(directory, ignore_errors=True)
    os.makedirs(directory, exist_ok=True)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1          # the benchmark's own spans
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(directory, profiler_options=opts)
    try:
        yield
    finally:
        jax.profiler.stop_trace()


def read_trace(directory: str, chips: int):
    """The reduction of the profile the traced window wrote."""
    from .trace import summarize
    path = sorted(glob.glob(os.path.join(
        directory, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    return summarize(path, chips=chips)


def read_metrics(specs: list, modules: dict, ctx: Context) -> dict:
    out = {}
    for spec in specs:
        value = modules[spec["name"]].read(ctx)
        if value is not None:
            out[spec["name"]] = {"value": value, "unit": spec["unit"]}
    return out


# ---------------------------------------------------------------------------
# entry point
# ---------------------------------------------------------------------------

def parse_args(argv):
    ap = argparse.ArgumentParser(
        prog="bench/run.py",
        description="Run one benchmark cell once and print its result.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, *, t0: "float | None" = None, root: str = ROOT) -> int:
    t0 = time.perf_counter() if t0 is None else t0
    args = parse_args(argv)
    spec = cell_spec(load_manifest(root), args.workload)
    cell = spec["cell"]
    chips = int(cell["chips"])
    config = load_json(os.path.join(root, spec["config"]["file"]))
    mix_data = load_json(os.path.join(root, "bench", "traffic",
                                      f"{cell['traffic']}.json"))
    metrics = spec["per_layer"] if args.trace else spec["end_to_end"]
    modules = {m["name"]: load_metric(root, m["name"]) for m in metrics}
    if args.trace:
        # the device trace keeps one event per XLA program and none per
        # operation: the lane step's loop runs millions of operations a
        # second, more than the profiler's buffer holds
        os.environ["LIBTPU_INIT_ARGS"] = " ".join(filter(None, (
            os.environ.get("LIBTPU_INIT_ARGS"), TRACE_FLAGS)))

    from repro.engine import install_jax_cache
    install_jax_cache()
    t_import = time.perf_counter()
    device = require_device(chips)
    t_device = time.perf_counter()
    mix = Mix(config, mix_data, os.path.join(root, "bench"))

    # set-up: unit 0 compiles (or loads) every shape the window uses
    from repro.engine.adapters import batch_cache_stats
    compile_s = batch_cache_stats()["trace_time_s"]
    run_unit(mix, args.seed, 0)
    t_warm = time.perf_counter()
    compile_s = batch_cache_stats()["trace_time_s"] - compile_s
    print(f"bench: set-up {t_import - t0:.3f} s imports, "
          f"{t_device - t_import:.3f} s device start, "
          f"{t_warm - t_device:.3f} s warm-up unit ({compile_s:.3f} s of it "
          f"compiling or loading the lane step)", file=sys.stderr)
    ctx = Context(device=device)
    for m in metrics:
        if hasattr(modules[m["name"]], "before"):
            modules[m["name"]].before(ctx)

    trace_dir = os.path.join(root, "bench_out", "trace", cell["name"])
    tracing = profiled(trace_dir) if args.trace else contextlib.nullcontext()
    with tracing:
        ctx.setup_s = time.perf_counter() - t0
        outcomes, window_s, attempted, failed = run_window(
            mix, args.seed, args.seconds)
    ctx.outcomes, ctx.window_s = outcomes, window_s
    ctx.device = dict(device, memory_peak_bytes=memory_peak_bytes(chips))
    result = {"correct": False, "attempted": attempted, "failed": failed}
    if args.trace:
        ctx.trace = read_trace(trace_dir, chips)
        ctx.device.update(busy_s=ctx.trace.busy_s,
                          window_s=ctx.trace.window_s)
    result["metrics"] = read_metrics(metrics, modules, ctx)
    result["device"] = ctx.device
    if args.trace:
        result["breakdown"] = ctx.trace.breakdown()

    # the reference, once the window has closed and the peak is read
    checker = Checker(mix)
    sample = sample_units(outcomes, mix_data["check_units"], args.seed)
    totals = {"warps_differing": 0, "cells_differing": 0}
    for outcome in sample:
        got = checker.check(outcome)
        for key in totals:
            totals[key] += got[key]
    checks = {"failed_units": {"value": failed, "limit": 0},
              "warps_differing": {"value": totals["warps_differing"],
                                  "limit": 0}}
    if any(o.unit.grid for o in sample):
        checks["cells_differing"] = {"value": totals["cells_differing"],
                                     "limit": 0}
    result["correct"] = bool(sample) and all(
        c["value"] <= c["limit"] for c in checks.values())
    print(f"bench: {cell['name']} seed {args.seed}: {len(outcomes)} units in "
          f"{window_s:.3f} s after {ctx.setup_s:.3f} s of set-up; checked "
          f"{len(sample)} units", file=sys.stderr)
    for name, c in checks.items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    result["checks"] = checks
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
