"""Tiny runs of every traffic mix through the benchmark harness, on the CPU.

The harness's look for a TPU is steered here, in the test: everything else
of a run — the manifest, the files found by name, the generator, the timed
entry, the window, the reference check and the last line — runs as on the
chip, at tiny sizes (two-lane-deep grids, 64 steps of fuel) in a temporary
copy of the benchmark.  Each cell brings its tiny version as a data file,
``tests/bench/tiny/<tiny cell>.json``; the copy lists the tiny cells alone.
The checks:

- each tiny cell prints the last-line schema with ``correct`` true, and a
  traced run reports every per-layer metric its cell lists;
- one corrupted result makes ``correct`` false;
- faults planted underneath the timed path (a lane step that returns its
  state unchanged, half of the batch left out, an answer altered where it
  is produced, a scheduler whose output is altered) make ``correct`` false;
- the control (the program's majority_first=False path) reads not correct;
- a new configuration, traffic mix and metric run from new files and
  manifest entries alone, and so does a new cell whose configuration,
  driver, window and per-layer metric are its own, with its tiny file;
- ``bench/run.py`` exits non-zero, with no result line, without a TPU and
  in a directory that holds only the benchmark.
"""
from __future__ import annotations

import contextlib
import dataclasses
import hashlib
import json
import os
import shutil
import subprocess
import sys

import numpy as np
import pytest

from bench import harness
from bench import control as bench_control

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
SEED = 3000000019          # seeds are any whole number past 32 bits


def _tiny_files(source_root) -> dict:
    """Each tiny cell's definition, ``tests/bench/tiny/<tiny cell>.json``,
    by name: ``of``, the real cell it shrinks; ``config``, the overrides of
    that cell's configuration (a new ``name``, ``machine`` merged key by
    key, other top-level keys replaced); optionally ``traffic``, the
    overrides of its traffic mix under a new ``name``; and a ``why`` for
    the reader."""
    directory = os.path.join(source_root, "tests", "bench", "tiny")
    return {f[:-len(".json")]: harness.load_json(os.path.join(directory, f))
            for f in sorted(os.listdir(directory)) if f.endswith(".json")}


CELLS = tuple(_tiny_files(ROOT))


def _tiny_root(source_root, base) -> str:
    """A copy of the benchmark at ``source_root`` whose manifest lists the
    tiny version of each cell that has a tiny file, and no other cell.

    Configurations, cells and traffic are found by name.  A metric's
    ``workloads`` keeps the tiny versions of the cells it lists; a metric
    left with none is dropped.  Tiny files that derive the same tiny
    configuration or traffic name must derive it alike."""
    root = str(base)
    shutil.copytree(os.path.join(source_root, "bench"),
                    os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    manifest = harness.load_manifest(source_root)
    configs = {c["name"]: c for c in manifest["configs"]}
    cells = {w["name"]: w for w in manifest["workloads"]}
    derived: dict = {}          # (kind, tiny name) -> (source, overrides)

    def derive(kind, source, over, data) -> str:
        """Write the tiny ``kind`` named ``over["name"]``: ``data``, the
        real one's, with ``over`` applied; its path."""
        name = over["name"]
        first = derived.setdefault((kind, name), (source, over))
        if first != (source, over):
            raise ValueError(f"tiny {kind} {name!r} is derived two ways: "
                             f"{first} and {(source, over)}")
        if kind == "config":
            data = {**data, **over, "machine": {**data["machine"],
                                                **over.get("machine", {})}}
            path = f"bench/configs/{name}.json"
        else:
            data = {**data, **{k: v for k, v in over.items() if k != "name"}}
            path = f"bench/traffic/{name}.json"
        with open(os.path.join(root, path), "w") as f:
            json.dump(data, f)
        return path

    tiny_cells: dict = {}           # real cell -> [its tiny cells]
    tiny_configs: dict = {}
    for name, tiny in _tiny_files(source_root).items():
        if tiny["of"] not in cells:
            raise ValueError(f"tiny cell {name!r} shrinks {tiny['of']!r}, "
                             "which BENCHMARK.json does not list")
        cell = cells[tiny["of"]]
        entry = configs[cell["config"]]
        config = tiny["config"]["name"]
        tiny_configs[config] = dict(entry, name=config, file=derive(
            "config", entry["name"], tiny["config"],
            harness.load_json(os.path.join(root, entry["file"]))))
        traffic = cell["traffic"]
        if "traffic" in tiny:
            derive("traffic", traffic, tiny["traffic"], harness.load_json(
                os.path.join(root, "bench", "traffic", f"{traffic}.json")))
            traffic = tiny["traffic"]["name"]
        tiny_cells.setdefault(cell["name"], []).append(dict(
            cell, name=name, config=config, traffic=traffic))
    manifest["configs"] = list(tiny_configs.values())
    manifest["workloads"] = [t for w in manifest["workloads"]
                             for t in tiny_cells.get(w["name"], ())]
    for section in ("end_to_end", "per_layer"):
        kept = []
        for m in manifest[section]:
            if "workloads" in m:
                m["workloads"] = [t["name"] for w in m["workloads"]
                                  for t in tiny_cells.get(w, ())]
                if not m["workloads"]:
                    continue
            kept.append(m)
        manifest[section] = kept
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root


def _on_the_cpu(mp):
    """Steer the harness's look for a TPU, and its compile cache, away."""
    import repro.engine
    mp.setattr(harness, "require_device", lambda chips: {
        "platform": "cpu", "kind": "cpu", "count": 1})
    mp.setattr(repro.engine, "install_jax_cache", lambda: None)


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        _on_the_cpu(mp)
        yield _tiny_root(ROOT, tmp_path_factory.mktemp("bench"))


@pytest.mark.parametrize("tiny_file,error", [
    ({"of": "grid-distinct",
      "config": {"name": "tiny-grid", "machine": {"max_steps": 32}}},
     "derived two ways"),
    ({"of": "retired-cell", "config": {"name": "tiny-suite"}},
     "does not list")])
def test_tiny_files_that_cannot_be_built_are_refused(tmp_path, tiny_file,
                                                     error):
    source = tmp_path / "source"
    for part in ("bench", os.path.join("tests", "bench", "tiny")):
        shutil.copytree(os.path.join(ROOT, part), source / part,
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), source)
    with open(source / "tests" / "bench" / "tiny" / "t-odd.json", "w") as f:
        json.dump(tiny_file, f)
    with pytest.raises(ValueError, match=error):
        _tiny_root(str(source), tmp_path / "tiny")


def _run(root, capsys, cell, seconds="0.3", seed=SEED, trace="0") -> dict:
    capsys.readouterr()
    assert harness.main(["--workload", cell, "--seed", str(seed),
                         "--seconds", seconds, "--trace", trace],
                        root=root) == 0
    out = capsys.readouterr()
    last = out.out.strip().splitlines()[-1]
    result = json.loads(last)
    # every number compared is on the last lines of standard error too
    tail = out.err.strip().splitlines()[-len(result["checks"]):]
    assert tail == [f"check {k} {v['value']} limit {v['limit']}"
                    for k, v in result["checks"].items()]
    return result


@pytest.mark.parametrize("cell", CELLS)
def test_tiny_run_prints_the_schema_and_is_correct(tiny, capsys, cell):
    result = _run(tiny, capsys, cell)
    assert list(result)[-1] == "checks"
    assert {"correct", "attempted", "failed", "metrics",
            "device"} <= set(result)
    assert result["correct"] is True, result["checks"]
    assert result["failed"] == 0 and result["attempted"] >= 1
    rate = "warp_instr_per_s"
    assert set(result["metrics"]) == {rate, "setup_s"}
    assert result["metrics"][rate]["unit"] == "warp-instr/s"
    assert result["metrics"][rate]["value"] > 0
    assert {"platform", "kind", "count",
            "memory_peak_bytes"} <= set(result["device"])
    for check in result["checks"].values():
        assert check["value"] == 0 and check["limit"] == 0


def _committed_trace(monkeypatch):
    """Traced runs read the committed chip trace, and the peak a number:
    what they check is that each reader gets what it reads."""
    from bench import trace
    fixture = os.path.join(ROOT, "tests", "bench", "trace_tiny.xplane.pb")
    monkeypatch.setattr(harness, "profiled",
                        lambda directory: contextlib.nullcontext())
    monkeypatch.setattr(harness, "read_trace", lambda directory, chips:
                        trace.summarize(fixture, chips=chips))
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda chips: 4321)


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_every_per_layer_metric_of_its_cell(
        tiny, capsys, monkeypatch, cell):
    _committed_trace(monkeypatch)
    result = _run(tiny, capsys, cell, trace="1")
    spec = harness.cell_spec(harness.load_manifest(tiny), cell)
    assert set(result["metrics"]) == {m["name"] for m in spec["per_layer"]}
    assert result["correct"] is True
    assert 0 < result["device"]["busy_s"] <= result["device"]["window_s"]
    assert result["device"]["memory_peak_bytes"] == 4321
    assert set(result["breakdown"]) == {"device_ops", "idle_gaps"}


def test_same_seed_same_inputs(tiny):
    config = harness.load_json(os.path.join(tiny, "bench", "configs",
                                            "tiny-grid.json"))
    mix = harness.load_json(os.path.join(tiny, "bench", "traffic",
                                         "tiny-distinct.json"))
    from bench.generator import Mix
    a = Mix(config, mix, os.path.join(tiny, "bench")).unit(SEED, 3)
    b = Mix(config, mix, os.path.join(tiny, "bench")).unit(SEED, 3)
    c = Mix(config, mix, os.path.join(tiny, "bench")).unit(SEED + 1, 3)
    mems = [[w.mem for w in cell] for cell in a.cells]
    assert all(np.array_equal(x, y) for row_a, row_b in
               zip(mems, [[w.mem for w in cell] for cell in b.cells])
               for x, y in zip(row_a, row_b))
    assert not all(np.array_equal(x.mem, y.mem) for x, y in
                   zip(a.cells[0], c.cells[0]))


def _corrupt(raw):
    """The raw result with one answer altered."""
    raw = list(raw)
    first = raw[0]
    if hasattr(first, "sm_trace"):                 # run_cells: SmResults
        w0 = first.warps[0]
        bad = dataclasses.replace(w0, regs=np.asarray(w0.regs) + 1)
        raw[0] = dataclasses.replace(first, warps=(bad,) + first.warps[1:])
    elif "sm" in first.meta:                       # run_batch on sm_jax
        sm = first.meta["sm"]
        raw[0] = dataclasses.replace(first, meta={
            "sm": dataclasses.replace(sm, cycles=sm.cycles + 1)})
    else:                                          # single warps
        raw[0] = dataclasses.replace(first, trace=first.trace[:-1])
    return raw


@pytest.mark.parametrize("cell", CELLS)
def test_one_corrupted_result_is_not_correct(tiny, capsys, monkeypatch,
                                             cell):
    from bench.generator import Mix
    call = Mix.call
    monkeypatch.setattr(Mix, "call", lambda self, unit: _corrupt(
        call(self, unit)))
    result = _run(tiny, capsys, cell)
    assert result["correct"] is False
    assert sum(c["value"] for c in result["checks"].values()) >= 1


def _lane_fault(kind):
    """A wrapper of the lane-step executable that plants ``kind``."""
    import jax

    from repro.core.hanoi import init_state
    from repro.engine import adapters

    original = adapters._compiled_batch_exec

    def compiled_batch_exec(cfg, majority_first, batch, pad_len):
        compiled, compile_s = original(cfg, majority_first, batch, pad_len)

        def run(progs, skips, regs, mems, lanes):
            if kind == "state_unchanged":
                return jax.vmap(lambda p, r, m, ln: init_state(
                    p.shape[0], cfg, init_regs=r, init_mem=m,
                    lane_ids=ln))(progs, regs, mems, lanes)
            states = compiled(progs, skips, regs, mems, lanes)
            if kind == "half_batch":
                # every other row is left out and given its neighbour's
                # result (a padded batch holds its real rows first)
                return jax.tree_util.tree_map(
                    lambda x: x.at[1::2].set(x[0::2][:batch // 2]), states)
            return states._replace(regs=states.regs.at[0, 0, 0].add(1))
        return run, compile_s
    return compiled_batch_exec


def _scheduler_fault():
    from repro.engine.mechanisms import sm_jax
    original = sm_jax._compiled_grid_scheduler

    def compiled_grid_scheduler(*args):
        compiled, compile_s = original(*args)

        def run(*operands):
            out = list(compiled(*operands))
            out[4] = out[4].at[0].add(1)           # cycles of cell 0
            return tuple(out)
        return run, compile_s
    return compiled_grid_scheduler


FAULTS = [(fault, cell) for fault in ("state_unchanged", "half_batch",
                                      "answer_altered")
          for cell in ("t-suite", "t-distinct", "t-replicated")] + [
    ("scheduler_altered", "t-distinct"), ("scheduler_altered",
                                          "t-replicated")]


@pytest.mark.parametrize("fault,cell", FAULTS)
def test_a_fault_underneath_the_timed_path_is_not_correct(
        tiny, capsys, monkeypatch, fault, cell):
    from repro.engine import adapters
    from repro.engine.mechanisms import sm_jax
    # both device mechanisms reach the lane step through adapters
    for module, seam in ((adapters, "_compiled_batch_exec"),
                         (sm_jax, "_compiled_grid_scheduler")):
        if not hasattr(module, seam):
            pytest.skip(f"the timed path has no {seam} to plant a fault in")
    if fault == "scheduler_altered":
        monkeypatch.setattr(sm_jax, "_compiled_grid_scheduler",
                            _scheduler_fault())
    else:
        monkeypatch.setattr(adapters, "_compiled_batch_exec",
                            _lane_fault(fault))
    result = _run(tiny, capsys, cell, seconds="0.1")
    assert result["correct"] is False, (fault, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_reads_not_correct(tiny, capsys, cell):
    sound = bench_control.readings(cell, [SEED], 1, False, root=tiny)
    ctrl = bench_control.readings(cell, [SEED], 1, True, root=tiny)
    assert sound["readings"][str(SEED)]["warps_differing"] == 0
    assert ctrl["readings"][str(SEED)]["warps_differing"] > 0


def _digest(root) -> dict:
    """Every file of the benchmark under ``root``, by its sha256."""
    out = {}
    for dirpath, _, files in (*os.walk(os.path.join(root, "bench")),
                              *os.walk(os.path.join(root, "tests"))):
        for name in files:
            if "__pycache__" in dirpath:
                continue
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


def _named(entries, name) -> dict:
    return next(e for e in entries if e["name"] == name)


def test_a_new_config_mix_and_metric_are_new_files_only(tiny, tmp_path,
                                                        capsys):
    root = str(tmp_path / "copy")
    shutil.copytree(tiny, root)
    before = _digest(root)
    bench = os.path.join(root, "bench")
    cfg = harness.load_json(os.path.join(bench, "configs", "tiny-suite.json"))
    cfg.update(name="dummy-cfg", executions=[["GAUS", 3], ["DIAMOND", 1]])
    with open(os.path.join(bench, "configs", "dummy-cfg.json"), "w") as f:
        json.dump(cfg, f)
    mix = harness.load_json(os.path.join(bench, "traffic",
                                         "suite-round.json"))
    with open(os.path.join(bench, "traffic", "dummy-mix.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "dummy_units_per_s.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return len(ctx.outcomes) / ctx.window_s\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["configs"].append(dict(_named(manifest["configs"],
                                           "tiny-suite"), name="dummy-cfg",
                                    file="bench/configs/dummy-cfg.json"))
    manifest["workloads"].append(dict(_named(manifest["workloads"],
                                             "t-suite"),
                                      name="dummy-cell", config="dummy-cfg",
                                      traffic="dummy-mix"))
    manifest["end_to_end"].append({
        "name": "dummy_units_per_s", "unit": "units/s", "better": "higher",
        "bound": 0.05, "source": "host_clock", "workloads": ["dummy-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    result = _run(root, capsys, "dummy-cell")
    assert result["correct"] is True
    assert set(result["metrics"]) == {"setup_s", "warp_instr_per_s",
                                      "dummy_units_per_s"}
    assert result["metrics"]["dummy_units_per_s"]["unit"] == "units/s"


#: a driver no mix of the benchmark uses: its own memory draw, another entry
#: of the program (one ``Simulator.run`` per warp, numpy Hanoi) and an open
#: window that offers units at a fixed interval and records their latency
PACED_DRIVER = '''
"""Driver: each warp its own Simulator.run, units offered at a fixed
interval."""
import time

import numpy as np

from bench.generator import Unit, WarpInput


def unit(mix, seed, k):
    rng = mix.rng(seed, k)
    cells = []
    for name, count in mix.config["executions"]:
        for _ in range(int(count)):
            mem = mix.mem(name, rng)
            cells.append([WarpInput(name, None if mem is None
                                    else np.sort(mem), None)])
    out = Unit(k, cells, grid=False)
    out.requests = [mix.request(c[0], skips=True, name=f"u{k}/x{i}")
                    for i, c in enumerate(cells)]
    return out


def call(mix, unit):
    from repro.engine import Simulator
    sim = Simulator(mix.mix["mechanism"])
    return [sim.run(r) for r in unit.requests]


def account(mix, unit, raw):
    return mix.warps_outcome(unit, raw)


def window(mix, seed, seconds, run_unit):
    interval = float(mix.mix["interval_s"])
    outcomes, k, start = [], 1, time.perf_counter()
    while time.perf_counter() - start < seconds:
        due = start + (k - 1) * interval
        time.sleep(max(0.0, due - time.perf_counter()))
        outcome = run_unit(mix, seed, k)
        outcome.latency_s = time.perf_counter() - due
        outcomes.append(outcome)
        k += 1
    return outcomes, time.perf_counter() - start, len(outcomes), 0
'''


def test_a_mix_with_a_new_driver_is_new_files_only(tiny, tmp_path, capsys):
    root = str(tmp_path / "copy")
    shutil.copytree(tiny, root)
    before = _digest(root)
    bench = os.path.join(root, "bench")
    with open(os.path.join(bench, "entries", "paced_single.py"), "w") as f:
        f.write(PACED_DRIVER)
    with open(os.path.join(bench, "traffic", "paced-mix.json"), "w") as f:
        json.dump({"entry": "paced_single", "mechanism": "hanoi",
                   "interval_s": 0.05, "check_units": 2}, f)
    with open(os.path.join(bench, "metrics", "unit_latency_max_s.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    return max(o.latency_s for o in ctx.outcomes)\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    manifest["workloads"].append(dict(_named(manifest["workloads"],
                                             "t-suite"),
                                      name="paced-cell", traffic="paced-mix"))
    manifest["end_to_end"].append({
        "name": "unit_latency_max_s", "unit": "s", "better": "lower",
        "bound": 0.25, "source": "host_clock", "workloads": ["paced-cell"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f)
    after = _digest(root)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    result = _run(root, capsys, "paced-cell")
    assert result["correct"] is True, result["checks"]
    assert result["attempted"] >= 2
    assert result["metrics"]["unit_latency_max_s"]["value"] > 0


#: a driver with its own open window, as a served cell has: a unit is the
#: configuration's executions up to its own ``max_batch``, offered at the
#: mix's fixed interval; each outcome carries the unit's fill, a counter
#: the cell's own per-layer metric reads
FILL_DRIVER = '''
"""Driver: up to max_batch warps a unit in one Simulator.run_batch call,
units offered at a fixed interval."""
import time

from bench.generator import Unit


def unit(mix, seed, k):
    cells = mix.executions(mix.rng(seed, k))
    cells = cells[:int(mix.config["service"]["max_batch"])]
    out = Unit(k, cells, grid=False)
    out.requests = [mix.request(c[0], skips=True, name=f"u{k}/x{i}")
                    for i, c in enumerate(cells)]
    return out


def call(mix, unit):
    from repro.engine import Simulator
    return Simulator(mix.mix["mechanism"]).run_batch(unit.requests)


def account(mix, unit, raw):
    outcome = mix.warps_outcome(unit, raw)
    outcome.fill = len(unit.requests)
    return outcome


def window(mix, seed, seconds, run_unit):
    interval = float(mix.mix["interval_s"])
    outcomes, k, start = [], 1, time.perf_counter()
    while time.perf_counter() - start < seconds:
        time.sleep(max(0.0, start + (k - 1) * interval
                       - time.perf_counter()))
        outcomes.append(run_unit(mix, seed, k))
        k += 1
    return outcomes, time.perf_counter() - start, len(outcomes), 0
'''


def test_a_cell_with_its_own_per_layer_metric_is_new_files_only(
        tmp_path, capsys, monkeypatch):
    # a copy of the real benchmark, not of the tiny root: the new cell, its
    # configuration (with a key no other configuration has), its traffic
    # and driver, a per-layer metric that lists only it, and its tiny file
    real = str(tmp_path / "real")
    for part in ("bench", os.path.join("tests", "bench", "tiny")):
        shutil.copytree(os.path.join(ROOT, part), os.path.join(real, part),
                        ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), real)
    before = _digest(real)
    bench = os.path.join(real, "bench")
    manifest = harness.load_manifest(real)
    suite = _named(manifest["configs"], "paper-suite32")
    cfg = harness.load_json(os.path.join(real, suite["file"]))
    cfg.update(name="served-suite", service={"max_batch": 64})
    files = {
        "bench/configs/served-suite.json": cfg,
        "bench/traffic/paced-fill.json": {
            "entry": "paced_fill", "mechanism": "hanoi", "interval_s": 0.5,
            "check_units": 2},
        "tests/bench/tiny/t-served.json": {
            "of": "served-paced",
            "config": {"name": "tiny-served", "machine": {"max_steps": 64},
                       "executions": [["HOTS", 2], ["RBFS", 1], ["LUD", 1],
                                      ["FIG6", 1]],
                       "service": {"max_batch": 4}},
            "traffic": {"name": "tiny-paced-fill", "interval_s": 0.05}}}
    for path, data in files.items():
        with open(os.path.join(real, path), "w") as f:
            json.dump(data, f)
    with open(os.path.join(bench, "entries", "paced_fill.py"), "w") as f:
        f.write(FILL_DRIVER)
    with open(os.path.join(bench, "metrics", "served.mean_fill.py"),
              "w") as f:
        f.write("def read(ctx):\n"
                "    fills = [o.fill for o in ctx.outcomes\n"
                "             if hasattr(o, 'fill')]\n"
                "    return sum(fills) / len(fills) if fills else None\n")
    manifest["configs"].append(dict(suite, name="served-suite",
                                    file="bench/configs/served-suite.json"))
    manifest["workloads"].append({
        "name": "served-paced", "config": "served-suite",
        "traffic": "paced-fill", "chips": 1,
        "why": "the suite offered at a fixed interval, in units of at most "
               "max_batch warps"})
    manifest["per_layer"].append({
        "name": "served.mean_fill", "unit": "warps", "better": "higher",
        "source": "program_counter", "layer": "served batches",
        "moves": "warp_instr_per_s", "workloads": ["served-paced"]})
    with open(os.path.join(real, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    after = _digest(real)
    assert all(after[k] == v for k, v in before.items())   # nothing edited
    assert set(after) - set(before) == {
        os.path.join(*p.split("/")) for p in (
            *files, "bench/entries/paced_fill.py",
            "bench/metrics/served.mean_fill.py")}

    _on_the_cpu(monkeypatch)
    root = _tiny_root(real, tmp_path / "tiny")
    cells = [w["name"] for w in harness.load_manifest(root)["workloads"]]
    assert sorted(cells) == sorted((*CELLS, "t-served"))
    for cell in cells:
        result = _run(root, capsys, cell)
        assert result["correct"] is True, (cell, result["checks"])
    _committed_trace(monkeypatch)
    for cell in cells:
        result = _run(root, capsys, cell, trace="1")
        assert result["correct"] is True, (cell, result["checks"])
        if cell == "t-served":
            assert set(result["metrics"]) == {"served.mean_fill"}
            assert result["metrics"]["served.mean_fill"]["value"] == 4
        else:
            assert "served.mean_fill" not in result["metrics"]


def _run_py(cwd, env_extra=None):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **(env_extra or {}))
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "suite32-hanoi",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def _no_result(proc) -> bool:
    lines = proc.stdout.strip().splitlines()
    return not lines or not lines[-1].startswith("{")


def test_run_exits_nonzero_without_a_tpu(tmp_path):
    proc = _run_py(ROOT, {"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert proc.returncode != 0 and _no_result(proc)
    assert "TPU" in proc.stderr


def test_run_exits_nonzero_with_only_the_benchmark(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    for p in ("bench", os.path.join("tests", "bench")):
        shutil.copytree(os.path.join(ROOT, p), tmp_path / p,
                        ignore=shutil.ignore_patterns("__pycache__"))
    proc = _run_py(str(tmp_path))
    assert proc.returncode != 0 and _no_result(proc)
