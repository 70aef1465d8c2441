"""Tiny runs of every traffic mix through the benchmark harness, on the CPU.

The harness's look for a TPU is steered here, in the test: everything else
of a run — the manifest, the files found by name, the generator, the timed
entry, the window, the reference check and the last line — runs as on the
chip, at tiny sizes (two-lane-deep grids, 64 steps of fuel) in a temporary
copy of the benchmark.  The checks:

- each mix prints the last-line schema with ``correct`` true, and a traced
  run reports every per-layer metric its cell lists;
- one corrupted result makes ``correct`` false;
- faults planted underneath the timed path (a lane step that returns its
  state unchanged, half of the batch left out, an answer altered where it
  is produced, a scheduler whose output is altered) make ``correct`` false;
- the control (the program's majority_first=False path) reads not correct;
- a new configuration, traffic mix and metric run from new files and
  manifest entries alone;
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
CELLS = ("t-suite", "t-distinct", "t-replicated")
SEED = 3000000019          # seeds are any whole number past 32 bits


def _tiny_root(base) -> str:
    """A copy of the benchmark whose manifest lists tiny cells of the same
    three mixes."""
    root = str(base)
    shutil.copytree(os.path.join(ROOT, "bench"), os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    cfg_dir = os.path.join(root, "bench", "configs")

    def derive(src, name, **changes):
        with open(os.path.join(cfg_dir, f"{src}.json")) as f:
            cfg = json.load(f)
        cfg["name"] = name
        cfg["machine"]["max_steps"] = 64
        cfg.update(changes)
        with open(os.path.join(cfg_dir, f"{name}.json"), "w") as f:
            json.dump(cfg, f)

    derive("paper-suite32", "tiny-suite",
           executions=[["HOTS", 2], ["RBFS", 1], ["BFSD", 1], ["LUD", 1],
                       ["SLOCK", 1], ["FIG6", 1]])
    derive("tu102-grid", "tiny-grid", n_sms=3, warps_per_sm=2)
    with open(os.path.join(root, "bench", "traffic",
                           "distinct-warps.json")) as f:
        mix = json.load(f)
    mix.update(sms=2, sm_programs=["LUD", "HOTS"])
    with open(os.path.join(root, "bench", "traffic", "tiny-distinct.json"),
              "w") as f:
        json.dump(mix, f)
    rename = {"suite32-hanoi": "t-suite", "grid-distinct": "t-distinct",
              "grid-replicated": "t-replicated"}
    manifest["configs"] = [
        dict(manifest["configs"][0], name="tiny-suite",
             file="bench/configs/tiny-suite.json"),
        dict(manifest["configs"][1], name="tiny-grid",
             file="bench/configs/tiny-grid.json")]
    manifest["workloads"] = [
        dict(manifest["workloads"][0], name="t-suite", config="tiny-suite"),
        dict(manifest["workloads"][1], name="t-distinct", config="tiny-grid",
             traffic="tiny-distinct"),
        dict(manifest["workloads"][2], name="t-replicated",
             config="tiny-grid")]
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        if "workloads" in m:
            m["workloads"] = [rename[w] for w in m["workloads"]]
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(manifest, f, indent=1)
    return root


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(harness, "require_device", lambda chips: {
            "platform": "cpu", "kind": "cpu", "count": 1})
        import repro.engine
        mp.setattr(repro.engine, "install_jax_cache", lambda: None)
        yield _tiny_root(tmp_path_factory.mktemp("bench"))


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


@pytest.mark.parametrize("cell", CELLS)
def test_a_traced_run_reports_every_per_layer_metric_of_its_cell(
        tiny, capsys, monkeypatch, cell):
    # the profiler's trace is the committed chip trace, and the peak a
    # number: what is checked is that each reader gets what it reads
    from bench import trace
    fixture = os.path.join(ROOT, "tests", "bench", "trace_tiny.xplane.pb")
    monkeypatch.setattr(harness, "profiled",
                        lambda directory: contextlib.nullcontext())
    monkeypatch.setattr(harness, "read_trace", lambda directory, chips:
                        trace.summarize(fixture, chips=chips))
    monkeypatch.setattr(harness, "memory_peak_bytes", lambda chips: 4321)
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
          for cell in CELLS] + [("scheduler_altered", "t-distinct"),
                                ("scheduler_altered", "t-replicated")]


@pytest.mark.parametrize("fault,cell", FAULTS)
def test_a_fault_underneath_the_timed_path_is_not_correct(
        tiny, capsys, monkeypatch, fault, cell):
    from repro.engine import adapters
    from repro.engine.mechanisms import sm_jax
    for module, seam in ((adapters, "_compiled_batch_exec"),
                         (sm_jax, "_compiled_batch_exec"),
                         (sm_jax, "_compiled_grid_scheduler")):
        if not hasattr(module, seam):
            pytest.skip(f"the timed path has no {seam} to plant a fault in")
    if fault == "scheduler_altered":
        monkeypatch.setattr(sm_jax, "_compiled_grid_scheduler",
                            _scheduler_fault())
    else:
        wrapper = _lane_fault(fault)
        monkeypatch.setattr(adapters, "_compiled_batch_exec", wrapper)
        monkeypatch.setattr(sm_jax, "_compiled_batch_exec", wrapper)
    result = _run(tiny, capsys, cell, seconds="0.1")
    assert result["correct"] is False, (fault, cell)


@pytest.mark.parametrize("cell", CELLS)
def test_the_control_reads_not_correct(tiny, capsys, cell):
    sound = bench_control.readings(cell, [SEED], 1, False, root=tiny)
    ctrl = bench_control.readings(cell, [SEED], 1, True, root=tiny)
    assert sound["readings"][str(SEED)]["warps_differing"] == 0
    assert ctrl["readings"][str(SEED)]["warps_differing"] > 0


def _digest(root) -> dict:
    out = {}
    for dirpath, _, files in os.walk(os.path.join(root, "bench")):
        for name in files:
            if "__pycache__" in dirpath:
                continue
            path = os.path.join(dirpath, name)
            with open(path, "rb") as f:
                out[os.path.relpath(path, root)] = hashlib.sha256(
                    f.read()).hexdigest()
    return out


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
    manifest["configs"].append(dict(manifest["configs"][0], name="dummy-cfg",
                                    file="bench/configs/dummy-cfg.json"))
    manifest["workloads"].append(dict(manifest["workloads"][0],
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
    manifest["workloads"].append(dict(manifest["workloads"][0],
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
