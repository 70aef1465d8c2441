"""The one traffic generator: builds each unit of work from the seed and
drives the entry the window times.

A traffic mix is a data file ``bench/traffic/<name>.json`` that this module
reads.  Its ``entry`` names a driver, ``bench/entries/<entry>.py``, found by
that name like a metric's reader: it builds one unit's inputs from the seed,
calls the program's entry with them, and accounts for what came back.  A
later PR brings traffic that no driver here knows as a new driver file and a
new data file, and edits neither this module nor the harness.

A driver module holds

- ``unit(mix, seed, k) -> Unit``: unit ``k``'s inputs and requests;
- ``call(mix, unit)``: the timed entry, called with ``unit.requests``; for
  a unit of single warps it returns their results in cell order;
- ``account(mix, unit, raw) -> Outcome``: what the window needs of it;
- for grids, ``sm_results(mix, raw)``: the per-SM results, in cell order;
- optionally ``cell_differs(mix, raw, c, want) -> bool``: what else of SM
  cell ``c`` the reference check compares (see :mod:`bench.check`);
- optionally ``window(mix, seed, seconds, run_unit)``: the measured window,
  ``(outcomes, window_s, attempted, failed)``, where units do not simply run
  back to back (the harness's closed loop is the default).

The keys the shared helpers below read, for the drivers that use them:

- ``sms`` (a count, or ``"all"`` for the configuration's ``n_sms``) and
  ``sm_programs`` (SM ``c`` runs ``sm_programs[c % len]``): for grids.
- ``memory``: ``per_warp`` (every warp its own image) or ``per_sm`` (one
  image per SM); programs without a value range keep zeroed memory.
- ``lane_ids``: ``lane`` (0..31) or ``global`` (global thread ids).
- ``check_units``: how many of the window's units the reference checks,
  drawn from the seed (``"all"`` for every one).

Unit ``k`` of seed ``s`` draws its memory images from
``numpy.random.default_rng([s, k])``, so the same seed gives the same
inputs.  Unit 0 is the warm-up; the window runs units 1, 2, ...
"""
from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass
class WarpInput:
    program: str                 # name in the configuration's program file
    mem: "np.ndarray | None"
    lane_ids: "np.ndarray | None"


@dataclass
class Unit:
    """One unit of work: its cells of warps and the requests sent."""

    index: int
    cells: list                  # [[WarpInput]]
    grid: bool                   # cells are SMs (else one warp per cell)
    requests: object = None      # what the entry is called with


@dataclass
class Outcome:
    """What the window needs of one finished unit."""

    unit: Unit
    raw: object                  # what the entry returned
    warp_instr: int              # simulated warp instructions (issue slots)
    useful_warp_steps: int       # steps of the executed lane-step rows
    issued_slots: int            # SM issue slots (0 without a scheduler)
    device_call_s: float         # the program's own device-call wall time


def load_programs(name: str, root: str = HERE) -> dict:
    with open(os.path.join(root, "programs", f"{name}.json")) as f:
        table = json.load(f)
    for entry in table.values():
        entry["words"] = np.asarray(entry["words"], np.int32)
    return table


def seed_words(seed: int) -> int:
    """A seed as a non-negative integer ``numpy`` accepts, of any size."""
    return int(seed) & (2 ** 64 - 1)


def warp_key(warp: WarpInput) -> tuple:
    """A warp's inputs as a key: warps with equal keys compute the same
    result (the machine and the mix's request options are shared)."""
    return (warp.program,
            None if warp.mem is None else warp.mem.tobytes(),
            None if warp.lane_ids is None else warp.lane_ids.tobytes())


def load_driver(root: str, entry: str):
    path = os.path.join(root, "entries", f"{entry}.py")
    if not os.path.exists(path):
        raise ValueError(f"unknown entry {entry!r}: no {path}")
    spec = importlib.util.spec_from_file_location(
        "bench_entry_" + entry.replace(".", "_").replace("-", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


class Mix:
    """One configuration under one traffic mix."""

    def __init__(self, config: dict, mix: dict, root: str = HERE, *,
                 majority_first: "bool | None" = None):
        self.config = config
        self.mix = mix
        self.programs = load_programs(config["programs"], root)
        self.machine = dict(config["machine"])
        stated = bool(config["guarantees"]["majority_first"])
        self.majority_first = stated if majority_first is None \
            else bool(majority_first)
        self.entry = mix["entry"]
        self.driver = load_driver(root, self.entry)
        self.window = getattr(self.driver, "window", None)

    # -- what the driver does ---------------------------------------------

    def unit(self, seed: int, k: int) -> Unit:
        return self.driver.unit(self, seed, k)

    def call(self, unit: Unit):
        return self.driver.call(self, unit)

    def account(self, unit: Unit, raw) -> Outcome:
        return self.driver.account(self, unit, raw)

    def sm_results(self, raw) -> list:
        """The per-SM results of a grid unit."""
        return list(self.driver.sm_results(self, raw))

    def cell_differs(self, raw, c: int, want) -> bool:
        more = getattr(self.driver, "cell_differs", None)
        return False if more is None else bool(more(self, raw, c, want))

    # -- shared helpers for drivers ------------------------------------------

    @staticmethod
    def rng(seed: int, k: int):
        return np.random.default_rng([seed_words(seed), int(k)])

    def mem(self, name: str, rng) -> "np.ndarray | None":
        """A fresh memory image for program ``name``, uniform in its value
        range; ``None`` (zeroed memory) for a program without one."""
        rng_range = self.programs[name]["mem_range"]
        if rng_range is None:
            return None
        lo, hi = rng_range
        return rng.integers(lo, hi, size=self.machine["mem_size"],
                            dtype=np.int32)

    def executions(self, rng) -> list:
        """One single-warp cell per execution the configuration lists."""
        return [[WarpInput(name, self.mem(name, rng), None)]
                for name, count in self.config["executions"]
                for _ in range(int(count))]

    def grid_cells(self, rng) -> list:
        """The SM cells of one grid, as the mix's ``sms``, ``sm_programs``,
        ``memory`` and ``lane_ids`` describe them."""
        mix, W = self.mix, self.machine["n_threads"]
        sms = mix["sms"]
        n_sms = int(self.config["n_sms"]) if sms == "all" else int(sms)
        n_warps = int(self.config["warps_per_sm"])
        progs = mix["sm_programs"]
        cells = []
        for c in range(n_sms):
            name = progs[c % len(progs)]
            shared = self.mem(name, rng) if mix["memory"] == "per_sm" \
                else None
            cell = []
            for w in range(n_warps):
                mem = shared if mix["memory"] == "per_sm" \
                    else self.mem(name, rng)
                lanes = None
                if mix["lane_ids"] == "global":
                    tid0 = (c * n_warps + w) * W
                    lanes = np.arange(tid0, tid0 + W, dtype=np.int32)
                cell.append(WarpInput(name, mem, lanes))
            cells.append(cell)
        return cells

    def request(self, warp: WarpInput, *, skips: bool, name: str,
                meta: "dict | None" = None):
        """The program's request for one warp."""
        from repro.core.isa import MachineConfig
        from repro.engine import SimRequest
        prog = self.programs[warp.program]
        return SimRequest(
            program=prog["words"], cfg=MachineConfig(**self.machine),
            init_mem=warp.mem, lane_ids=warp.lane_ids, record_trace=True,
            majority_first=self.majority_first,
            bsync_skip_pcs=tuple(prog["skip_bsync_pcs"]) if skips else (),
            name=name, meta=meta or {})

    def warps_outcome(self, unit: Unit, raw) -> Outcome:
        """The outcome of a unit of single warps: every warp's steps are
        warp instructions and useful lane-step steps."""
        steps = sum(int(r.steps) for r in raw)
        return Outcome(unit, raw, warp_instr=steps, useful_warp_steps=steps,
                       issued_slots=0,
                       device_call_s=sum(r.wall_time_s for r in raw))

    def grid_outcome(self, unit: Unit, raw) -> Outcome:
        """The outcome of a grid: its SMs' issue slots are its warp
        instructions; the lane step's useful steps count each distinct warp
        input once, as the lane step runs identical warps once."""
        sms = self.sm_results(raw)
        slots = sum(int(sm.steps) for sm in sms)
        rows: dict = {}
        for cell, sm in zip(unit.cells, sms):
            for warp, got in zip(cell, sm.warps):
                rows.setdefault(warp_key(warp), int(got.steps))
        return Outcome(unit, raw, warp_instr=slots,
                       useful_warp_steps=sum(rows.values()),
                       issued_slots=slots,
                       device_call_s=sum(sm.wall_time_s for sm in sms))
