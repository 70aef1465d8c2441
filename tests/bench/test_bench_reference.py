"""The benchmark's plain references agree with the repository's own numpy
interpreter and host SM scheduler, which they were copied from.

The references under ``bench/reference`` import nothing of the program;
these tests tie them to the program's reference implementations while those
exist, on the suite's programs at 32 lanes and on the random structured
programs the property suites use.
"""
from __future__ import annotations

import os

import numpy as np
import pytest

from bench.generator import load_programs
from bench.reference.hanoi_ref import Machine, run_warp
from bench.reference.sm_ref import Latencies, schedule

interp = pytest.importorskip("repro.core.interp")
progen = pytest.importorskip("progen")

PROGRAMS = load_programs("table2-w32-m256")
FIELDS = ("n_threads", "n_regs", "n_preds", "n_bx", "mem_size", "max_steps")


def _machine(cfg) -> Machine:
    return Machine(**{f: getattr(cfg, f) for f in FIELDS})


def _same(want, got, cfg):
    """``want``: the repository's RunResult; ``got``: the reference's."""
    full = cfg.full_mask
    status = ("error" if want.error else "out_of_fuel" if want.fuel_left == 0
              else "ok" if (want.finished & full) == full else "deadlock")
    assert got.status == status
    assert (got.steps, got.fuel_left, got.finished, got.error) == (
        want.steps, want.fuel_left, want.finished, want.error)
    assert got.trace == list(want.trace)
    assert np.array_equal(got.regs, want.regs)
    assert np.array_equal(got.preds, want.preds)
    assert np.array_equal(got.mem, want.mem)


@pytest.mark.parametrize("name", sorted(PROGRAMS))
@pytest.mark.parametrize("majority_first", [True, False])
def test_hanoi_reference_matches_on_the_suite(name, majority_first):
    from repro.core.isa import MachineConfig
    cfg = MachineConfig(n_threads=32)
    rng = np.random.default_rng(7)
    entry = PROGRAMS[name]
    mem = None
    if entry["mem_range"] is not None:
        mem = rng.integers(*entry["mem_range"], size=cfg.mem_size,
                           dtype=np.int32)
    lanes = np.arange(4096, 4096 + 32, dtype=np.int32)
    for lane_ids in (None, lanes):
        want = interp.run_hanoi(entry["words"], cfg, init_mem=mem,
                                lane_ids=lane_ids,
                                majority_first=majority_first)
        got = run_warp(entry["words"], _machine(cfg), mem=mem,
                       lane_ids=lane_ids, majority_first=majority_first)
        _same(want, got, cfg)


@pytest.mark.parametrize("features", [{}, {"sync_features": True},
                                      {"mem_features": True}])
def test_hanoi_reference_matches_on_random_programs(features):
    checked = 0
    for seed in range(12):
        made, cfg = progen.make_program(seed, 8, **features)
        if made is None:
            continue
        prog, mem = made
        want = interp.run_hanoi(prog, cfg, init_mem=mem)
        _same(want, run_warp(prog, _machine(cfg), mem=mem), cfg)
        checked += 1
    assert checked >= 8


def test_hanoi_reference_atomadd_wraps_past_int32():
    """ATOMADDs whose sums cross 2**31 both ways, lanes colliding on two
    words: the copy wraps in int32 as numpy ``hanoi`` and ``hanoi_jax``
    do."""
    from repro.core.asm import assemble
    from repro.core.isa import MachineConfig
    from repro.engine import SimRequest, Simulator
    cfg = MachineConfig(n_threads=32, mem_size=64, max_steps=16)
    top, lane = 2**31 - 1, np.arange(32)
    prog = assemble("    ATOMADD R4, [R0+0], R1\n    EXIT\n")
    regs = np.zeros((32, cfg.n_regs), np.int64)
    regs[:, 0] = lane % 2
    regs[:, 1] = np.where(lane % 2, -top + lane, top - lane)
    regs = regs.astype(np.int32)
    mem = np.arange(64, dtype=np.int32)
    mem[0], mem[1] = top - 5, -top
    got = run_warp(prog, _machine(cfg), regs=regs, mem=mem)
    _same(interp.run_hanoi(prog, cfg, init_regs=regs, init_mem=mem), got,
          cfg)
    jax_got = Simulator("hanoi_jax").run(SimRequest(
        program=prog, cfg=cfg, init_regs=regs, init_mem=mem))
    assert np.array_equal(np.asarray(jax_got.mem), got.mem)
    assert np.array_equal(np.asarray(jax_got.regs), got.regs)
    for word in (0, 1):         # each word's lanes summed, wrapped once
        total = int(mem[word]) + int(regs[word::2, 1].astype(np.int64).sum())
        assert not -2**31 <= total < 2**31
        assert got.mem[word] == (total + 2**31) % 2**32 - 2**31


def test_sm_reference_matches_the_host_scheduler():
    from repro.timing import CycleConfig, schedule_cycle
    from repro.core.isa import MachineConfig
    cfg = MachineConfig(n_threads=32)
    rng = np.random.default_rng(11)
    names = sorted(PROGRAMS)
    lat = Latencies()
    ccfg = CycleConfig(alu_latency=lat.alu, control_latency=lat.control,
                       memory_latency=lat.memory, atomic_latency=lat.atomic,
                       scoreboard=False)
    for trial in range(6):
        n = int(rng.integers(1, 9))
        picks = [names[int(i)] for i in rng.integers(0, len(names), size=n)]
        traces, progs = [], []
        for name in picks:
            entry = PROGRAMS[name]
            mem = None if entry["mem_range"] is None else rng.integers(
                *entry["mem_range"], size=cfg.mem_size, dtype=np.int32)
            traces.append(run_warp(entry["words"], _machine(cfg),
                                   mem=mem).trace)
            progs.append(entry["words"])
        if trial == 0:
            traces.append([])                  # a warp with nothing to issue
            progs.append(progs[0])
        want = schedule_cycle(traces, progs, "greedy_then_oldest", ccfg)
        got = schedule(traces, [p[:, 0] for p in progs], lat)
        assert got.sm_trace == want.order
        assert (got.cycles, got.thread_instructions, got.busy_cycles,
                got.issue_stall_cycles, got.scoreboard_stall_cycles,
                got.memory_stall_cycles) == (
            want.cycles, want.thread_instructions, want.busy_cycles,
            want.issue_stall_cycles, want.scoreboard_stall_cycles,
            want.memory_stall_cycles)


def test_program_data_is_whole():
    for name, entry in PROGRAMS.items():
        words = entry["words"]
        assert words.ndim == 2 and words.shape[1] == 8, name
        assert 1 <= words.shape[0] <= 32, name       # one padding class
        assert os.path.basename(name) == name
