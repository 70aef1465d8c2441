"""Differential conformance: every registered mechanism, one contract.

The paper's correctness criterion (SS VIII): a control-flow-management
mechanism may schedule lanes however it likes, but on race-free programs
the final architectural state must be exactly what the pre-Volta baseline
computes.  This suite enforces that *differentially* across the whole
registry (``iter_mechanisms()``), so any future ``@register_mechanism``
plugin — DARM-style melding, decoupled control flow, ... — is held to the
bar automatically:

* over the shared benchmark suite (race-free members), at 8 lanes and at
  the paper's 32 (where a full mask is ``0xFFFFFFFF``, ``-1`` as int32),
  and over random ``tests/progen.py`` programs (8 lanes), final
  ``regs`` / ``mem`` / ``finished``
  must agree with ``simt_stack`` wherever BOTH mechanisms report
  ``SimStatus.OK``.  Register comparison excludes ``BMOV B->R`` spill
  destinations: those hold microarchitectural reconvergence masks on the
  stack machines and are (correctly) never written by stackless or
  NOP-ing mechanisms;
* on synchronization-heavy programs (``sync_features=True``: spinlocks,
  WARPSYNC joins, BREAK loops with nested Whiles) the pre-Volta baseline
  deadlocks — there the stack mechanisms cross-check each other and the
  per-thread-PC scheduler, with ``hanoi`` as the reference;
* progress properties: ``volta_itps`` must terminate (never a structural
  ``DEADLOCK``) on every generated program that ``turing_oracle``
  finishes — the Volta forward-progress guarantee — including the
  spinlock programs that hang ``simt_stack`` and YIELD-less Hanoi.

The JAX engine participates through the suite half only: running it over
hundreds of random programs re-JITs per shape bucket for minutes, and its
bit-exactness against ``hanoi`` is already property-tested in
``test_hanoi_jax.py``.
"""
import numpy as np
import pytest

from repro.core import MachineConfig
from repro.core.isa import F_DST, F_OP, Op
from repro.core.programs import (make_suite, spinlock_no_yield_program,
                                 spinlock_program)
from repro.engine import SimStatus, Simulator, as_request, iter_mechanisms
from tests.progen import CHECK_REGS, COUNTER_CELL, W, make_program

CFG = MachineConfig(n_threads=8, mem_size=64, max_steps=20_000)
SUITE = make_suite(CFG, datasets=1)
CFG32 = MachineConfig(n_threads=32, mem_size=256, max_steps=20_000)
# the suite half runs at both widths; 32 is the paper's warp width and
# that of every benchmark cell
WIDTHS = {"w8": (CFG, SUITE), "w32": (CFG32, make_suite(CFG32, datasets=1))}
SIM = Simulator("simt_stack")

ALL_MECHANISMS = [m.name for m in iter_mechanisms()]
NUMPY_MECHANISMS = [m.name for m in iter_mechanisms() if m.backend != "jax"]

PROGEN_SEEDS = list(range(10))
SYNC_SEEDS = list(range(12))
TERMINATION_SEEDS = list(range(30))


def _bmov_spill_regs(program) -> set[int]:
    """Register columns that receive Bx spills (mechanism-internal state)."""
    prog = np.asarray(program)
    return {int(prog[pc, F_DST]) for pc in range(prog.shape[0])
            if int(prog[pc, F_OP]) == Op.BMOV_B2R}


def _assert_state_agrees(res, base, *, check_regs=None, program=None,
                         who=""):
    assert res.finished == base.finished, f"{who}: finished masks differ"
    np.testing.assert_array_equal(res.mem, base.mem,
                                  err_msg=f"{who}: memory differs")
    if check_regs is None:
        ncols = res.regs.shape[1]
        check_regs = [r for r in range(ncols)
                      if r not in _bmov_spill_regs(program)]
    np.testing.assert_array_equal(
        res.regs[:, check_regs], base.regs[:, check_regs],
        err_msg=f"{who}: architectural registers differ")


# ---------------------------------------------------------------------------
# shared benchmark suite: everyone vs the pre-Volta baseline
# ---------------------------------------------------------------------------

# at 32 lanes the baseline is not compared with itself
@pytest.mark.parametrize("cfg, bench, mech", [
    pytest.param(cfg, bench, mech, id=f"{w}-{bench.name}-{mech}")
    for w, (cfg, suite) in WIDTHS.items()
    for bench in suite if bench.race_free
    for mech in ALL_MECHANISMS
    if not (w == "w32" and mech == "simt_stack")])
def test_suite_conformance(cfg, bench, mech):
    base = SIM.run(bench, cfg, mechanism="simt_stack")
    res = SIM.run(bench, cfg, mechanism=mech)
    if not (base.ok and res.ok):
        pytest.skip(f"not comparable: {mech}={res.status.value} "
                    f"baseline={base.status.value}")
    _assert_state_agrees(res, base, program=bench.program,
                         who=f"{bench.name}/{mech}")


@pytest.mark.parametrize("mech", ALL_MECHANISMS)
@pytest.mark.parametrize("width", list(WIDTHS))
def test_suite_mechanisms_complete_race_free_programs(width, mech):
    """No registered mechanism may be vacuously conformant: every one must
    actually finish the deadlock-free structured suite."""
    cfg, suite = WIDTHS[width]
    for bench in suite:
        if not bench.race_free:
            continue
        res = SIM.run(bench, cfg, mechanism=mech)
        assert res.ok, f"{mech} failed {bench.name}: {res.status.value}"


# ---------------------------------------------------------------------------
# random structured programs (historical distribution)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mech", NUMPY_MECHANISMS)
@pytest.mark.parametrize("seed", PROGEN_SEEDS)
def test_progen_conformance(seed, mech):
    built, cfg = make_program(seed, 8)
    if built is None:
        pytest.skip("rejected program shape")
    prog, mem = built
    base = SIM.run(prog, cfg, mechanism="simt_stack", init_mem=mem)
    res = SIM.run(prog, cfg, mechanism=mech, init_mem=mem)
    assert base.ok, "historical progen programs are deadlock-free pre-Volta"
    if not res.ok:
        pytest.skip(f"not comparable: {mech}={res.status.value}")
    _assert_state_agrees(res, base, check_regs=CHECK_REGS,
                         who=f"seed {seed}/{mech}")


# ---------------------------------------------------------------------------
# synchronization-heavy programs: spinlocks, WARPSYNC joins, nested BREAKs
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mech", NUMPY_MECHANISMS)
@pytest.mark.parametrize("seed", SYNC_SEEDS)
def test_sync_progen_conformance(seed, mech):
    """On lock-bearing programs simt_stack hangs by design, so ``hanoi``
    (the paper's correct mechanism) anchors the differential check; the
    "agree wherever both OK" contract is unchanged."""
    built, cfg = make_program(seed, 8, sync_features=True)
    if built is None:
        pytest.skip("rejected program shape")
    prog, mem = built
    base = SIM.run(prog, cfg, mechanism="hanoi", init_mem=mem)
    res = SIM.run(prog, cfg, mechanism=mech, init_mem=mem)
    if not (base.ok and res.ok):
        pytest.skip(f"not comparable: {mech}={res.status.value} "
                    f"hanoi={base.status.value}")
    _assert_state_agrees(res, base, check_regs=CHECK_REGS,
                         who=f"sync seed {seed}/{mech}")
    assert int(res.mem[COUNTER_CELL]) == W, \
        f"{mech}: spinlock mutual exclusion violated"


def test_sync_programs_exercise_the_prevolta_gap():
    """Sanity for the distribution itself: the sync-feature programs must
    actually hit the paper's gap — pre-Volta hangs, Hanoi completes."""
    prevolta_hangs = hanoi_completes = 0
    for seed in SYNC_SEEDS:
        built, cfg = make_program(seed, 8, sync_features=True)
        if built is None:
            continue
        prog, mem = built
        if not SIM.run(prog, cfg, init_mem=mem).ok:
            prevolta_hangs += 1
        if SIM.run(prog, cfg, mechanism="hanoi", init_mem=mem).ok:
            hanoi_completes += 1
    assert prevolta_hangs >= len(SYNC_SEEDS) // 2
    assert hanoi_completes >= len(SYNC_SEEDS) // 2


# ---------------------------------------------------------------------------
# forward-progress properties of the per-thread-PC scheduler
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("seed", TERMINATION_SEEDS)
def test_volta_terminates_where_oracle_finishes(seed):
    """The Volta progress guarantee as a property: on every generated
    synchronization-heavy program that ``turing_oracle`` finishes,
    ``volta_itps`` must never report a structural DEADLOCK (and, fuel
    being equal, must in fact finish)."""
    built, cfg = make_program(seed, 8, sync_features=True)
    if built is None:
        pytest.skip("rejected program shape")
    prog, mem = built
    oracle = SIM.run(prog, cfg, mechanism="turing_oracle", init_mem=mem)
    if not oracle.ok:
        pytest.skip(f"oracle itself: {oracle.status.value}")
    volta = SIM.run(prog, cfg, mechanism="volta_itps", init_mem=mem)
    assert volta.status is not SimStatus.DEADLOCK
    assert volta.ok, f"volta_itps: {volta.status.value}"


@pytest.mark.parametrize("prog_fn, name", [
    (spinlock_program, "spinlock"),
    (spinlock_no_yield_program, "spinlock_no_yield"),
])
def test_volta_completes_spinlocks_where_stack_machines_hang(prog_fn, name):
    """The acceptance scenario: both spinlock variants terminate under
    independent thread scheduling; pre-Volta hangs on both, and even Hanoi
    hangs without YIELD (paper SS V-G) — volta_itps needs neither YIELD
    nor a reconvergence stack, only the progress guarantee."""
    prog = prog_fn()
    volta = SIM.run(prog, CFG, mechanism="volta_itps")
    assert volta.ok and int(volta.mem[1]) == CFG.n_threads
    assert not SIM.run(prog, CFG, mechanism="simt_stack").ok
    if name == "spinlock_no_yield":
        assert not SIM.run(prog, CFG, mechanism="hanoi").ok


def test_volta_structural_deadlock_is_flagged_not_burned():
    """A WARPSYNC whose mask can never assemble (half the warp EXITs first)
    is a *structural* deadlock: volta_itps must report DEADLOCK with fuel
    to spare, not spin the budget away."""
    from repro.core.asm import assemble
    full = (1 << CFG.n_threads) - 1
    prog = assemble(f"""
        LANEID R1
        ISETP.GE P0, R1, {CFG.n_threads // 2}
        @P0 EXIT                 ; upper half leaves without syncing
        WARPSYNC {full}          ; waits for lanes that already exited? no:
        EXIT                     ; finished lanes count as arrived
    """)
    r = SIM.run(prog, CFG, mechanism="volta_itps")
    assert r.ok      # exited lanes satisfy the rendezvous

    prog2 = assemble(f"""
        LANEID R1
        ISETP.GE P0, R1, {CFG.n_threads // 2}
        @P0 BRA other
        WARPSYNC {full}          ; lower half parks here...
        EXIT
    other:
        WARPSYNC {full}          ; ...upper half parks THERE: split rendezvous
        EXIT
    """)
    r2 = SIM.run(prog2, CFG, mechanism="volta_itps")
    assert r2.status is SimStatus.DEADLOCK
    assert r2.fuel_left > 0, "structural deadlock must not burn the budget"


def test_volta_divergent_warpsync_masks_union_not_overwrite():
    """Two groups reaching one WARPSYNC pc with different register-operand
    masks (UB on real hardware): the rendezvous must take the UNION of the
    masks, so a later narrow-mask arrival can never spring earlier parked
    lanes out of a rendezvous that never assembled."""
    from repro.core.asm import assemble
    cfg = MachineConfig(n_threads=4, max_steps=512)
    prog = assemble("""
        LANEID R1
        ISETP.EQ P1, R1, 1
        @P1 BRA spin         ; lane 1 never arrives, never exits
        MOV R2, 14           ; lanes 2,3 will demand {1,2,3}
        ISETP.EQ P0, R1, 0
        @P0 MOV R2, 1        ; lane 0 demands only {0}
        @P0 BRA slow         ; lane 0 arrives at the sync second
    sync:
        WARPSYNC R2
        EXIT
    slow:
        NOP
        BRA sync
    spin:
        BRA spin
    """)
    r = SIM.run(prog, cfg, mechanism="volta_itps")
    # lanes 2,3 park demanding lane 1; lane 0's later {0}-mask arrival must
    # NOT release them (or itself) -- nobody may reach EXIT
    assert r.status is SimStatus.OUT_OF_FUEL
    assert r.finished == 0, \
        "a narrow-mask arrival released lanes from an unassembled rendezvous"


# ---------------------------------------------------------------------------
# the acceptance-criteria surface, end to end
# ---------------------------------------------------------------------------

def test_compare_volta_against_oracle_baseline():
    """``Simulator.compare("volta_itps", baseline="turing_oracle")`` over
    (a slice of) the benchmark suite: every row computed, every status OK
    on race-free programs, and the per-thread-PC schedule genuinely
    diverges from the stack schedule."""
    benches = [b for b in SUITE if b.race_free][:6]
    report = SIM.compare("volta_itps", benches, CFG,
                         baseline="turing_oracle", timing=False)
    rows = report.pair("volta_itps", "turing_oracle")
    assert len(rows) == len(benches)
    assert all(r.status_a == "ok" and r.status_b == "ok" for r in rows)
    assert any(r.discrepancy > 0 for r in rows)


def test_sm_interleave_conforms_and_aggregates():
    bench = next(b for b in SUITE if b.name == "RBFS0")
    res = SIM.run(bench, CFG, mechanism="sm_interleave",
                  meta={"sm_warps": 3, "sm_inner": "hanoi"})
    base = SIM.run(bench, CFG, mechanism="hanoi")
    _assert_state_agrees(res, base, program=bench.program,
                         who="sm_interleave")
    sm = res.meta["sm"]
    assert sm.n_warps == 3 and sm.inner == "hanoi"
    assert sm.steps == 3 * len(base.trace)
    assert len(res.trace) == sm.steps


def test_sm_rejects_nesting_itself():
    """Both nesting routes are errors — explicit ``inner=`` on run_sm and
    ``sm_inner`` meta on the registered mechanism; only a Simulator whose
    *default* happens to be sm_interleave falls back to hanoi."""
    bench = next(b for b in SUITE if b.name == "DIAMOND")
    with pytest.raises(ValueError, match="single-warp"):
        SIM.run_sm(bench, CFG, inner="sm_interleave")
    with pytest.raises(ValueError, match="single-warp"):
        SIM.run(bench, CFG, mechanism="sm_interleave",
                meta={"sm_inner": "sm_interleave"})
    sm = Simulator("sm_interleave").run_sm(bench, CFG, n_warps=2)
    assert sm.inner == "hanoi" and sm.ok
