"""``sm_jax`` conformance + the ISSUE 7 satellite bugfixes.

Acceptance contract:

* the lane-parallel ``sm_jax`` engine is **bit-identical** to the Python
  ``sm_interleave`` scheduler — ``(warp, pc, mask)`` SM trace, cycle
  count, stall taxonomy, instruction totals — for every issue policy,
  over the benchmark suite *and* randomized progen programs (sync and
  memory feature mixes), for homogeneous and heterogeneous cells;
* the argmin-vector policy formulation (``priority_keys``) can never
  drift from the stateful ``IssuePolicy`` classes (randomized drift
  test) — it is the contract ``sm_jax`` compiles against;
* ``sm_jax`` cells archive through the normal sink path and self-replay
  to exactly 0.0 discrepancy;
* satellite fixes stay fixed: ``sm_interleave`` dispatches its warps as
  ONE native batch through the planner (counting probe);
  ``hanoi_jax`` batch compilation is metered separately from execution
  wall time (``compile_time_s`` meta); ``warp_count`` accepts any sized
  sequence and raises on unsized iterables, and the service's warp-level
  stats agree with the façade's cell width for 3-D ndarray stacks.
"""
import re

import numpy as np
import pytest

from repro.archive import ArchiveReader, Replayer
from repro.core import MachineConfig
from repro.core.isa import Op
from repro.core.programs import make_suite
from repro.engine import RotatingJsonlSink, SimRequest, Simulator
from repro.engine.mechanisms.sm import (DEFAULT_WARPS, per_warp_programs,
                                        warp_count)
from repro.engine.registry import (get_mechanism, register_mechanism,
                                   unregister_mechanism)
from repro.service import SimulationService
from repro.timing.policies import POLICY_NAMES, get_policy, priority_keys
from repro.timing.sm_model import CycleConfig
from tests.progen import make_program

# Same shape as the conformance CFG so the jit caches warm once per session.
CFG = MachineConfig(n_threads=8, mem_size=64, max_steps=20_000)
SUITE = make_suite(CFG, datasets=1)
BENCH = {b.name: b for b in SUITE}
SIM = Simulator("hanoi")
BENCHES = ("GAUS0", "RBFS0", "DIAMOND", "HOTS0")


def _assert_sm_equal(j, p):
    """Bit-equality of two SmResults (jax cell vs Python interleaver)."""
    assert j.sm_trace == p.sm_trace
    assert j.steps == p.steps
    assert j.cycles == p.cycles
    assert j.thread_instructions == p.thread_instructions
    assert j.stall_breakdown == p.stall_breakdown
    assert j.utilization == pytest.approx(p.utilization)
    assert j.status == p.status
    assert j.policy == p.policy
    assert len(j.warps) == len(p.warps)
    for wj, wp in zip(j.warps, p.warps):
        assert wj.status == wp.status
        assert wj.trace == wp.trace
        assert np.array_equal(np.asarray(wj.regs), np.asarray(wp.regs))


def _cell_req(bench, *, warps, inner, policy, name=None):
    return SimRequest(program=bench.program, cfg=CFG,
                      init_mem=bench.init_mem, name=name or bench.name,
                      meta={"sm_warps": warps, "sm_inner": inner,
                            "sm_policy": policy})


# ---------------------------------------------------------------------------
# policy drift: priority_keys argmin == stateful select, always
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_priority_keys_never_drift_from_select(policy):
    """For random ready sets and issue/stall histories, the stateful
    ``select`` equals ``argmin over ready of priority_keys()`` — the exact
    formulation ``sm_jax`` compiles.  Injectivity makes ties impossible."""
    rng = np.random.default_rng(20260809)
    for n_warps in (1, 2, 3, 8):
        pol = get_policy(policy, n_warps)
        for _ in range(200):
            keys = pol.priority_keys()
            assert keys.shape == (n_warps,)
            assert len(set(int(k) for k in keys)) == n_warps  # injective
            k = int(rng.integers(1, n_warps + 1))
            ready = sorted(rng.choice(n_warps, size=k, replace=False))
            sel = pol.select(ready)
            assert sel == min(ready, key=lambda w: int(keys[w]))
            if rng.random() < 0.25:
                pol.stalled()
            else:
                pol.issued(sel)
    # the stateless module function agrees with the class methods
    assert list(priority_keys("oldest_first", 4)) == [0, 1, 2, 3]
    assert list(priority_keys("greedy_then_oldest", 4, last=2)) == \
        [1, 2, 0, 4]
    assert list(priority_keys("greedy_then_oldest", 4, last=None)) == \
        [1, 2, 3, 4]
    assert list(priority_keys("round_robin", 4, cursor=3)) == [1, 2, 3, 0]


# ---------------------------------------------------------------------------
# tentpole gate: sm_jax == sm_interleave, bit for bit
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_sm_jax_matches_interleave_on_suite(policy):
    """Every suite bench x warp widths {1, 3, 4}: identical SM schedules."""
    widths = {BENCHES[0]: 1, BENCHES[1]: 3}
    jax_reqs = [_cell_req(BENCH[n], warps=widths.get(n, 4),
                          inner="hanoi_jax", policy=policy)
                for n in BENCHES]
    py_reqs = [_cell_req(BENCH[n], warps=widths.get(n, 4), inner="hanoi",
                         policy=policy) for n in BENCHES]
    jax_res = SIM.run_batch(jax_reqs, mechanism="sm_jax")
    py_res = SIM.run_batch(py_reqs, mechanism="sm_interleave")
    for a, b in zip(jax_res, py_res):
        assert a.error is None and b.error is None
        sm_j, sm_p = a.meta["sm"], b.meta["sm"]
        assert sm_j.mechanism == "sm_jax"
        assert sm_p.mechanism == "sm_interleave"
        _assert_sm_equal(sm_j, sm_p)
        # top-level SimResult mirrors warp 0 + the interleaved (pc, mask)
        assert a.trace == tuple((pc, m) for _, pc, m in sm_j.sm_trace)
        assert np.array_equal(np.asarray(a.regs), np.asarray(b.regs))


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_sm_jax_matches_interleave_on_progen(policy):
    """Randomized programs with sync and memory-latency features — the
    divergence/reconvergence + long-latency shapes the scheduler's stall
    taxonomy actually exercises."""
    pairs = []
    for seed in range(4):
        for sf, mf in ((True, False), (False, True)):
            built, cfg = make_program(seed, 8, sync_features=sf,
                                      mem_features=mf)
            if built is None:
                continue
            prog, mem = built
            pairs.append((prog, mem, cfg))
    assert pairs
    jax_reqs = [SimRequest(program=prog, cfg=cfg, init_mem=mem,
                           name=f"progen{i}",
                           meta={"sm_warps": 3, "sm_inner": "hanoi_jax",
                                 "sm_policy": policy})
                for i, (prog, mem, cfg) in enumerate(pairs)]
    py_reqs = [SimRequest(program=q.program, cfg=q.cfg, init_mem=q.init_mem,
                          name=q.name,
                          meta={**dict(q.meta), "sm_inner": "hanoi"})
               for q in jax_reqs]
    jax_res = SIM.run_batch(jax_reqs, mechanism="sm_jax")
    py_res = SIM.run_batch(py_reqs, mechanism="sm_interleave")
    for a, b in zip(jax_res, py_res):
        assert a.status == b.status
        _assert_sm_equal(a.meta["sm"], b.meta["sm"])


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_sm_jax_grid_matches_interleave_per_cell(policy):
    """One grid of replicated, mixed and distinct cells whose trace
    volumes differ 36-fold (903 against 25 slots), on 6 unique rows padded
    to a batch class of 8: every cell's schedule equals the Python
    interleaver's on that cell alone."""
    from repro.engine.mechanisms.sm_jax import run_cells
    names = [("LUD0", "LUD0", "LUD0"), ("HIST0", "DIAMOND", "HIST0"),
             ("HOTS0", "RBFS0", "SLOCK")]
    cells = [[SimRequest(program=BENCH[n].program, cfg=CFG,
                         init_mem=BENCH[n].init_mem, name=n) for n in cell]
             for cell in names]
    sms = run_cells(cells, policy=policy)
    for sm, cell in zip(sms, names):
        ref = SIM.run_sm([BENCH[n] for n in cell], CFG, inner="hanoi",
                         policy=policy)
        _assert_sm_equal(sm, ref)
    assert sms[0].steps > 8 * sms[1].steps


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_scheduler_issues_a_pc_outside_the_program_as_nop(policy):
    """No program traces a pc outside ``[0, L)``, so the latency table's
    NOP path is driven here with made-up traces: the compiled scheduler
    equals ``schedule_cycle`` on them, a padding row left unread."""
    from repro.engine.mechanisms.sm_jax import (_POLICY_IDS,
                                                _compiled_grid_scheduler)
    from repro.timing.sm_model import schedule_cycle
    L, T, lat = 8, 16, (2, 7, 30, 41)           # ALU, control, memory, atomic
    ops_u = np.array([[Op.LDG, Op.IADD, Op.BRA, Op.ATOMADD, Op.STG, Op.MOV,
                       Op.EXIT, Op.LDG]] * 4, np.int32)
    ops_u[1] = np.roll(ops_u[0], 3)
    ops_u[2] = np.roll(ops_u[0], 5)             # row 3 pads with row 0
    pcs = [[0, -1, 7, L, 3, 1, L + 5, 2, 4, 0], [-2, 2, 3, 5, 0, L],
           [6, 99, 1, 4, 7, 7, -1, 0, 3, 5, 2, 6]]
    trace_pc = np.full((4, T), -1, np.int32)
    trace_mask = np.zeros((4, T), np.uint32)
    rng = np.random.default_rng(19)
    for u, row in enumerate(pcs + pcs[:1]):
        trace_pc[u, :len(row)] = row
        trace_mask[u, :len(row)] = rng.integers(1, 1 << 32, len(row))
    warp_map = np.array([[0, 1, 2], [2, 2, 0]], np.int32)
    trace_n = np.array([[len(pcs[u]) for u in cell] for cell in warp_map],
                       np.int32)
    compiled, _ = _compiled_grid_scheduler(2, 3, 4, T, L, 256,
                                           _POLICY_IDS[policy], lat)
    ow, opc, om, issued, cycles, busy, istall, sstall, mstall, tinstr = (
        np.asarray(x) for x in compiled(warp_map, trace_n, ops_u,
                                        trace_pc, trace_mask))
    ccfg = CycleConfig(alu_latency=lat[0], control_latency=lat[1],
                       memory_latency=lat[2], atomic_latency=lat[3],
                       scoreboard=False)
    for c, cell in enumerate(warp_map):
        ref = schedule_cycle(
            [list(zip(trace_pc[u, :len(pcs[u])].tolist(),
                      trace_mask[u, :len(pcs[u])].tolist())) for u in cell],
            [ops_u[u] for u in cell], policy, ccfg)
        n = int(issued[c])
        assert list(zip(ow[c, :n].tolist(), opc[c, :n].tolist(),
                        om[c, :n].tolist())) == ref.order
        assert (ow[c, n:] == -1).all() and (opc[c, n:] == -1).all()
        assert (om[c, n:] == 0).all()
        assert (int(cycles[c]), int(tinstr[c]), int(busy[c]),
                int(istall[c]), int(sstall[c]), int(mstall[c])) == (
            ref.cycles, ref.thread_instructions, ref.busy_cycles,
            ref.issue_stall_cycles, ref.scoreboard_stall_cycles,
            ref.memory_stall_cycles)


def test_run_sm_sm_jax_heterogeneous_and_ndarray_cells():
    """Facade routing: heterogeneous per-warp programs and a 3-D stacked
    ndarray both reach sm_jax and match the Python interleaver."""
    progs = [BENCH["DIAMOND"], BENCH["HOTS0"], BENCH["BFSD"]]
    j = SIM.run_sm(progs, CFG, inner="hanoi_jax",
                   policy="greedy_then_oldest", sm_mechanism="sm_jax")
    p = SIM.run_sm(progs, CFG, inner="hanoi", policy="greedy_then_oldest")
    assert j.mechanism == "sm_jax" and j.inner == "hanoi_jax"
    assert j.n_warps == 3 and len(j.requests) == 3
    _assert_sm_equal(j, p)

    stack = np.stack([BENCH["DIAMOND"].program] * 3)
    j = SIM.run_sm(stack, CFG, inner="hanoi_jax", policy="round_robin",
                   sm_mechanism="sm_jax")
    p = SIM.run_sm(stack, CFG, inner="hanoi", policy="round_robin")
    assert j.n_warps == p.n_warps == 3
    _assert_sm_equal(j, p)


def test_sm_jax_rejects_unsupported_inner_and_timing():
    b = BENCH["DIAMOND"]
    with pytest.raises(ValueError, match="jitted hanoi lane step"):
        SIM.run_sm(b, CFG, inner="volta_itps", sm_mechanism="sm_jax")
    with pytest.raises(ValueError, match="composite"):
        SIM.run_sm(b, CFG, inner="sm_interleave", sm_mechanism="sm_jax")
    with pytest.raises(ValueError, match="sm_mechanism"):
        SIM.run_sm(b, CFG, sm_mechanism="sm_vulkan")
    # trace-conservative cycle accounting only: no scoreboard lift, no
    # stochastic memory model
    with pytest.raises(ValueError, match="scoreboard"):
        SIM.run_sm(b, CFG, sm_mechanism="sm_jax",
                   timing_cfg=CycleConfig(scoreboard=True))
    with pytest.raises(ValueError, match="stochastic-memory"):
        SIM.run_sm(b, CFG, sm_mechanism="sm_jax",
                   timing_cfg=CycleConfig(scoreboard=False,
                                          memory_model="uniform"))


# ---------------------------------------------------------------------------
# archive round-trip: sm_jax cells replay to exactly 0.0
# ---------------------------------------------------------------------------

def test_sm_jax_archive_round_trip_self_replay(tmp_path):
    sink = RotatingJsonlSink(str(tmp_path))
    sm = Simulator("hanoi", sink=sink).run_sm(
        [BENCH["DIAMOND"], BENCH["HOTS0"]], CFG, inner="hanoi_jax",
        policy="greedy_then_oldest", sm_mechanism="sm_jax")
    sink.flush()
    sink.close()
    reader = ArchiveReader(str(tmp_path))
    runs = reader.runs()
    assert len(runs) == sm.n_warps == 2
    assert all(r.replayable for r in runs)
    for w, run in enumerate(runs):
        assert run.meta["sm_warp"] == w
        assert run.meta["sm_warps"] == 2
        assert run.meta["sm_policy"] == "greedy_then_oldest"
        assert run.meta["mechanism"] == "hanoi_jax"
        assert run.trace == sm.warps[w].trace
    report = Replayer().replay(reader)
    assert report.replayed == 2
    assert report.skipped_unreplayable == 0
    assert all(r.discrepancy == 0.0 for r in report.rows)


# ---------------------------------------------------------------------------
# satellite: sm_interleave routes warps through the planner as ONE batch
# ---------------------------------------------------------------------------

def test_sm_interleave_dispatches_warps_as_one_native_batch():
    """A homogeneous 5-warp cell through an inner with a batch_runner must
    hit it exactly once with all 5 warp requests — not 5 scalar calls."""
    hanoi = get_mechanism("hanoi")
    calls = {"batch": 0, "scalar": 0, "sizes": []}

    def probe_batch(reqs):
        calls["batch"] += 1
        calls["sizes"].append(len(reqs))
        return [hanoi(q) for q in reqs]

    try:
        @register_mechanism("probe_counter", backend="numpy",
                            batch_runner=probe_batch, overwrite=True,
                            description="counts native dispatches (test)")
        def _probe(req):
            calls["scalar"] += 1
            return hanoi(req)

        sm = SIM.run_sm(BENCH["DIAMOND"], CFG, n_warps=5,
                        inner="probe_counter")
    finally:
        unregister_mechanism("probe_counter")
    assert sm.ok and sm.n_warps == 5
    assert calls == {"batch": 1, "scalar": 0, "sizes": [5]}


# ---------------------------------------------------------------------------
# satellite: hanoi_jax batches meter compilation separately from wall
# ---------------------------------------------------------------------------

def test_hanoi_jax_compile_time_metered_separately():
    """First batch on a fresh executable shape stamps ``compile_time_s``
    meta and excludes it from ``wall_time_s``; a warm re-run of the same
    shape has no compile stamp at all."""
    cfg = MachineConfig(n_threads=8, mem_size=48, max_steps=4096)
    bench = next(b for b in make_suite(cfg, datasets=1)
                 if b.name == "DIAMOND")
    reqs = [SimRequest(program=bench.program, cfg=cfg,
                       init_mem=bench.init_mem, name=f"d{i}")
            for i in range(2)]
    cold = SIM.run_batch(reqs, mechanism="hanoi_jax")
    for r in cold:
        assert r.error is None
        assert r.meta.get("compile_time_s", 0.0) > 0.0
        # execution wall excludes the (much larger) trace-time compile
        assert 0.0 < r.wall_time_s < r.meta["compile_time_s"]
    warm = SIM.run_batch(reqs, mechanism="hanoi_jax")
    for r, c in zip(warm, cold):
        assert r.error is None
        assert "compile_time_s" not in r.meta
        assert r.trace == c.trace


# ---------------------------------------------------------------------------
# the grid's spans and lane-step / scheduler counters (repro.obs)
# ---------------------------------------------------------------------------

def test_grid_feeds_the_lane_step_and_scheduler_counters():
    """A grid counts the lane step's rows (padding included), its loop trip
    count and the distinct rows' steps, and the scheduler's scanned and
    issued cell-slots; each cell's wall time is its share of the lane-step
    and scheduler spans."""
    from repro import obs
    from repro.engine.adapters import batch_class
    from repro.engine.mechanisms.sm_jax import _out_capacity, run_cells
    a, b, c = (SimRequest(program=BENCH[n].program, cfg=CFG,
                          init_mem=BENCH[n].init_mem, name=n)
               for n in ("DIAMOND", "HOTS0", "GAUS0"))
    cells = [[a, b], [b, c], [a, a]]
    obs.reset()
    obs.enable()
    try:
        sms = run_cells(cells, policy="greedy_then_oldest")
    finally:
        obs.disable()
    snap = obs.snapshot()
    obs.reset()
    counters, spans = snap["counters"], snap["spans"]
    distinct = [sms[0].warps[0], sms[0].warps[1], sms[1].warps[1]]
    rows = batch_class(3)
    trip = max(CFG.max_steps - w.fuel_left for w in distinct)
    cap = _out_capacity(max(sum(len(w.trace) for w in sm.warps)
                            for sm in sms))
    assert counters == {
        "lane_step.rows": rows,
        "lane_step.row_iterations": rows * trip,
        "lane_step.useful_steps": sum(w.steps for w in distinct),
        "schedule.slots_scanned": len(cells) * cap,
        "schedule.slots_issued": sum(sm.steps for sm in sms)}
    want = {"sim.run_cells": 1, "sim.pack": 2, "sim.lane_step": 1,
            "sim.schedule": 1, "sim.assemble": 2}
    assert {n: spans[n]["n"] for n in want} == want
    device_s = spans["sim.lane_step"]["total_s"] + \
        spans["sim.schedule"]["total_s"]
    for sm in sms:
        assert sm.wall_time_s == pytest.approx(device_s / len(cells))
        if "compile_time_s" in sm.meta:      # a shape compiled here
            assert sm.meta["compile_time_s"] == pytest.approx(
                spans["sim.compile"]["total_s"])


def test_scheduler_program_keeps_its_module_name():
    """Profiler traces name the issue scheduler's device program by its XLA
    module, ``jit_schedule``: a refactor must not rename it unseen."""
    from repro.engine.mechanisms.sm_jax import (_POLICY_IDS,
                                                _compiled_grid_scheduler)
    compiled, _ = _compiled_grid_scheduler(
        2, 2, 8, 64, 32, 256, _POLICY_IDS["greedy_then_oldest"],
        (2, 1, 30, 40))
    assert compiled.as_text().startswith("HloModule jit_schedule,")


def _hlo_ops(hlo: str, op: str, within: str | None = None) -> int:
    """How many ``op`` instructions the HLO module text holds, or only
    those of computation ``within`` and the computations it calls."""
    comps = dict(re.findall(r"^(?:ENTRY )?%([\w.\-]+) .*?\{\n(.*?)^\}",
                            hlo, re.M | re.S))
    names = list(comps) if within is None else [within]
    seen: set = set()
    while names:
        name = names.pop()
        if name in seen:
            continue
        seen.add(name)
        if within is not None:
            for ref in re.findall(r"\b(?:calls|to_apply|body|condition|"
                                  r"branch_computations)=(\{[^}]*\}|%[\w.\-]+)",
                                  comps[name]):
                names += re.findall(r"%([\w.\-]+)", ref)
    return sum(len(re.findall(rf"\s{op}\(", comps[n])) for n in seen)


@pytest.mark.parametrize("policy", POLICY_NAMES)
def test_scheduler_scan_body_has_no_scatter_and_one_gather(policy):
    """The scan body updates the issued warp by one-hot selects and reads
    one precomputed latency word: no scatter, at most one gather (batched
    indexed ops cost per cell on a TPU v5e).  The SM trace columns are
    gathered after the scan, outside the loop."""
    from repro.engine.mechanisms.sm_jax import (_POLICY_IDS,
                                                _compiled_grid_scheduler)
    compiled, _ = _compiled_grid_scheduler(
        3, 4, 8, 64, 32, 256, _POLICY_IDS[policy], (2, 1, 30, 40))
    hlo = compiled.as_text()
    (body,) = re.findall(r"\swhile\(.*?\bbody=%([\w.\-]+)", hlo)
    assert _hlo_ops(hlo, "scatter") == 0
    assert _hlo_ops(hlo, "gather", within=body) <= 1
    # warp_map[w], trace_pc_u[row, t] and trace_mask_u[row, t]
    assert _hlo_ops(hlo, "gather") - _hlo_ops(hlo, "gather",
                                                within=body) >= 3


# ---------------------------------------------------------------------------
# satellite: warp_count sized-sequence contract + service stats parity
# ---------------------------------------------------------------------------

def test_warp_count_accepts_any_sized_sequence():
    p = BENCH["DIAMOND"].program
    stack = np.stack([p, p, p])
    assert warp_count(stack, None) == 3
    assert [a.shape for a in per_warp_programs(stack, None)] == [p.shape] * 3
    assert warp_count([p, p], None) == 2
    assert warp_count(p, None) == DEFAULT_WARPS
    assert warp_count(p, 6) == 6
    assert warp_count(BENCH["DIAMOND"], None) == DEFAULT_WARPS

    class Deque:                       # sized, but not list/tuple/ndarray
        def __init__(self, items):
            self._items = list(items)

        def __len__(self):
            return len(self._items)

        def __iter__(self):
            return iter(self._items)

    assert warp_count(Deque([p, p]), None) == 2
    assert len(per_warp_programs(Deque([p, p]), None)) == 2
    with pytest.raises(TypeError, match="unsized iterable"):
        warp_count(iter([p, p]), None)
    with pytest.raises(TypeError, match="unsized iterable"):
        per_warp_programs((q for q in [p, p]), None)
    with pytest.raises(ValueError, match="conflicts"):
        per_warp_programs([p, p], 3)


def test_submit_sm_stats_count_ndarray_stack_warps():
    """The service's warp-level accounting uses the same warp_count as the
    façade: a 3-plane ndarray stack is 3 warps, not DEFAULT_WARPS."""
    stack = np.stack([BENCH["DIAMOND"].program] * 3)
    with SimulationService(default_mechanism="hanoi", workers=1) as svc:
        sm = svc.submit_sm(stack, CFG, policy="round_robin").result()
        stats = svc.stats()
    assert sm.n_warps == 3
    assert stats.sm_jobs == 1
    assert stats.submitted == stats.completed == 3
    assert stats.failed == 0
