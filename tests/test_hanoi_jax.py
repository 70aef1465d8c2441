"""Exact-equivalence tests: vectorized JAX engine vs. numpy reference.

The JAX engine must produce bit-identical architectural state AND the exact
same control-flow trace (the paper's comparison object) for every program.
"""
import numpy as np
import pytest

from repro.core import MachineConfig
from repro.core.hanoi import (_put, run_hanoi_jax, run_warps_jax,
                              state_deadlocked, state_trace)
from repro.engine import Simulator
from repro.core.programs import (fig5_program, fig6_program, make_suite,
                                 spinlock_program, warpsync_program)
# compat shim: without hypothesis only the @given tests skip, the
# example-based equivalence tests below still run
from tests.hypothesis_compat import given, settings, st
from tests.progen import BASE_CFG, MEM, W, make_program

CFG = MachineConfig(n_threads=4, max_steps=2048)
PAD = 128
SIM = Simulator("hanoi")


def run_ref(prog, cfg, *, init_mem=None, init_regs=None, skips=()):
    """The numpy Hanoi reference through the canonical ``repro.engine`` API
    (``interp.run_hanoi`` is a deprecated shim); a non-empty oracle skip set
    selects the ``turing_oracle`` mechanism, which is Hanoi + skips."""
    mech = "turing_oracle" if skips else "hanoi"
    return SIM.run(prog, cfg, mechanism=mech, init_mem=init_mem,
                   init_regs=init_regs, bsync_skip_pcs=tuple(skips))


def assert_equiv(prog, cfg, *, init_mem=None, skips=()):
    ref = run_ref(prog, cfg, init_mem=init_mem, skips=skips)
    st_ = run_hanoi_jax(prog, cfg, init_mem=init_mem, bsync_skip_pcs=skips,
                        pad_to=PAD)
    assert state_deadlocked(st_, cfg) == ref.deadlocked
    np.testing.assert_array_equal(np.asarray(st_.regs), ref.regs)
    np.testing.assert_array_equal(np.asarray(st_.preds), ref.preds)
    np.testing.assert_array_equal(np.asarray(st_.mem), ref.mem)
    assert int(st_.finished) == ref.finished
    assert tuple(state_trace(st_)) == ref.trace


@pytest.mark.parametrize("mk", [fig5_program, fig6_program,
                                lambda: warpsync_program(4)])
def test_jax_matches_numpy_on_figures(mk):
    assert_equiv(mk(), CFG)


def test_jax_matches_numpy_on_spinlock():
    assert_equiv(spinlock_program(), MachineConfig(n_threads=4,
                                                   max_steps=2048))


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 5_000), n_bx=st.sampled_from([2, 8]))
def test_jax_matches_numpy_on_random_programs(seed, n_bx):
    built, cfg = make_program(seed, n_bx)
    if built is None:
        return
    prog, mem = built
    if prog.shape[0] > 256:
        return
    cfg = cfg._replace(max_steps=4096)
    ref = run_ref(prog, cfg, init_mem=mem)
    st_ = run_hanoi_jax(prog, cfg, init_mem=mem, pad_to=256)
    np.testing.assert_array_equal(np.asarray(st_.regs), ref.regs)
    np.testing.assert_array_equal(np.asarray(st_.mem), ref.mem)
    assert int(st_.finished) == ref.finished
    assert tuple(state_trace(st_)) == ref.trace


def test_vmapped_warps_match_sequential():
    """The vectorized simulator's selling point: many warps in one XLA call,
    each bit-identical to a solo run."""
    cfg = MachineConfig(n_threads=8, mem_size=64, max_steps=4096)
    built, _ = make_program(1234, 8)
    prog, _ = built
    n_warps = 4
    rng = np.random.default_rng(0)
    regs = np.zeros((n_warps, cfg.n_threads, cfg.n_regs), np.int32)
    mems = rng.integers(0, 8, size=(n_warps, cfg.mem_size)).astype(np.int32)
    batched = run_warps_jax(prog, cfg, regs, mems)
    for i in range(n_warps):
        ref = run_ref(prog, cfg, init_regs=regs[i], init_mem=mems[i])
        np.testing.assert_array_equal(np.asarray(batched.regs[i]), ref.regs)
        np.testing.assert_array_equal(np.asarray(batched.mem[i]), ref.mem)
        assert int(batched.finished[i]) == ref.finished


@settings(max_examples=25, deadline=None)
@given(seed=st.integers(0, 5_000), fuel=st.sampled_from([7, 23, 61]))
def test_fuel_exhaustion_equivalence(seed, fuel):
    """Out-of-fuel normalization: when the scheduler-slot budget expires
    mid-execution (including mid-split), the numpy and JAX engines must
    agree on the truncated trace, step count, remaining fuel, AND the
    normalized SimStatus — fuel exhaustion is flagged, never silently
    truncated differently per engine."""
    from repro.engine import classify_status
    built, cfg = make_program(seed, 2)
    if built is None:
        return
    prog, mem = built
    if prog.shape[0] > 256:
        return
    cfg = cfg._replace(max_steps=fuel)
    ref = run_ref(prog, cfg, init_mem=mem)
    st_ = run_hanoi_jax(prog, cfg, init_mem=mem, pad_to=256)
    assert tuple(state_trace(st_)) == ref.trace
    assert int(st_.steps) == ref.steps
    assert int(st_.fuel) == ref.fuel_left
    assert int(st_.finished) == ref.finished
    s_np = classify_status(finished=ref.finished, full_mask=cfg.full_mask,
                           fuel_left=ref.fuel_left, error=ref.error)
    s_jx = classify_status(finished=int(st_.finished),
                           full_mask=cfg.full_mask,
                           fuel_left=int(st_.fuel), error=None)
    assert s_np == s_jx


def test_oracle_skip_on_jax_engine():
    from repro.core.isa import Op
    built = None
    for seed in range(77, 120):
        built, cfg = make_program(seed, 8)
        if built is not None:
            break
    prog, mem = built
    cfg = cfg._replace(max_steps=4096)
    skips = ()
    bsyncs = [pc for pc in range(prog.shape[0]) if prog[pc, 0] == Op.BSYNC]
    if bsyncs:
        skips = (bsyncs[-1],)
    ref = run_ref(prog, cfg, init_mem=mem, skips=skips)
    st_ = run_hanoi_jax(prog, cfg, init_mem=mem, bsync_skip_pcs=skips)
    np.testing.assert_array_equal(np.asarray(st_.regs), ref.regs)
    assert tuple(state_trace(st_)) == ref.trace


@pytest.mark.parametrize("i", [-9, -8, -1, 0, 3, 7, 8, 40])
def test_put_matches_at_set(i):
    """The lane step's one-hot update gives what ``.at[i].set`` gives:
    negative indices count from the end, indices past the end change
    nothing — on a vector and on the rows of a matrix."""
    import jax
    import jax.numpy as jnp
    vec = jnp.arange(8, dtype=jnp.int32)
    mat = jnp.arange(24, dtype=jnp.int32).reshape(8, 3)
    row = jnp.array([-1, -2, -3], jnp.int32)
    put = jax.jit(_put)
    np.testing.assert_array_equal(put(vec, i, -5), vec.at[i].set(-5))
    np.testing.assert_array_equal(put(mat, i, row), mat.at[i].set(row))


# ---------------------------------------------------------------------------
# the batch path's spans and lane-step counters (repro.obs)
# ---------------------------------------------------------------------------

def test_batch_feeds_the_lane_step_counters_and_spans():
    """A hanoi_jax batch counts every row, its loop's trip count (the most
    fuel a row spent: reconvergence and halt iterations spend fuel without
    a step) and the rows' steps; its wall time is the lane-step span's."""
    from repro import obs
    from repro.engine import SimRequest
    from repro.engine.adapters import batch_class, padded_len
    cfg = MachineConfig(n_threads=4, mem_size=48, max_steps=1536)
    suite = [b for b in make_suite(cfg, datasets=2)
             if padded_len(len(b.program)) == 32][:5]
    assert len(suite) == 5
    reqs = [SimRequest(program=b.program, cfg=cfg, init_mem=b.init_mem,
                       name=b.name) for b in suite]
    obs.reset()
    obs.enable()
    try:
        results = Simulator("hanoi_jax").run_batch(reqs)
    finally:
        obs.disable()
    snap = obs.snapshot()
    obs.reset()
    counters, spans = snap["counters"], snap["spans"]
    trip = max(cfg.max_steps - r.fuel_left for r in results)
    assert trip >= max(r.steps for r in results)
    rows = batch_class(5)                 # 5 distinct rows + 3 of padding
    assert counters == {
        "lane_step.rows": rows,
        "lane_step.row_iterations": rows * trip,
        "lane_step.useful_steps": sum(r.steps for r in results)}
    want = {"sim.run_batch": 1, "sim.pack": 1, "sim.lane_step": 1,
            "sim.assemble": 2}            # the bulk copy, then the results
    assert {n: spans[n]["n"] for n in want} == want
    assert spans["sim.run_batch"]["self_s"] < spans["sim.run_batch"][
        "total_s"]
    for r in results:
        assert r.wall_time_s == pytest.approx(
            spans["sim.lane_step"]["total_s"] / 5)
        if "compile_time_s" in r.meta:       # this shape compiled here
            assert r.meta["compile_time_s"] == pytest.approx(
                spans["sim.compile"]["total_s"])


@pytest.mark.parametrize("n,cls", [(0, 1), (1, 1), (2, 2), (3, 4), (4, 4),
                                   (5, 8), (16, 16), (17, 32), (63, 64),
                                   (64, 64), (65, 128), (2176, 4096)])
def test_batch_class(n, cls):
    from repro.engine.adapters import batch_class
    assert batch_class(n) == cls


def test_run_batch_compiles_one_executable_per_batch_class():
    """Batches of 1..17 requests under one signature run on the executables
    of their batch classes (1, 2, 4, 8, 16, 32), each compiled once, and
    every warp is bit-identical to numpy Hanoi.  BFSD carries oracle skip
    pcs, which hanoi_jax ignores: it joins the others' batch."""
    from repro.engine import as_request
    from repro.engine.adapters import batch_cache_stats, batch_class
    cfg = MachineConfig(n_threads=4, mem_size=48, max_steps=1280)  # own key
    reqs = [as_request(b, cfg) for b in make_suite(cfg, datasets=3)[:17]]
    assert any(r.bsync_skip_pcs for r in reqs)
    ref = [SIM.run(r) for r in reqs]
    sim, seen = Simulator("hanoi_jax"), set()
    for n in range(1, 18):
        before = batch_cache_stats()["misses"]
        got = sim.run_batch(reqs[:n])
        new = batch_class(n) not in seen
        seen.add(batch_class(n))
        assert batch_cache_stats()["misses"] - before == int(new), n
        for g, w in zip(got, ref):
            _assert_same_result(g, w)
    assert seen == {1, 2, 4, 8, 16, 32}


def test_lane_step_program_keeps_its_module_name():
    """Profiler traces name the lane step's device program by its XLA
    module, ``jit_one``: a refactor must not rename it unseen."""
    from repro.engine.adapters import _compiled_batch_exec
    compiled, _ = _compiled_batch_exec(CFG, True, 2, 32)
    assert compiled.as_text().startswith("HloModule jit_one,")


# ---------------------------------------------------------------------------
# result assembly: one bulk copy per batch, then numpy rows
# ---------------------------------------------------------------------------

NO_FREE_BX_ASM = """
    BSSY B0, s0
    BSSY B1, s1
    WARPSYNC 15
    MOV R3, 9
s1:
    BSYNC B1
s0:
    BSYNC B0
    EXIT
"""

SPIN_FOREVER_ASM = """
top:
    IADDI R1, R1, 1
    BRA top
"""

# a diamond whose arms make it longer than one padding quantum
LONG_DIAMOND_ASM = (
    "    LANEID R1\n    BSSY B0, sync\n    ISETP.LT P0, R1, 2\n"
    "    @P0 BRA taken\n" + "    IADDI R2, R2, 3\n" * 16 +
    "    BRA sync\ntaken:\n" + "    IADDI R3, R3, 5\n" * 16 +
    "sync:\n    BSYNC B0\n    STG [R1], R2\n    EXIT\n")


def _mixed_batch():
    """Requests for one device batch: programs of two padding classes, a
    warp that runs out of fuel, one whose WARPSYNC finds no free Bx, and
    ``record_trace`` on and off.  The planner would split them by
    signature; the batch runner takes them as one batch."""
    from repro.core.asm import assemble
    from repro.engine import SimRequest, SimStatus
    from repro.engine.adapters import padded_len
    cfg = MachineConfig(n_threads=4, n_bx=2, mem_size=48, max_steps=1536)
    short = next(b for b in make_suite(cfg, datasets=2) if b.name == "LUD0")
    long_ = assemble(LONG_DIAMOND_ASM)
    assert padded_len(len(short.program)) == 32
    assert padded_len(len(long_)) == 64
    reqs = [
        SimRequest(program=short.program, cfg=cfg, init_mem=short.init_mem),
        SimRequest(program=long_, cfg=cfg, record_trace=False),
        SimRequest(program=long_, cfg=cfg),
        SimRequest(program=assemble(SPIN_FOREVER_ASM), cfg=cfg),
        SimRequest(program=assemble(NO_FREE_BX_ASM), cfg=cfg),
        SimRequest(program=fig5_program(), cfg=cfg, record_trace=False),
    ]
    ref = [Simulator("hanoi").run(r) for r in reqs]
    assert ref[3].status is SimStatus.OUT_OF_FUEL
    assert ref[4].error == "WARPSYNC: no free Bx register"
    return reqs, ref


def _assert_same_result(got, want):
    for f in ("status", "finished", "steps", "fuel_left", "trace",
              "utilization", "error"):
        assert getattr(got, f) == getattr(want, f), f
    for f in ("regs", "preds", "mem"):
        a, b = getattr(got, f), getattr(want, f)
        assert a.dtype == b.dtype and a.shape == b.shape, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def test_batch_results_match_single_requests_and_numpy():
    """Every field of a mixed batch's results equals the single-request
    path's and the numpy Hanoi reference's (mechanism, wall time and
    compile meta aside)."""
    from repro.engine.adapters import _run_hanoi_jax_batch
    reqs, ref = _mixed_batch()
    batch = _run_hanoi_jax_batch(reqs)
    one = Simulator("hanoi_jax")
    for req, got, want in zip(reqs, batch, ref):
        single = one.run(req)
        assert got.mechanism == single.mechanism == "hanoi_jax"
        _assert_same_result(got, want)
        _assert_same_result(single, want)


def test_batch_copies_to_the_host_once_and_assembles_numpy(monkeypatch):
    """A batch brings its states to the host in one call; every leaf a
    result is built from is numpy, and no result shares memory with
    another or with the fetched batch."""
    from repro.engine import adapters
    reqs, _ = _mixed_batch()
    fetches, seen = [], []
    fetch, build = adapters._fetch_states, adapters._jax_result

    def counting_fetch(states):
        fetches.append(fetch(states))
        return fetches[-1]

    def checking_build(req, state, *a, **kw):
        seen.append([type(x) for x in state])
        return build(req, state, *a, **kw)

    monkeypatch.setattr(adapters, "_fetch_states", counting_fetch)
    monkeypatch.setattr(adapters, "_jax_result", checking_build)
    results = adapters._run_hanoi_jax_batch(reqs)
    assert len(fetches) == 1
    assert len(seen) == len(reqs)
    for types in seen:
        assert all(issubclass(t, (np.ndarray, np.generic)) for t in types)
    [host] = fetches
    for i, a in enumerate(results):
        for f in ("regs", "preds", "mem"):
            assert not np.shares_memory(getattr(a, f), getattr(host, f)), f
            for b in results[i + 1:]:
                assert not np.shares_memory(getattr(a, f), getattr(b, f)), f


# ---------------------------------------------------------------------------
# STG and the atomics: lanes collide on addresses, in ascending lane order
# ---------------------------------------------------------------------------

MEM_CFG = MachineConfig(n_threads=32, mem_size=64, max_steps=16)
MEM_OPS = ("STG", "ATOMADD", "ATOMEXCH", "ATOMCAS")
LANE = np.arange(32)
I32_MAX = 2**31 - 1

# scenario -> (address register R0, b in R1, c in R2, active lanes in R3,
# imm, the atomics' destination register, memory words set before the run)
MEM_CASES = {
    "one_address": (np.full(32, 5), LANE * 7 + 1, LANE + 100,
                    np.ones(32), 0, 4, {5: 36}),
    "two_interleaved": (9 + 20 * (LANE % 2), LANE // 2, LANE // 2 + 1,
                        np.ones(32), 0, 4, {9: 0, 29: 3}),
    "predicated_gaps": (LANE % 3, LANE // 3, LANE // 3 + 1,
                        LANE % 4 != 1, 2, 4, {2: 0, 3: 0, 4: 1}),
    "negative_wrapping": ((LANE % 4) * 64 - 200 + LANE % 2, LANE - 16,
                          16 - LANE, np.ones(32), 70, 4, {}),
    "int32_overflow": (LANE % 2, I32_MAX - LANE, -I32_MAX + LANE,
                       np.ones(32), 0, 4, {0: I32_MAX - 5, 1: -I32_MAX}),
    "dst_is_s0": (LANE % 3 + 4, LANE % 5, LANE + 1, np.ones(32), 0, 0,
                  {4: 0, 5: 2}),
    "dst_is_s1": (LANE % 3 + 4, LANE % 5, LANE + 1, np.ones(32), 0, 1,
                  {4: 0, 5: 2}),
    # lane 2k compares with k and swaps in k + 1 on one word, lane 2k + 1
    # on another: each chain runs only as long as every earlier swap did,
    # until lane 20 (compare 99) breaks the first
    "cas_chain": (11 + 3 * (LANE % 2), np.where(LANE == 20, 99, LANE // 2),
                  LANE // 2 + 1, np.ones(32), 0, 4, {11: 0, 14: 0}),
}


def _mem_request(op, case, variant):
    """One warp whose only work is one ``op`` under a collision scenario;
    ``variant`` picks the memory image the scenario's words are set in."""
    from repro.core.asm import assemble
    from repro.engine import SimRequest
    addr, b, c, active, imm, d, words = MEM_CASES[case]
    operands = f"[R0+{imm}], R1"
    text = {"STG": f"STG {operands}",
            "ATOMCAS": f"ATOMCAS R{d}, {operands}, R2"}.get(
                op, f"{op} R{d}, {operands}")
    prog = assemble(f"    ISETP.NE P0, R3, 0\n    @P0 {text}\n    EXIT\n")
    regs = np.zeros((32, MEM_CFG.n_regs), np.int64)
    regs[:, 0], regs[:, 1], regs[:, 2], regs[:, 3] = addr, b, c, active
    regs[:, 5:] = LANE[:, None] * 3 - 7            # registers no op names
    rng = np.random.default_rng([MEM_OPS.index(op), variant])
    mem = (np.arange(64) * 3 - 50 if variant == 0
           else rng.integers(-2**31, 2**31, 64))
    for a, v in words.items():
        mem[a] = v
    return SimRequest(program=prog, cfg=MEM_CFG,
                      init_regs=regs.astype(np.int32),
                      init_mem=mem.astype(np.int32))


@pytest.fixture(scope="module")
def mem_batch():
    """Every (op, scenario) case under two memory images: 64 warps of four
    opcodes in one vmapped lane-step batch, each request with its result."""
    from repro.engine.adapters import _run_hanoi_jax_batch
    keys = [(op, case, v) for v in (0, 1) for op in MEM_OPS
            for case in MEM_CASES]
    assert len(keys) == 64
    reqs = [_mem_request(*k) for k in keys]
    got = _run_hanoi_jax_batch(reqs)
    return {k: (r, g) for k, r, g in zip(keys, reqs, got)}


@pytest.mark.parametrize("case", list(MEM_CASES))
@pytest.mark.parametrize("op", MEM_OPS)
def test_memory_op_collisions_match_numpy(op, case, mem_batch):
    """STG and the atomics keep the numpy reference's lane-serial order —
    lanes in ascending order, only the executing ones — when lanes collide
    on addresses, as a single warp and as one row of a 64-row batch."""
    for v in (0, 1):
        req, batched = mem_batch[(op, case, v)]
        want = Simulator("hanoi").run(req)
        assert want.status.name == "OK"
        _assert_same_result(Simulator("hanoi_jax").run(req), want)
        _assert_same_result(batched, want)


def test_lane_step_has_no_serial_lane_loop():
    """The batched lane step's only loops are the step loop and at most
    one more: STG and the atomics update memory lane-parallel, not in a
    loop over the 32 lanes whose carry holds the memory or registers (a
    static ``fori_loop`` shows up as ``scan``)."""
    import jax
    import jax.numpy as jnp
    from repro.engine.adapters import _jitted_batch_runner
    cfg = MachineConfig(n_threads=32)
    n, L, W = 4, 32, cfg.n_threads
    sds = jax.ShapeDtypeStruct
    jaxpr = jax.make_jaxpr(_jitted_batch_runner(cfg, True))(
        sds((n, L, 8), jnp.int32), sds((n, L), jnp.bool_),
        sds((n, W, cfg.n_regs), jnp.int32), sds((n, cfg.mem_size), jnp.int32),
        sds((n, W), jnp.int32))

    def loops(jx):
        found = []
        for eqn in jx.eqns:
            if eqn.primitive.name in ("while", "scan"):
                found.append(eqn.primitive.name)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                found += loops(sub)
        return found

    found = loops(jaxpr.jaxpr)
    assert "while" in found
    assert len(found) <= 2, found
