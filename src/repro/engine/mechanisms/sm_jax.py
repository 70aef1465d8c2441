"""``sm_jax`` — the whole SM as one ``jit(vmap)`` lane-parallel program.

``sm_interleave`` time-multiplexes warps in Python, one issue slot per
iteration of :func:`repro.timing.schedule_cycle`.  This module reformulates
the same SM model as a lane-parallel state machine so it runs in array
land end to end, in two fused device programs:

1. **warp phase** — every warp of every cell executes the paper's Hanoi
   mechanism through the *same* batching path and cached ``jit(vmap)``
   executable as ``hanoi_jax`` (:func:`repro.engine.adapters.
   _run_lane_step`: one row per distinct warp, padded to its
   :func:`~repro.engine.adapters.batch_class`, programs to their
   :func:`~repro.engine.adapters.padded_len` class);
2. **scheduler phase** — one ``lax.scan`` steps an entire N-warp SM:
   per-warp trace cursors, completion times and memory-blocked flags are
   vectors, warp readiness is a boolean vector, and the issue policy is an
   ``argmin`` over the :func:`repro.timing.policies.priority_keys` vector
   (``greedy_then_oldest`` / ``round_robin`` / ``oldest_first`` — the same
   formulation the Python policy classes expose, pinned by a drift test).
   ``jax.vmap`` lifts the cell scheduler over a whole *grid* of SM cells,
   so a batch of cells is one compiled call.

The schedule reproduces :func:`repro.timing.schedule_cycle`'s
trace-conservative single-issue fixed-latency mode **bit-for-bit**: the
``(warp, pc, mask)`` SM trace, cycle count, and the busy/issue/scoreboard/
memory stall taxonomy all match ``sm_interleave`` exactly (the conformance
suite and ``bench_sm.py --smoke`` gate this).  Scoreboard mode, dual issue
and stochastic memory models remain ``sm_interleave``'s domain — requests
asking for them are rejected with a pointer, never silently approximated.

Request options mirror ``sm_interleave`` (``sm_warps`` / ``sm_policy``);
``sm_inner`` must name a Hanoi engine (``hanoi`` or ``hanoi_jax`` — the
warp phase *is* the jitted Hanoi lane step, bit-identical to both).
"""
from __future__ import annotations

import dataclasses
import itertools
from typing import Sequence

import numpy as np

from repro import obs
from repro.core.isa import ATOMIC_OPS, F_OP, MEMORY_OPS, Op
from repro.core.timing import TimingConfig
from repro.timing import CycleConfig
from repro.timing.policies import POLICY_NAMES, resolve_policy_name
from repro.timing.sm_model import _CONTROL_LAT_OPS

from ..adapters import _jax_result, _run_lane_step
# the benchmark's shape rehearsal and fault tests find these here
from ..adapters import _compiled_batch_exec, _dedupe_rows  # noqa: F401
from ..adapters import batch_class as _batch_class  # noqa: F401
from ..registry import get_mechanism, register_mechanism
from ..types import SimRequest, SimResult, SmResult, worst_status
from .sm import DEFAULT_POLICY, _sm_options

__all__ = ["run_cells"]

# hanoi engines the warp phase is bit-identical to (it *is* the jitted
# hanoi lane step); anything else must go through sm_interleave
_SUPPORTED_INNER = ("hanoi", "hanoi_jax")

# static policy ids for the compiled scheduler (one executable per policy)
_POLICY_IDS = {name: i for i, name in enumerate(POLICY_NAMES)}
_GTO = _POLICY_IDS["greedy_then_oldest"]
_RR = _POLICY_IDS["round_robin"]

_N_OPS = max(int(op) for op in Op) + 1


def _supported_cycle_cfg(tcfg) -> CycleConfig:
    """Validate that the cycle model requested is the one sm_jax compiles."""
    ccfg = CycleConfig.from_timing(tcfg)     # default lift: trace-conservative
    if ccfg.scoreboard or ccfg.issue_width != 1 \
            or ccfg.memory_model != "fixed":
        raise ValueError(
            "sm_jax schedules in the trace-conservative, single-issue, "
            "fixed-latency mode (the sm_interleave default); use "
            "sm_interleave for scoreboard / dual-issue / stochastic-memory "
            "cycle models")
    if min(ccfg.alu_latency, ccfg.control_latency,
           ccfg.memory_latency, ccfg.atomic_latency) < 1:
        raise ValueError("sm_jax requires all class latencies >= 1")
    return ccfg


def _latency_tables(ccfg: CycleConfig) -> tuple[np.ndarray, np.ndarray]:
    """Per-opcode ``(issue latency, blocks-on-memory?)`` lookup tables —
    the array form of ``schedule_cycle``'s latency classification."""
    lat = np.full(_N_OPS, ccfg.alu_latency, np.int32)
    for op in _CONTROL_LAT_OPS:
        lat[int(op)] = ccfg.control_latency
    for op in MEMORY_OPS:                    # includes atomics; atomics
        lat[int(op)] = ccfg.memory_latency   # override below
    for op in ATOMIC_OPS:
        lat[int(op)] = ccfg.atomic_latency
    is_mem = np.zeros(_N_OPS, bool)
    for op in MEMORY_OPS:
        is_mem[int(op)] = True
    return lat, is_mem


def _out_capacity(n: int) -> int:
    """Issue-slot capacity class: power of two with a floor, so the
    scheduler recompiles per coarse trace-volume class, not per cell."""
    return max(256, 1 << max(0, int(n) - 1).bit_length())


def _cell_scheduler(n_warps: int, out_cap: int, policy_id: int,
                    lat_tab: np.ndarray, mem_tab: np.ndarray):
    """One-cell scheduler: a single ``lax.scan`` over issue slots.

    State is entirely vectors over the cell's warps; each iteration issues
    exactly one instruction (after an optional event hop over an idle gap),
    mirroring ``schedule_cycle``'s trace-conservative single-issue loop.
    """
    import jax.numpy as jnp
    from jax import lax

    I32 = jnp.int32
    BIG = jnp.int32(np.iinfo(np.int32).max)
    # an opcode's issue latency and blocks-on-memory flag in one word
    WORD = jnp.asarray(lat_tab.astype(np.int32) * 2 + mem_tab)
    w_ids = jnp.arange(n_warps, dtype=jnp.int32)
    NOP = jnp.int32(int(Op.NOP))

    def priority(last, cursor):
        # the priority_keys() vector formulation, in jnp (drift-tested
        # against repro.timing.policies on the numpy side)
        if policy_id == _GTO:
            return jnp.where(w_ids == last, I32(0), w_ids + 1)
        if policy_id == _RR:
            return (w_ids - cursor) % n_warps
        return w_ids                                   # oldest_first

    # the name is the XLA module's in profiler traces (``jit_schedule``)
    def schedule(warp_map, trace_n, ops_u, trace_pc_u, trace_mask_u):
        # warp_map[w] -> row in the hash-consed trace buffers and in
        # ``ops_u``, the rows' opcode columns (shared, un-vmapped
        # operands): replicated warps read one copy.  A fixed-length scan
        # over issue slots; slots past ``total`` are masked no-ops
        # (``out_cap`` is the grid's padded slot budget).
        total = jnp.sum(trace_n)
        n_uniq, T = trace_pc_u.shape
        L = ops_u.shape[1]
        if n_warps * T > np.iinfo(np.int32).max:
            raise ValueError(f"{n_warps} warps x {T} trace steps overflow "
                             f"the scheduler's int32 slot words")
        # every trace position's latency word, once per row before the
        # scan, so the scan body holds one indexed op: the lookup below
        pc = trace_pc_u
        op = jnp.where((pc >= 0) & (pc < L),
                       ops_u[jnp.arange(n_uniq)[:, None],
                             jnp.clip(pc, 0, L - 1)], NOP)
        slot_word = WORD[jnp.clip(op, 0, _N_OPS - 1)]

        def step(st, _):
            (idx, t_ready, t_mem, in_order, cycle, issued, last, cursor,
             busy, istall, sstall, mstall) = st
            active = issued < total
            pending = idx < trace_n
            earliest = jnp.where(pending,
                                 jnp.maximum(in_order, t_ready), BIG)
            next_t = jnp.min(earliest)
            stalled = active & (next_t > cycle)
            # idle gap: hop to the earliest completion that readies a warp,
            # classified memory/scoreboard by the warps waking at it
            blocked_mem = t_mem & (t_ready >= in_order)
            gap_mem = jnp.any(pending & (earliest <= next_t) & blocked_mem)
            gap = jnp.where(stalled, next_t - cycle, I32(0))
            mstall = mstall + jnp.where(gap_mem, gap, I32(0))
            sstall = sstall + jnp.where(gap_mem, I32(0), gap)
            cycle = jnp.where(active, jnp.maximum(cycle, next_t), cycle)
            last = jnp.where(stalled, I32(-1), last)   # pol.stalled()
            ready = pending & (earliest <= cycle)
            sel = jnp.argmin(jnp.where(ready, priority(last, cursor),
                                       BIG)).astype(jnp.int32)
            n_ready = jnp.sum(ready).astype(jnp.int32)
            # one-hot sums and selects stand in for gathers from and
            # scatters into the warp vectors (``hanoi._put``'s idiom):
            # batched indexed ops cost per cell on a TPU v5e
            one = w_ids == sel
            hit = one & active
            t = jnp.sum(jnp.where(one, idx, I32(0)))
            word = slot_word[jnp.sum(jnp.where(one, warp_map, I32(0))), t]
            t_ready = jnp.where(hit, cycle + (word >> 1), t_ready)
            t_mem = jnp.where(hit, (word & 1) == 1, t_mem)
            in_order = jnp.where(hit, cycle + 1, in_order)
            idx = idx + hit.astype(jnp.int32)
            act32 = active.astype(jnp.int32)
            busy = busy + act32
            # port contention: a warp left ready in the issued cycle
            istall = istall + act32 * (n_ready > 1).astype(jnp.int32)
            if policy_id == _GTO:
                last = jnp.where(active, sel, last)
            if policy_id == _RR:
                cursor = jnp.where(active, (sel + 1) % n_warps, cursor)
            return (idx, t_ready, t_mem, in_order, cycle + act32,
                    issued + act32, last, cursor, busy, istall, sstall,
                    mstall), jnp.where(active, sel * T + t, I32(-1))

        init = (jnp.zeros(n_warps, jnp.int32),          # idx
                jnp.zeros(n_warps, jnp.int32),          # t_ready
                jnp.zeros(n_warps, jnp.bool_),          # t_mem
                jnp.zeros(n_warps, jnp.int32),          # in_order
                I32(0), I32(0),                         # cycle, issued
                I32(0), I32(0),                         # last (GTO init 0),
                                                        # cursor
                I32(0), I32(0), I32(0), I32(0))         # busy + stalls
        st, slot = lax.scan(step, init, None, length=out_cap)
        (idx, t_ready, t_mem, in_order, cycle, issued, last, cursor,
         busy, istall, sstall, mstall) = st
        # the SM trace from each slot's (warp, trace position), after the
        # scan; idle slots read (-1, -1, 0)
        on = slot >= 0
        w, t = jnp.divmod(jnp.maximum(slot, 0), T)
        row = warp_map[w]
        ow = jnp.where(on, w, I32(-1))
        opc = jnp.where(on, trace_pc_u[row, t], I32(-1))
        om = jnp.where(on, trace_mask_u[row, t], jnp.uint32(0))
        tinstr = jnp.sum(lax.population_count(om).astype(jnp.int32))
        return ow, opc, om, issued, cycle, busy, istall, sstall, mstall, \
            tinstr

    return schedule


# AOT-compiled grid schedulers, keyed by every static the kernel closes
# over; compile time is measured at build, never inside a timed window
_SCHED_CACHE: dict = {}


def _compiled_grid_scheduler(n_cells: int, n_warps: int, n_uniq: int,
                             trace_cap: int, prog_len: int, out_cap: int,
                             policy_id: int,
                             lat_key: tuple[int, int, int, int]):
    key = (n_cells, n_warps, n_uniq, trace_cap, prog_len, out_cap,
           policy_id, lat_key)
    hit = _SCHED_CACHE.get(key)
    if hit is not None:
        return hit, None
    import jax
    import jax.numpy as jnp

    alu, ctrl, mem, atom = lat_key
    lat_tab, mem_tab = _latency_tables(CycleConfig(
        alu_latency=alu, control_latency=ctrl, memory_latency=mem,
        atomic_latency=atom, scoreboard=False))
    fn = jax.jit(jax.vmap(_cell_scheduler(n_warps, out_cap, policy_id,
                                          lat_tab, mem_tab),
                          in_axes=(0, 0, None, None, None)))
    sds = jax.ShapeDtypeStruct
    with obs.span("sim.compile") as timed:
        compiled = fn.lower(
            sds((n_cells, n_warps), jnp.int32),           # warp_map
            sds((n_cells, n_warps), jnp.int32),           # trace_n
            sds((n_uniq, prog_len), jnp.int32),           # opcode columns
            sds((n_uniq, trace_cap), jnp.int32),          # hash-consed traces
            sds((n_uniq, trace_cap), jnp.uint32)).compile()
    _SCHED_CACHE[key] = compiled
    return compiled, timed.seconds


#: sequence number of each ``run_cells`` call, the ``call`` of its span
_GRID_CALLS = itertools.count()


def run_cells(cells: Sequence[Sequence[SimRequest]], *,
              policy: str = DEFAULT_POLICY,
              timing_cfg: "TimingConfig | CycleConfig" = TimingConfig(),
              inner_label: str = "hanoi_jax") -> list[SmResult]:
    """Run a grid of SM cells — ``cells[c][w]`` is cell *c*'s warp *w* —
    through the two fused device programs; returns one
    :class:`~repro.engine.types.SmResult` per cell.

    Every warp request across the grid must share its resolved config,
    ``majority_first``, ``record_trace`` and a full entry mask; warps may
    differ in program, memory image, registers and lane ids (heterogeneous
    cells).  All cells must have the same warp count (one compiled
    scheduler steps the whole grid).

    Each cell's ``wall_time_s`` is its even share of the ``sim.lane_step``
    and ``sim.schedule`` spans (:mod:`repro.obs`).
    """
    with obs.span("sim.run_cells", call=next(_GRID_CALLS)):
        policy_name = resolve_policy_name(policy)
        ccfg = _supported_cycle_cfg(timing_cfg)
        if inner_label not in _SUPPORTED_INNER:
            raise ValueError(
                f"sm_jax executes warps on the jitted hanoi lane step; "
                f"inner must be one of {_SUPPORTED_INNER}, got "
                f"{inner_label!r} — use sm_interleave for other inner "
                f"mechanisms")
        if not cells or any(not cell for cell in cells):
            raise ValueError("run_cells needs at least one warp per cell")
        n_warps = len(cells[0])
        if any(len(cell) != n_warps for cell in cells):
            raise ValueError("all cells in one sm_jax grid must share a "
                             "warp count")
        flat = [q for cell in cells for q in cell]
        cfg = flat[0].resolved_cfg()
        mf, record = flat[0].majority_first, flat[0].record_trace
        for q in flat:
            if q.resolved_cfg() != cfg or q.majority_first != mf \
                    or q.record_trace != record:
                raise ValueError("sm_jax warps must share cfg, "
                                 "majority_first and record_trace across "
                                 "the grid")
            if q.active0 is not None:
                raise ValueError("sm_jax assumes a full entry mask "
                                 "(active0=None)")

        import jax
        import jax.numpy as jnp

        # phase 1: every distinct warp row once through the lane step
        # shared with hanoi_jax; N replicated warps of a cell collapse to
        # one row, so a grid costs #unique-programs lane executions
        host, inv, progs, (dev_pc, dev_mask), exec_s, compile_s = \
            _run_lane_step(flat)
        L, n_uniq = progs.shape[1], host.steps.shape[0]

        C, N, T = len(cells), n_warps, cfg.max_steps
        total_compile = compile_s or 0.0
        with obs.span("sim.pack"):
            warp_map = inv.reshape(C, N).astype(np.int32)
            trace_n = host.trace_n[inv].reshape(C, N).astype(np.int32)
            scheduled = bool(record) and int(trace_n.max(initial=0)) > 0
            if scheduled:
                ops_u = progs[:, :, F_OP]
                out_cap = _out_capacity(int(trace_n.sum(axis=1).max()))
        if scheduled:
            # phase 2: the whole grid through one compiled vmapped
            # scheduler; the hash-consed trace buffers are passed
            # un-vmapped, so warps gather their (pc, mask) stream from one
            # device-resident copy
            lat_key = (ccfg.alu_latency, ccfg.control_latency,
                       ccfg.memory_latency, ccfg.atomic_latency)
            sched, sched_compile_s = _compiled_grid_scheduler(
                C, N, n_uniq, T, L, out_cap, _POLICY_IDS[policy_name],
                lat_key)
            total_compile += sched_compile_s or 0.0
            with obs.span("sim.schedule") as sched_span:
                out = sched(jnp.asarray(warp_map), jnp.asarray(trace_n),
                            jnp.asarray(ops_u), dev_pc, dev_mask)
                out = [np.asarray(x) for x in jax.block_until_ready(out)]
            exec_s += sched_span.seconds
            ow, opc, om, out_n, cycles, busy, istall, sstall, mstall, \
                tinstr = out
            if obs.enabled():
                obs.count("schedule.slots_scanned", C * out_cap)
                obs.count("schedule.slots_issued", int(out_n.sum()))

        with obs.span("sim.assemble"):
            warp_wall = exec_s / max(1, len(flat))
            cell_wall = exec_s / max(1, C)
            sm_meta = {"compile_time_s": total_compile} if total_compile \
                else {}
            width = cfg.n_threads
            # one SimResult per unique row, shared by every warp that
            # hash-consed onto it (SimResult is frozen; SmResult.requests
            # keeps per-warp names)
            first = np.unique(inv, return_index=True)[1]
            uniq_results = [
                _jax_result(flat[int(first[u])],
                            jax.tree_util.tree_map(lambda x, u=u: x[u],
                                                   host),
                            warp_wall)
                for u in range(len(first))]
            sms: list[SmResult] = []
            for c, cell in enumerate(cells):
                warps = tuple(uniq_results[inv[i]]
                              for i in range(c * N, (c + 1) * N))
                if scheduled:
                    n_c = int(out_n[c])
                    sm_trace = tuple(zip(ow[c, :n_c].tolist(),
                                         opc[c, :n_c].tolist(),
                                         om[c, :n_c].tolist()))
                    kw = dict(steps=n_c, cycles=int(cycles[c]),
                              thread_instructions=int(tinstr[c]),
                              utilization=int(tinstr[c])
                              / max(1, n_c * width),
                              busy_cycles=int(busy[c]),
                              issue_stall_cycles=int(istall[c]),
                              scoreboard_stall_cycles=int(sstall[c]),
                              memory_stall_cycles=int(mstall[c]))
                else:
                    sm_trace = ()
                    kw = dict(steps=0, cycles=0, thread_instructions=0,
                              utilization=0.0, busy_cycles=0,
                              issue_stall_cycles=0,
                              scoreboard_stall_cycles=0,
                              memory_stall_cycles=0)
                sms.append(SmResult(
                    mechanism="sm_jax", inner=inner_label,
                    policy=policy_name, warps=warps, sm_trace=sm_trace,
                    status=worst_status([r.status for r in warps]),
                    requests=tuple(cell), wall_time_s=cell_wall,
                    meta=sm_meta, **kw))
        return sms


def _sm_jax_options(req: SimRequest) -> tuple[int, str, str]:
    n_warps, inner_name, policy = _sm_options(req)
    inner = get_mechanism(inner_name)
    if "composite" in inner.tags:
        raise ValueError("sm_inner must be a single-warp mechanism, not "
                         f"the composite {inner.name!r}")
    if inner.name not in _SUPPORTED_INNER:
        raise ValueError(
            f"sm_jax executes warps on the jitted hanoi lane step; "
            f"sm_inner must be one of {_SUPPORTED_INNER} (got "
            f"{inner.name!r}) — use sm_interleave for other inner "
            f"mechanisms")
    return n_warps, inner.name, policy


def _run_sm_jax_batch(reqs: Sequence[SimRequest]) -> list[SimResult]:
    """Native batch runner: a whole grid of signature-homogeneous SM cells
    as one warp-phase call plus one scheduler call."""
    n_warps, inner_name, policy = _sm_jax_options(reqs[0])
    cells = []
    for req in reqs:
        stripped = {k: v for k, v in req.meta.items()
                    if not k.startswith("sm_")}
        cells.append([dataclasses.replace(req, meta=stripped,
                                          name=f"{req.name or 'warp'}/w{w}")
                      for w in range(n_warps)])
    sms = run_cells(cells, policy=policy, inner_label=inner_name)
    out = []
    with obs.span("sim.assemble"):
        for sm in sms:
            w0 = sm.warps[0]
            out.append(SimResult(
                mechanism="sm_jax", status=sm.status,
                regs=w0.regs, preds=w0.preds, mem=w0.mem,
                finished=w0.finished, steps=sm.steps,
                fuel_left=min(r.fuel_left for r in sm.warps),
                trace=tuple((pc, mask) for _, pc, mask in sm.sm_trace),
                utilization=sm.utilization,
                error=next((r.error for r in sm.warps if r.error), None),
                wall_time_s=sm.wall_time_s, meta={"sm": sm}))
    return out


@register_mechanism(
    "sm_jax", backend="jax", batch_runner=_run_sm_jax_batch,
    tags=("sm", "multi-warp", "composite", "vectorized"),
    description="per-SM model as one jit(vmap) lane-parallel program: "
                "warps run on the cached hanoi_jax batch executable, the "
                "SM scheduler is a lax.scan with the issue policy "
                "as an argmin over a priority vector (meta: sm_warps, "
                "sm_inner in {hanoi, hanoi_jax}, sm_policy); SM traces "
                "bit-identical to sm_interleave")
def _run_sm_jax(req: SimRequest) -> SimResult:
    return _run_sm_jax_batch([req])[0]
