"""Built-in mechanism adapters: existing engines -> normalized SimResult.

Five mechanisms ship with the engine (the paper's comparable family plus
the Dual-Path comparison point and the TPU-vectorized engine):

==============  =======  ====================================================
name            backend  model
==============  =======  ====================================================
simt_stack      numpy    pre-Volta SIMT-Stack, IPDom reconvergence (SS II)
hanoi           numpy    the paper's Hanoi mechanism (SS VII)
turing_oracle   numpy    Hanoi + the runtime skip heuristic (SS IX); consumes
                         ``SimRequest.bsync_skip_pcs``
dualpath        numpy    Dual-Path execution model (Rhu & Erez, HPCA'13)
hanoi_jax       jax      Hanoi as a JIT/vmap JAX state machine with the
                         native batched runner.  Drop-in for ``hanoi``:
                         it *ignores* ``bsync_skip_pcs`` (use the low-level
                         ``repro.core.hanoi.run_hanoi_jax`` for oracle-mode
                         JAX runs)
==============  =======  ====================================================

Each adapter funnels through :func:`~repro.engine.types.classify_status`, so
``SimResult.status`` means the same thing no matter which engine produced it.
"""
from __future__ import annotations

import contextlib
import threading
import time
from collections import OrderedDict
from typing import Sequence

import numpy as np

from repro import obs
from repro.core.interp import RunResult, run_hanoi, run_simt_stack, \
    simd_utilization
from repro.core.dualpath import run_dual_path

from .registry import register_mechanism
from .types import SimRequest, SimResult, classify_status

__all__ = ["PAD_QUANTUM", "padded_len", "batch_class",
           "result_from_runresult", "batch_cache_stats",
           "reset_batch_caches", "set_batch_cache_capacity"]


def result_from_runresult(mechanism: str, r: RunResult, req: SimRequest,
                          wall_time_s: float = 0.0) -> SimResult:
    """Map a legacy numpy ``RunResult`` onto the normalized schema."""
    cfg = req.resolved_cfg()
    trace = tuple(r.trace)
    return SimResult(
        mechanism=mechanism,
        status=classify_status(finished=r.finished, full_mask=cfg.full_mask,
                               fuel_left=r.fuel_left, error=r.error),
        regs=np.asarray(r.regs), preds=np.asarray(r.preds),
        mem=np.asarray(r.mem), finished=int(r.finished), steps=int(r.steps),
        fuel_left=int(r.fuel_left), trace=trace,
        utilization=simd_utilization(r.trace, cfg.n_threads),
        error=r.error, wall_time_s=wall_time_s)


# ---------------------------------------------------------------------------
# numpy mechanisms
# ---------------------------------------------------------------------------

@register_mechanism(
    "hanoi", backend="numpy", tags=("paper", "reference"),
    description="Hanoi WS/REC-stack mechanism (paper SS VII), numpy "
                "reference interpreter")
def _run_hanoi(req: SimRequest) -> SimResult:
    cfg = req.resolved_cfg()
    t0 = time.perf_counter()
    r = run_hanoi(req.program, cfg, init_regs=req.init_regs,
                  init_mem=req.init_mem, lane_ids=req.lane_ids,
                  active0=req.active0, majority_first=req.majority_first,
                  record_trace=req.record_trace)
    return result_from_runresult("hanoi", r, req, time.perf_counter() - t0)


@register_mechanism(
    "turing_oracle", backend="numpy", uses_skip_pcs=True, tags=("paper",),
    description="Hanoi plus the Turing runtime skip heuristic (paper SS IX);"
                " skips reconvergence at SimRequest.bsync_skip_pcs")
def _run_turing_oracle(req: SimRequest) -> SimResult:
    cfg = req.resolved_cfg()
    t0 = time.perf_counter()
    r = run_hanoi(req.program, cfg, init_regs=req.init_regs,
                  init_mem=req.init_mem, lane_ids=req.lane_ids,
                  active0=req.active0, majority_first=req.majority_first,
                  bsync_skip_pcs=frozenset(req.bsync_skip_pcs),
                  record_trace=req.record_trace)
    return result_from_runresult("turing_oracle", r, req,
                                 time.perf_counter() - t0)


@register_mechanism(
    "simt_stack", backend="numpy", tags=("paper", "baseline"),
    description="pre-Volta SIMT-Stack with compile-time IPDom reconvergence "
                "(paper SS II)")
def _run_simt_stack(req: SimRequest) -> SimResult:
    cfg = req.resolved_cfg()
    t0 = time.perf_counter()
    r = run_simt_stack(req.program, cfg, init_regs=req.init_regs,
                       init_mem=req.init_mem, lane_ids=req.lane_ids,
                       record_trace=req.record_trace)
    return result_from_runresult("simt_stack", r, req,
                                 time.perf_counter() - t0)


@register_mechanism(
    "dualpath", backend="numpy", tags=("related-work",),
    description="Dual-Path execution model (Rhu & Erez, HPCA'13), the "
                "paper's SS X comparison point")
def _run_dualpath(req: SimRequest) -> SimResult:
    cfg = req.resolved_cfg()
    t0 = time.perf_counter()
    r = run_dual_path(req.program, cfg, init_regs=req.init_regs,
                      init_mem=req.init_mem, lane_ids=req.lane_ids,
                      record_trace=req.record_trace)
    return result_from_runresult("dualpath", r, req,
                                 time.perf_counter() - t0)


# ---------------------------------------------------------------------------
# vectorized JAX mechanism (lazy import: keep numpy-only paths jax-free)
# ---------------------------------------------------------------------------

PAD_QUANTUM = 32       # pad program length up to a multiple -> fewer recompiles


def padded_len(n: int) -> int:
    """The padding class of an ``n``-instruction program: its length rounded
    up to the next :data:`PAD_QUANTUM` multiple.  Programs in the same class
    compile to (and batch into) the same XLA executable; the service planner
    uses it as part of the execution signature."""
    return -(-n // PAD_QUANTUM) * PAD_QUANTUM


def batch_class(n: int) -> int:
    """The batch class of ``n`` lane-step rows: the next power of two at or
    above ``n`` (at least 1).  A batch is padded to its class, so batches
    of 1..64 rows share 7 executables per (cfg, padding class)."""
    return 1 << max(0, int(n) - 1).bit_length()


def _fetch_states(states):
    """Bring a (batched) ``HanoiState`` to the host in one call.

    ``jax.device_get`` starts every leaf's copy before it waits on any, so
    a batch costs one round trip; its rows are then numpy views, and
    assembling a result from one touches the device no more."""
    import jax
    return jax.device_get(states)


def _jax_result(req: SimRequest, state, wall_time_s: float,
                mechanism: str = "hanoi_jax",
                meta: "dict | None" = None) -> SimResult:
    """The ``SimResult`` of one warp's host-side (numpy) ``HanoiState``.
    ``regs``, ``preds`` and ``mem`` are copies, so a result never aliases
    or pins the batch it came from."""
    from repro.core.hanoi import ERR_NO_FREE_BX, state_trace
    cfg = req.resolved_cfg()
    err_flags = int(state.error)
    error = ("WARPSYNC: no free Bx register"
             if err_flags & ERR_NO_FREE_BX else None)
    trace = tuple(state_trace(state)) if req.record_trace else ()
    fuel_left = int(state.fuel)
    return SimResult(
        mechanism=mechanism,
        status=classify_status(finished=int(state.finished),
                               full_mask=cfg.full_mask,
                               fuel_left=fuel_left, error=error),
        regs=np.array(state.regs), preds=np.array(state.preds),
        mem=np.array(state.mem), finished=int(state.finished),
        steps=int(state.steps), fuel_left=fuel_left, trace=trace,
        utilization=simd_utilization(list(trace), cfg.n_threads),
        error=error, wall_time_s=wall_time_s, meta=meta or {})


class _LruDict(OrderedDict):
    """A bounded mapping with LRU eviction and an eviction counter.

    The old ``functools.lru_cache(maxsize=None)`` / bare-dict pair grew
    without bound in a long-lived service process — one entry per distinct
    (cfg, majority_first, batch, pad-class) shape a tenant ever submitted.
    ``__setitem__`` evicts the least-recently-used entry past ``maxsize``;
    ``get`` refreshes recency.  Callers serialize access through
    ``_BATCH_CACHE_LOCK`` — the class itself is not thread-safe.
    """

    def __init__(self, maxsize: int):
        super().__init__()
        self.maxsize = int(maxsize)
        self.evictions = 0

    def get(self, key, default=None):
        try:
            self.move_to_end(key)
        except KeyError:
            return default
        return super().__getitem__(key)

    def __setitem__(self, key, value):
        super().__setitem__(key, value)
        self.move_to_end(key)
        while len(self) > self.maxsize:
            self.popitem(last=False)
            self.evictions += 1


#: Default capacities: executables dominate host memory, jit wrappers are
#: cheap but each fronts its own XLA trace cache, so both are bounded.
_EXEC_CACHE_CAPACITY = 256
_JIT_CACHE_CAPACITY = 64

_BATCH_CACHE_LOCK = threading.Lock()
_JITTED_RUNNERS = _LruDict(_JIT_CACHE_CAPACITY)

#: hits / misses are *executable*-cache counters: a miss means a fresh XLA
#: trace+compile happened in this process (the "re-trace" the warm-start
#: gate asserts to zero); a disk_hit means the persistent compile cache
#: supplied the executable without tracing.
_BATCH_STATS = {"hits": 0, "misses": 0, "disk_hits": 0, "trace_time_s": 0.0}


def _jitted_batch_runner(cfg, majority_first: bool):
    """One jitted vmap-over-(warps, programs) callable per (cfg,
    majority_first).  The jit boundary is essential for service throughput:
    a bare ``jax.vmap(one)`` re-traces the whole state machine on *every*
    batch call (slower than the per-request path, whose inner ``_run`` jit
    caches), whereas this callable re-traces only per new (batch class,
    padding class) shape and then replays the cached executable."""
    key = (cfg, bool(majority_first))
    with _BATCH_CACHE_LOCK:
        fn = _JITTED_RUNNERS.get(key)
    if fn is not None:
        return fn
    import jax
    from repro.core.hanoi import _run, init_state

    # the name is the XLA module's in profiler traces (``jit_one``)
    def one(prog, skip, reg, mem, lane):
        st = init_state(prog.shape[0], cfg, init_regs=reg, init_mem=mem,
                        lane_ids=lane)
        return _run(prog, st, skip, cfg, majority_first)

    fn = jax.jit(jax.vmap(one))
    with _BATCH_CACHE_LOCK:
        _JITTED_RUNNERS[key] = fn
    return fn


def _batch_arrays(reqs: Sequence[SimRequest], cfg, pad_len: int
                  ) -> tuple[np.ndarray, ...]:
    """``(progs, skips, regs, mems, lanes)`` operand arrays for one
    signature-homogeneous batch, programs padded with unreachable EXITs to
    ``pad_len``."""
    from repro.core.isa import Op

    W = cfg.n_threads
    progs = np.zeros((len(reqs), pad_len, 8), np.int32)
    progs[:, :, 0] = int(Op.EXIT)                      # unreachable pad
    skips = np.zeros((len(reqs), pad_len), bool)       # hanoi: no oracle skips
    regs = np.zeros((len(reqs), W, cfg.n_regs), np.int32)
    mems = np.zeros((len(reqs), cfg.mem_size), np.int32)
    lanes = np.broadcast_to(np.arange(W, dtype=np.int32),
                            (len(reqs), W)).copy()
    for i, r in enumerate(reqs):
        p = np.asarray(r.program, np.int32)
        progs[i, :p.shape[0]] = p
        if r.init_regs is not None:
            regs[i] = np.asarray(r.init_regs, np.int32).reshape(W, cfg.n_regs)
        if r.init_mem is not None:
            mems[i] = np.asarray(r.init_mem, np.int32).reshape(cfg.mem_size)
        if r.lane_ids is not None:
            lanes[i] = np.asarray(r.lane_ids, np.int32).reshape(W)
    return progs, skips, regs, mems, lanes


def _dedupe_rows(progs: np.ndarray, skips: np.ndarray, regs: np.ndarray,
                 mems: np.ndarray, lanes: np.ndarray
                 ) -> tuple[np.ndarray, np.ndarray]:
    """Hash-cons warp rows: ``(first, inv)`` with ``first`` the indices of
    the unique rows (in first-seen order) and ``inv[i]`` the unique slot of
    row ``i``.  Execution is a pure function of the row operands (the
    resolved config and ``majority_first`` are batch-wide), so identical
    rows — N replicated warps of a cell, repeated cells of a grid — run
    the lane program once and share one result."""
    uniq: dict[bytes, int] = {}
    first: list[int] = []
    inv = np.empty(progs.shape[0], np.int64)
    for i in range(progs.shape[0]):
        key = (progs[i].tobytes() + skips[i].tobytes() + regs[i].tobytes()
               + mems[i].tobytes() + lanes[i].tobytes())
        u = uniq.get(key)
        if u is None:
            u = len(first)
            uniq[key] = u
            first.append(i)
        inv[i] = u
    return np.asarray(first, np.int64), inv


# AOT-compiled executables keyed by (cfg, majority_first, batch, pad_len).
# Compilation happens exactly once per key, *outside* any request's timed
# window — first-call compile latency used to be amortized into the batch's
# per-request wall times, poisoning ServiceStats p50/p99 and bench numbers.
_COMPILED_BATCH = _LruDict(_EXEC_CACHE_CAPACITY)


def batch_cache_stats() -> dict:
    """Snapshot of the hanoi_jax batch-compilation caches.

    ``misses`` counts fresh XLA trace+compiles in this process (the
    "re-trace" events the warm-start gate asserts to zero); ``disk_hits``
    counts executables supplied by an installed persistent
    :mod:`~repro.engine.compile_cache` without tracing; ``trace_time_s``
    is the cumulative wall time spent tracing+compiling.
    """
    with _BATCH_CACHE_LOCK:
        return {**_BATCH_STATS,
                "entries": len(_COMPILED_BATCH),
                "capacity": _COMPILED_BATCH.maxsize,
                "evictions": (_COMPILED_BATCH.evictions
                              + _JITTED_RUNNERS.evictions)}


def reset_batch_caches() -> None:
    """Drop every in-memory compiled executable / jit wrapper and zero the
    counters — simulates a process restart for warm-start tests without
    actually respawning the interpreter."""
    with _BATCH_CACHE_LOCK:
        _COMPILED_BATCH.clear()
        _COMPILED_BATCH.evictions = 0
        _JITTED_RUNNERS.clear()
        _JITTED_RUNNERS.evictions = 0
        for k in _BATCH_STATS:
            _BATCH_STATS[k] = 0.0 if k == "trace_time_s" else 0


def set_batch_cache_capacity(executables: int | None = None,
                             runners: int | None = None) -> None:
    """Re-bound the in-memory caches (existing overflow evicts eagerly)."""
    with _BATCH_CACHE_LOCK:
        if executables is not None:
            _COMPILED_BATCH.maxsize = int(executables)
            while len(_COMPILED_BATCH) > _COMPILED_BATCH.maxsize:
                _COMPILED_BATCH.popitem(last=False)
                _COMPILED_BATCH.evictions += 1
        if runners is not None:
            _JITTED_RUNNERS.maxsize = int(runners)
            while len(_JITTED_RUNNERS) > _JITTED_RUNNERS.maxsize:
                _JITTED_RUNNERS.popitem(last=False)
                _JITTED_RUNNERS.evictions += 1


def _compiled_batch_exec(cfg, majority_first: bool, batch: int, pad_len: int):
    """``(compiled executable, fresh compile seconds | None)`` for one
    (cfg, majority_first, batch-class, padding-class) shape signature.

    Lookup order: in-memory LRU -> installed persistent compile cache
    (deserialized AOT executable, no trace) -> fresh AOT trace+compile
    (``jit(...).lower(...).compile()``), which is then offered back to the
    persistent cache.  An in-memory hit whose signature is missing from
    the installed cache's manifest is *adopted* (stored on the spot):
    executables compiled before the cache was installed are still hot
    traffic, and a warm start must replay them too.  Only the
    fresh-compile path returns a non-``None`` compile time —
    trace/compile latency is measured separately from execution so it
    never inflates request wall times.
    """
    from .compile_cache import installed_cache

    key = (cfg, bool(majority_first), int(batch), int(pad_len))
    with _BATCH_CACHE_LOCK:
        hit = _COMPILED_BATCH.get(key)
        if hit is not None:
            _BATCH_STATS["hits"] += 1
    if hit is not None:
        cache = installed_cache()
        if cache is not None and not cache.has(
                "hanoi_jax", cfg, majority_first, batch, pad_len):
            # compiled before the cache was installed: adopt it, so the
            # signature is hot in the manifest and warm starts replay it
            cache.store_executable("hanoi_jax", cfg, majority_first,
                                   batch, pad_len, hit)
        return hit, None

    cache = installed_cache()
    if cache is not None:
        with obs.span("sim.compile"):
            compiled = cache.load_executable("hanoi_jax", cfg,
                                             majority_first, batch, pad_len)
        if compiled is not None:
            with _BATCH_CACHE_LOCK:
                _BATCH_STATS["disk_hits"] += 1
                _COMPILED_BATCH[key] = compiled
            return compiled, None

    import jax
    import jax.numpy as jnp
    from jax._src import config as jax_config

    W = cfg.n_threads
    sds = jax.ShapeDtypeStruct
    # an executable that JAX's persistent compilation cache handed back does
    # not survive serialize -> deserialize on the CPU backend (its fused
    # functions are missing at run time), so the executable an installed
    # cache serializes is compiled fresh
    fresh = (jax_config.enable_compilation_cache(False) if cache is not None
             else contextlib.nullcontext())
    with obs.span("sim.compile") as timed, fresh:
        compiled = _jitted_batch_runner(cfg, majority_first).lower(
            sds((batch, pad_len, 8), jnp.int32),
            sds((batch, pad_len), jnp.bool_),
            sds((batch, W, cfg.n_regs), jnp.int32),
            sds((batch, cfg.mem_size), jnp.int32),
            sds((batch, W), jnp.int32)).compile()
    compile_s = timed.seconds
    with _BATCH_CACHE_LOCK:
        _BATCH_STATS["misses"] += 1
        _BATCH_STATS["trace_time_s"] += compile_s
        _COMPILED_BATCH[key] = compiled
    if cache is not None:
        cache.store_executable("hanoi_jax", cfg, majority_first, batch,
                               pad_len, compiled, compile_s)
    return compiled, compile_s


def _run_lane_step(reqs: Sequence[SimRequest]):
    """Run one signature's requests through the lane step, each distinct
    row once: the batching path of ``hanoi_jax`` and ``sm_jax``.

    Requests must share cfg / majority_first / active0=None.  Rows are
    packed, hash-consed and padded to their :func:`batch_class` by
    repeating the first.  Returns ``(host, inv, progs, trace, lane_s,
    compile_s)``: the batch's host states (unique rows first), ``inv[i]``
    the row of ``reqs[i]``, the batch's packed programs, the device's
    ``(trace_pc, trace_mask)`` (the rest of the device states is freed on
    return), the ``sim.lane_step`` seconds and the fresh compile's seconds
    (``None`` on a cache hit).
    """
    import jax
    import jax.numpy as jnp

    cfg = reqs[0].resolved_cfg()
    with obs.span("sim.pack"):
        L = padded_len(max(int(np.asarray(r.program).shape[0])
                           for r in reqs))
        arrays = _batch_arrays(reqs, cfg, L)
        first, inv = _dedupe_rows(*arrays)
        sel = np.concatenate([first, np.full(
            batch_class(len(first)) - len(first), first[0], np.int64)])
        rows = [a[sel] for a in arrays]
    compiled, compile_s = _compiled_batch_exec(cfg, reqs[0].majority_first,
                                               len(sel), L)
    with obs.span("sim.lane_step") as lane:
        states = compiled(*(jnp.asarray(a) for a in rows))
        jax.block_until_ready(states.regs)
    with obs.span("sim.assemble"):
        host = _fetch_states(states)
    if obs.enabled():
        # rows past len(first) repeat row 0: padding, not useful work
        _count_lane_step(cfg, host.steps[:len(first)], host.fuel)
    return (host, inv, rows[0], (states.trace_pc, states.trace_mask),
            lane.seconds, compile_s)


def _run_hanoi_jax_batch(reqs: Sequence[SimRequest]) -> list[SimResult]:
    """Native batched execution through :func:`_run_lane_step`.

    Wall-time accounting: ``wall_time_s`` is execution-only, amortized per
    request.  A fresh XLA compile (first batch per shape signature) is
    measured separately and stamped as ``meta["compile_time_s"]`` on that
    batch's results — it never inflates latency percentiles.
    """
    import jax

    host, inv, _, _, lane_s, compile_s = _run_lane_step(reqs)
    wall = lane_s / len(reqs)
    meta = {"compile_time_s": compile_s} if compile_s is not None else None
    with obs.span("sim.assemble"):
        return [_jax_result(r, jax.tree_util.tree_map(
                    lambda x, u=u: x[u], host), wall, meta=meta)
                for r, u in zip(reqs, inv)]


def _count_lane_step(cfg, steps, fuel_left) -> None:
    """Feed the lane-step counters for one executed batch (every row,
    padding included; ``steps`` only for the distinct rows).

    ``lane_step.row_iterations`` is rows x the batch's ``while_loop`` trip
    count.  Each iteration spends one unit of fuel, executing an
    instruction or not (a reconvergence, a halt), so the trip count is the
    most fuel any row spent, which can exceed its ``steps``."""
    spent = max(cfg.max_steps - int(f) for f in fuel_left)
    obs.count("lane_step.rows", len(fuel_left))
    obs.count("lane_step.row_iterations", len(fuel_left) * spent)
    obs.count("lane_step.useful_steps", sum(int(s) for s in steps))


@register_mechanism(
    "hanoi_jax", backend="jax",
    batch_runner=_run_hanoi_jax_batch, tags=("paper", "vectorized"),
    description="Hanoi as a JIT-compiled, vmap-batched JAX state machine "
                "(TPU-native); bit-identical to the numpy reference. "
                "Ignores bsync_skip_pcs — drop-in for 'hanoi'; use the "
                "low-level run_hanoi_jax for oracle-mode batches")
def _run_hanoi_jax(req: SimRequest) -> SimResult:
    from repro.core.hanoi import run_hanoi_jax
    cfg = req.resolved_cfg()
    t0 = time.perf_counter()
    state = run_hanoi_jax(
        req.program, cfg, init_regs=req.init_regs, init_mem=req.init_mem,
        lane_ids=req.lane_ids, active0=req.active0,
        majority_first=req.majority_first,
        pad_to=padded_len(int(np.asarray(req.program).shape[0])))
    import jax
    jax.block_until_ready(state.regs)
    wall = time.perf_counter() - t0
    return _jax_result(req, _fetch_states(state), wall)
