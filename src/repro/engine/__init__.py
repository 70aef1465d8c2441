"""repro.engine — the unified control-flow simulation API.

This package is the **canonical entry point** for running SASS-lite warps
under any control-flow-management mechanism.  It replaces the four ad-hoc
engine entry points (``interp.run_hanoi``, ``interp.run_simt_stack``,
``dualpath.run_dual_path`` and the JAX ``hanoi`` module) with one façade,
one request/result schema, and one trace format.

Quick start
-----------
::

    from repro.core.programs import make_suite
    from repro.engine import MachineConfig, Simulator

    cfg = MachineConfig(n_threads=32, mem_size=256, max_steps=60_000)
    sim = Simulator("hanoi")

    # one warp, one mechanism
    res = sim.run(make_suite(cfg)[0], cfg)
    print(res.status, res.utilization, len(res.trace))

    # the paper's Fig 9/10 evaluation in one call
    report = sim.compare(["hanoi", "turing_oracle"], make_suite(cfg), cfg)
    print(report.mean_discrepancy("hanoi", "turing_oracle"))

    # batched execution: one vmap over warps+programs on the JAX engine
    results = sim.run_batch(make_suite(cfg), cfg, mechanism="hanoi_jax")

Layout
------
* :mod:`repro.engine.types`     — frozen :class:`SimRequest` /
  :class:`SimResult` with the normalized :class:`SimStatus`
  (``OK`` / ``OUT_OF_FUEL`` / ``DEADLOCK`` / ``ERROR``);
* :mod:`repro.engine.registry`  — the :class:`Mechanism` registry and the
  :func:`register_mechanism` decorator for third-party mechanisms;
* :mod:`repro.engine.adapters`  — the five built-ins: ``simt_stack``,
  ``hanoi``, ``turing_oracle``, ``dualpath``, ``hanoi_jax``;
* :mod:`repro.engine.mechanisms` — plugin mechanisms beyond the adapter
  family: ``volta_itps`` (per-thread-PC independent thread scheduling) and
  ``sm_interleave`` (per-SM multi-warp time-multiplexing);
* :mod:`repro.engine.sinks`     — pluggable :class:`TraceSink` consumers
  (:class:`MemorySink`, :class:`JsonlSink`, :class:`RingBufferSink`, the
  rotating archival :class:`RotatingJsonlSink`); :func:`run_meta` stamps
  begin events with a ``replay`` payload, making archives replayable
  offline by :mod:`repro.archive` (read + Fig 9 diffing at archive scale);
* :mod:`repro.engine.simulator` — the :class:`Simulator` façade with
  ``run`` / ``run_batch`` / ``run_sm`` / ``compare``; batch dispatch is
  shared with :mod:`repro.service` (the queue-fed simulation service —
  admission coalescing, native-batch routing, sharded SM cells, service
  metrics).

Adding a mechanism
------------------
::

    from repro.engine import SimRequest, SimResult, register_mechanism

    @register_mechanism("darm", description="divergence-melding prototype")
    def run_darm(req: SimRequest) -> SimResult:
        ...

New plugins must pass the differential conformance suite
(``tests/test_conformance.py``): final architectural state must agree with
``simt_stack`` on every program where both report ``SimStatus.OK``.
Candidate future mechanisms (see ROADMAP): DARM-style branch melding and
decoupled control flow.
"""
from repro.core.isa import MachineConfig

from .registry import (Mechanism, available_mechanisms, get_mechanism,
                       iter_mechanisms, register_mechanism,
                       unregister_mechanism)
from .sinks import (JsonlSink, MemorySink, RingBufferSink, RotatingJsonlSink,
                    TraceSink, feed_result, replay_payload, run_meta,
                    sm_run_meta, timing_meta)
from .types import (SimRequest, SimResult, SimStatus, SmResult,
                    classify_status, worst_status)
from .simulator import (CompareReport, CompareRow, Simulator, as_request)
from .compile_cache import (CompileCache, WarmReport, compile_cache_stats,
                            install_compile_cache, install_jax_cache,
                            installed_cache, uninstall_compile_cache)
from . import adapters as _adapters            # registers the built-ins
from . import mechanisms as _mechanisms        # registers the plugins

__all__ = [
    "CompareReport", "CompareRow", "CompileCache", "JsonlSink",
    "MachineConfig", "Mechanism",
    "MemorySink", "RingBufferSink", "RotatingJsonlSink", "SimRequest",
    "SimResult", "SimStatus", "SmResult", "Simulator", "TraceSink",
    "WarmReport",
    "as_request", "available_mechanisms", "classify_status",
    "compile_cache_stats", "feed_result",
    "get_mechanism", "install_compile_cache", "install_jax_cache",
    "installed_cache",
    "iter_mechanisms", "register_mechanism",
    "replay_payload", "run_meta", "sm_run_meta", "timing_meta",
    "uninstall_compile_cache", "unregister_mechanism",
    "worst_status",
]
