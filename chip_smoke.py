"""One run of the simulator's device path on one TPU chip.

Drives the main path through the entry points a user calls, at the paper's
32-lane warp width, and checks every result bit for bit against the numpy
reference interpreters:

a. process tier — ``SimulationService(procs=2)`` serves a ``hanoi_jax`` +
   ``hanoi`` mix of the suite.  It runs first, while this process has not
   touched jax: a chip belongs to one process at a time, and here shard 0
   must own it.  Every jax group must have run on shard 0, on a TPU.
b. device check — ``jax.devices()[0].platform`` must be ``"tpu"``.
c. the Fig 9/10 suite (23 programs) through ``Simulator.run_batch`` on
   ``hanoi_jax``.
d. ``SimulationService`` (thread tier, static verification on) answers 64
   requests with memory images drawn from ``--seed``.
e. a full-GPU grid on ``sm_jax``: 68 SMs (RTX 2080 Ti, TU102) x 32 resident
   warps (NVIDIA Turing Tuning Guide) = 2176 distinct warps, global thread
   ids as lane ids, greedy-then-oldest issue.  All warps are compared with
   numpy ``hanoi`` and a seeded sample of cells with ``sm_interleave``.

Each phase prints what it compared, its mismatches (must be 0) and its wall
and compile seconds.  The times are those of one smoke run, not a
measurement.  The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``;
any failed check exits non-zero before it is printed.

Run from the repository root:  python chip_smoke.py [--seed N]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np  # noqa: E402

from repro.core.isa import MachineConfig  # noqa: E402
from repro.core.programs import make_suite  # noqa: E402
from repro.engine import Simulator, as_request, install_jax_cache  # noqa: E402
from repro.engine.adapters import batch_cache_stats  # noqa: E402

#: The suite's machine: the paper's 32-lane warp (phases a, c, d).
SUITE_CFG = MachineConfig(n_threads=32)
#: The grid's machine (phase e): more fuel for the longest warps.
GRID_CFG = MachineConfig(n_threads=32, max_steps=8192)
N_SMS = 68              # RTX 2080 Ti (TU102)
WARPS_PER_SM = 32       # resident warps per SM, NVIDIA Turing Tuning Guide
GRID_SAMPLE = 8         # cells re-scheduled on sm_interleave
SERVICE_REQUESTS = 64


def _differs(a, b) -> bool:
    """Whether two single-warp results differ in any architectural field."""
    return (a.status != b.status or a.steps != b.steps
            or a.fuel_left != b.fuel_left or a.trace != b.trace
            or not np.array_equal(a.regs, b.regs)
            or not np.array_equal(a.preds, b.preds)
            or not np.array_equal(a.mem, b.mem))


def _drawn_mem(bench, cfg: MachineConfig, rng) -> "np.ndarray | None":
    """A memory image from ``rng`` for programs that read input data; the
    suite's data-less programs (no ``init_mem``) keep their zeroed
    memory."""
    if bench.init_mem is None:
        return None
    return rng.integers(0, 8, size=cfg.mem_size, dtype=np.int32)


def _compile_stamp() -> tuple[int, float]:
    s = batch_cache_stats()
    return s["misses"], s["trace_time_s"]


def phase_process_tier(suite, cfg: MachineConfig) -> dict:
    """Serve the suite under ``hanoi_jax`` and ``hanoi`` through two shard
    processes.  Touches no jax in this process."""
    from jax._src import xla_bridge

    from repro.service import SimulationService

    reqs = [as_request(b, cfg, record_trace=True) for b in suite]
    want = Simulator("hanoi").run_batch(reqs)
    mechs = ("hanoi_jax", "hanoi")
    t0 = time.perf_counter()
    with SimulationService(default_mechanism="hanoi", procs=2) as svc:
        tickets = [svc.submit(r, mechanism=m) for m in mechs for r in reqs]
        svc.flush()
        got = [t.result() for t in tickets]
        stats = svc.stats()
    wall = time.perf_counter() - t0
    jax_meta = [g.meta["service"] for g in got[:len(reqs)]]
    return {
        "compared": len(got),
        "mismatches": sum(_differs(g, w)
                          for g, w in zip(got, want * len(mechs))),
        "jax_shards": sorted({m["shard"] for m in jax_meta}),
        "host_shards": sorted({g.meta["service"]["shard"]
                               for g in got[len(reqs):]}),
        "platforms": sorted({m["platform"] for m in jax_meta}),
        "parent_touched_jax": xla_bridge.backends_are_initialized(),
        "wall_s": wall, "compile_s": stats.cache_trace_time_s,
        "compiles": stats.cache_misses}


def phase_suite(suite, cfg: MachineConfig) -> dict:
    """The Fig 9/10 suite through ``Simulator.run_batch`` on ``hanoi_jax``."""
    misses, trace_s = _compile_stamp()
    t0 = time.perf_counter()
    got = Simulator("hanoi_jax").run_batch(suite, cfg, record_trace=True)
    wall = time.perf_counter() - t0
    misses2, trace_s2 = _compile_stamp()
    want = Simulator("hanoi").run_batch(suite, cfg, record_trace=True)
    return {"compared": len(got),
            "mismatches": sum(_differs(g, w) for g, w in zip(got, want)),
            "ok": sum(g.ok for g in got),
            "max_steps_used": max(cfg.max_steps - g.fuel_left for g in got),
            "wall_s": wall, "compile_s": trace_s2 - trace_s,
            "compiles": misses2 - misses}


def phase_service(suite, cfg: MachineConfig, rng,
                  n: int = SERVICE_REQUESTS) -> dict:
    """``n`` requests through the thread-tier service, every ticket read."""
    from repro.service import SimulationService

    reqs = [as_request(b, cfg, init_mem=_drawn_mem(b, cfg, rng),
                       record_trace=True, name=f"{b.name}/req{i}")
            for i, b in ((i, suite[i % len(suite)]) for i in range(n))]
    misses, trace_s = _compile_stamp()
    t0 = time.perf_counter()
    with SimulationService(default_mechanism="hanoi_jax") as svc:
        tickets = [svc.submit(r) for r in reqs]
        got = [t.result() for t in tickets]
        stats = svc.stats()
    wall = time.perf_counter() - t0
    misses2, trace_s2 = _compile_stamp()
    want = Simulator("hanoi").run_batch(reqs)
    return {"compared": len(got),
            "mismatches": sum(_differs(g, w) for g, w in zip(got, want)),
            "ok": sum(g.ok for g in got),
            "batches": stats.batches, "native_batches": stats.native_batches,
            "p50_s": stats.latency_p50_s, "p99_s": stats.latency_p99_s,
            "wall_s": wall, "compile_s": trace_s2 - trace_s,
            "compiles": misses2 - misses}


def grid_cells(suite, cfg: MachineConfig, rng, n_cells: int,
               n_warps: int) -> list:
    """Cell ``c`` runs suite program ``c mod len(suite)``; lane ids are
    global thread ids and every warp has its own memory image."""
    cells = []
    for c in range(n_cells):
        bench = suite[c % len(suite)]
        cell = []
        for w in range(n_warps):
            tid0 = (c * n_warps + w) * cfg.n_threads
            cell.append(as_request(
                bench, cfg, record_trace=True, name=f"{bench.name}/sm{c}/w{w}",
                init_mem=rng.integers(0, 8, size=cfg.mem_size, dtype=np.int32),
                lane_ids=np.arange(tid0, tid0 + cfg.n_threads,
                                   dtype=np.int32)))
        cells.append(cell)
    return cells


def phase_grid(suite, cfg: MachineConfig, seed: int, n_cells: int = N_SMS,
               n_warps: int = WARPS_PER_SM, n_sample: int = GRID_SAMPLE
               ) -> dict:
    """A grid of SM cells through ``sm_jax.run_cells`` (GTO issue)."""
    import jax

    from repro.engine.adapters import _batch_arrays, padded_len
    from repro.engine.mechanisms.sm_jax import _dedupe_rows, run_cells

    rng = np.random.default_rng(seed)
    cells = grid_cells(suite, cfg, rng, n_cells, n_warps)
    flat = [q for cell in cells for q in cell]
    L = padded_len(max(int(q.program.shape[0]) for q in flat))
    first, _ = _dedupe_rows(*_batch_arrays(flat, cfg, L))
    t0 = time.perf_counter()
    sms = run_cells(cells, policy="greedy_then_oldest")
    wall = time.perf_counter() - t0
    got = [w for sm in sms for w in sm.warps]
    want = Simulator("hanoi").run_batch(flat)
    sample = sorted(rng.choice(n_cells, size=min(n_sample, n_cells),
                               replace=False).tolist())
    ref = Simulator("hanoi")
    cell_mismatches = 0
    for c in sample:
        sm_ref = ref.run_sm(cells[c], sm_mechanism="sm_interleave",
                            inner="hanoi", policy="greedy_then_oldest")
        cell_mismatches += (sms[c].sm_trace != sm_ref.sm_trace
                            or sms[c].cycles != sm_ref.cycles
                            or sms[c].stall_breakdown
                            != sm_ref.stall_breakdown)
    statuses: dict[str, int] = {}
    for r in got:
        statuses[r.status.value] = statuses.get(r.status.value, 0) + 1
    mem_stats = jax.devices()[0].memory_stats() or {}
    return {"warps": len(flat), "unique_rows": len(first),
            "mismatches": sum(_differs(g, w) for g, w in zip(got, want)),
            "cells_sampled": sample, "cell_mismatches": cell_mismatches,
            "statuses": statuses, "wall_s": wall,
            "compile_s": sms[0].meta.get("compile_time_s", 0.0),
            "exec_s": sum(sm.wall_time_s for sm in sms),
            "peak_bytes_in_use": mem_stats.get("peak_bytes_in_use")}


def _report(phase: str, record: dict) -> None:
    print(f"[{phase}] " + " ".join(f"{k}={v}" for k, v in record.items()),
          flush=True)


def _require(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def main(argv: "list[str] | None" = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the memory images and the grid sample")
    args = ap.parse_args(argv)
    install_jax_cache()
    print("chip_smoke: one run of the device path (a smoke check, not a "
          "benchmark)", flush=True)
    suite = make_suite(SUITE_CFG, datasets=2)

    a = phase_process_tier(suite, SUITE_CFG)
    _report("a process-tier", a)
    _require(a["mismatches"] == 0, "process-tier results differ from hanoi")
    _require(not a["parent_touched_jax"],
             "the parent touched jax while its shards held the device")
    _require(a["jax_shards"] == [0], "jax groups ran off shard 0")
    _require(a["platforms"] == ["tpu"],
             f"shard 0 ran jax on {a['platforms']}, not a TPU")

    import jax
    dev = jax.devices()[0]
    _report("b device", {"platform": dev.platform, "kind": dev.device_kind,
                         "count": len(jax.devices())})
    _require(dev.platform == "tpu", f"JAX found no TPU (got {dev.platform})")

    c = phase_suite(suite, SUITE_CFG)
    _report("c suite", c)
    _require(c["compared"] == 23 and c["mismatches"] == 0,
             "suite results differ from hanoi")

    d = phase_service(suite, SUITE_CFG, np.random.default_rng(args.seed))
    _report("d service", d)
    _require(d["compared"] == SERVICE_REQUESTS and d["mismatches"] == 0,
             "service results differ from hanoi")

    e = phase_grid(suite, GRID_CFG, args.seed)
    _report("e grid", e)
    _require(e["unique_rows"] == N_SMS * WARPS_PER_SM,
             "grid warps were not all distinct")
    _require(e["mismatches"] == 0, "grid warps differ from hanoi")
    _require(e["cell_mismatches"] == 0,
             "sampled grid cells differ from sm_interleave")

    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
