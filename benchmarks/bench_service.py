"""Simulation-service throughput sweep: batch size x request mix x mechanism.

Three arms per cell, all producing identical results (the service test
suite asserts that); what differs is dispatch:

* ``loop``    — the pre-service baseline: one ``Simulator.run`` per request;
* ``batch``   — the planner path: one ``Simulator.run_batch`` call
  (signature grouping, native vmap for homogeneous JAX groups);
* ``service`` — the full queue: admission -> coalescer -> worker pool.

Headline effects to look for:

* on the **homogeneous hanoi_jax sweep** the coalesced arms beat the
  per-request loop and the gap widens with batch size (one vmap executable
  amortizes dispatch across the whole group) — the ISSUE 3 acceptance
  criterion;
* on the **mixed sweep** the service still routes each homogeneous
  sub-group natively; the numpy remainder bounds the speedup (GIL-bound
  reference interpreters);
* service-over-batch overhead (queue + ticket hops) stays small and fixed,
  i.e. it amortizes to noise at production batch sizes.

The ``--procs`` sweep adds the process-backed execution tier (ISSUE 8):
the same numpy-heavy traffic through 1..N shard processes.  Numpy
mechanisms serialize behind the GIL, so the thread pool cannot scale them
— the proc tier chunks homogeneous numpy groups across shards and must
deliver real scaling.  ``--smoke --procs 2`` enforces two hard gates
(exit 1 on failure):

* **scaling** — the numpy mix at 2 procs sustains >= 1.5x the warps/s of
  1 proc (request work dwarfs pickle + queue overhead).  Enforced only
  when the host exposes >= 2 CPUs to this process — two shard processes
  pinned to one core cannot scale, so a 1-CPU runner reports the sweep
  and marks the gate SKIPPED rather than failing on missing hardware;
* **warm start** — a restarted ``warm_start=`` service admits traffic
  with zero serve-time re-traces, proven by the service's own cache
  counters (``cache_misses == 0`` with every hot signature deserialized).

The process tier runs before the in-process sweep: its device shard needs
the accelerator, which this process holds from its first jax batch on.

Run:   PYTHONPATH=src python benchmarks/bench_service.py
       PYTHONPATH=src python benchmarks/bench_service.py --procs 2
CI:    PYTHONPATH=src python benchmarks/bench_service.py --smoke --procs 2
"""
from __future__ import annotations

import argparse
import tempfile
import time

import numpy as np

from repro.core import MachineConfig
from repro.core.programs import make_suite
from repro.engine import SimRequest, Simulator, install_jax_cache
from repro.service import SimulationService

CFG = MachineConfig(n_threads=8, mem_size=64, max_steps=8192)
BATCH_SIZES = (4, 16, 64)
MIXES = {
    "hanoi_jax": ("hanoi_jax",),                      # homogeneous, native
    "hanoi": ("hanoi",),                              # homogeneous, numpy
    "mixed": ("hanoi_jax", "hanoi", "simt_stack"),    # round-robin mix
}


def _requests(n: int, benches, seed: int = 0, *,
              rotate: bool = False) -> list[SimRequest]:
    """``n`` requests over fresh memory images.

    The homogeneous sweeps replicate ONE kernel over many datasets (the
    service's target traffic shape — the batched while_loop runs all warps
    in lockstep until the slowest halts, so same-program batches waste no
    work); ``rotate=True`` cycles programs for the mixed sweep.
    """
    rng = np.random.default_rng(seed)
    return [SimRequest(program=benches[i % len(benches)].program
                       if rotate else benches[0].program, cfg=CFG,
                       init_mem=rng.integers(0, 8, size=CFG.mem_size)
                       .astype(np.int32),
                       record_trace=False, name=f"req{i}")
            for i in range(n)]


def _time(fn, repeats: int = 3) -> float:
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        fn()
        best = min(best, time.perf_counter() - t0)
    return best


def sweep_rows(batch_sizes=BATCH_SIZES, mixes=MIXES, *, workers: int = 2,
               repeats: int = 3) -> list[dict]:
    benches = [b for b in make_suite(CFG, datasets=1)
               if b.name in ("HOTS0", "GAUS0", "RBFS0", "DIAMOND")]
    sim = Simulator("hanoi")
    rows = []
    for mix_name, mechs in mixes.items():
        for n in batch_sizes:
            reqs = _requests(n, benches, rotate=len(mechs) > 1)
            assign = [mechs[i % len(mechs)] for i in range(n)]

            def loop_arm():
                return [sim.run(r, mechanism=m)
                        for r, m in zip(reqs, assign)]

            def batch_arm():
                out = []
                for mech in mechs:        # one run_batch per mechanism lane
                    sub = [r for r, m in zip(reqs, assign) if m == mech]
                    out.extend(sim.run_batch(sub, mechanism=mech))
                return out

            def service_arm():
                with SimulationService(default_mechanism=mechs[0],
                                       max_batch=n, max_wait_s=0.05,
                                       workers=workers,
                                       annotate=False) as svc:
                    tickets = [svc.submit(r, mechanism=m)
                               for r, m in zip(reqs, assign)]
                    svc.flush()
                    return [t.result() for t in tickets]

            loop_arm(); batch_arm(); service_arm()        # warm-up/compile
            t_loop = _time(loop_arm, repeats)
            t_batch = _time(batch_arm, repeats)
            t_service = _time(service_arm, repeats)
            rows.append({
                "mix": mix_name, "batch": n,
                "loop_warps_s": n / t_loop,
                "batch_warps_s": n / t_batch,
                "service_warps_s": n / t_service,
                "coalesced_speedup": t_loop / t_service,
            })
    return rows


def _available_cpus() -> int:
    """CPUs this process may actually run on (affinity-aware)."""
    import os
    try:
        return len(os.sched_getaffinity(0))
    except AttributeError:                       # non-Linux fallback
        return os.cpu_count() or 1


def proc_scaling_rows(procs_list=(1, 2), n: int = 64,
                      repeats: int = 3) -> list[dict]:
    """Numpy-mix throughput through the process tier, per shard count.

    The workload is the suite's heaviest numpy kernel (LUD0, ~4.4 ms per
    request at this config) replicated over fresh memory images, so the
    per-request interpreter work dwarfs the pickle + queue overhead the
    spawn boundary adds — that is what makes the >= 1.5x gate fair.  The
    service is started once per shard count; only ``svc.run`` is timed.
    """
    benches = [b for b in make_suite(CFG, datasets=1) if b.name == "LUD0"]
    reqs = _requests(n, benches)
    rows = []
    for procs in procs_list:
        with SimulationService(default_mechanism="hanoi", procs=procs,
                               max_batch=n, max_wait_s=0.05,
                               annotate=False) as svc:
            svc.run(reqs, timeout=300)                      # warm-up
            t = _time(lambda: svc.run(reqs, timeout=300), repeats)
            st = svc.stats()
        rows.append({"procs": procs, "batch": n, "warps_s": n / t,
                     "scaling": (n / t) / rows[0]["warps_s"] if rows
                     else 1.0,
                     "shards_used": sum(1 for s in st.shards
                                        if s.completed > 0)})
    return rows


def warm_start_report(n: int = 8) -> dict:
    """Cold-serve then restart-warm-serve one hot hanoi_jax signature.

    Returns the counters the zero-re-trace gate is judged on: the second
    (restarted, warm-started) service must admit and serve the same
    traffic shape without a single serve-time XLA trace.
    """
    cache_dir = tempfile.mkdtemp(prefix="repro-warm-bench-")
    benches = [b for b in make_suite(CFG, datasets=1) if b.name == "GAUS0"]
    reqs = _requests(n, benches)
    with SimulationService(default_mechanism="hanoi_jax", procs=1,
                           warm_start=cache_dir, max_batch=n,
                           annotate=False) as svc:
        t0 = time.perf_counter()
        cold = svc.run(reqs, timeout=600)
        cold_s = time.perf_counter() - t0
        st1 = svc.stats()
    with SimulationService(default_mechanism="hanoi_jax", procs=1,
                           warm_start=cache_dir, max_batch=n,
                           annotate=False) as svc:
        t0 = time.perf_counter()
        warm = svc.run(reqs, timeout=600)
        warm_s = time.perf_counter() - t0
        st2 = svc.stats()
    zero_retrace = st2.cache_misses == 0 and st2.warm_loaded >= 1
    return {"cold_s": cold_s, "warm_s": warm_s,
            "cold_ok": sum(r.ok for r in cold),
            "warm_ok": sum(r.ok for r in warm),
            "cold_misses": st1.cache_misses,
            "warm_signatures": st2.warm_signatures,
            "warm_loaded": st2.warm_loaded,
            "warm_retraced": st2.warm_retraced,
            "serve_misses": st2.cache_misses,
            "zero_retrace": zero_retrace}


def proc_tier_gates(procs: int, repeats: int) -> list[str]:
    """The process-tier sweep and its gates; returns the failures."""
    failures = []
    print(f"== process tier: numpy mix (LUD0 x64) across shard "
          f"processes ==")
    prows = proc_scaling_rows(procs_list=tuple(range(1, procs + 1)),
                              repeats=repeats)
    for r in prows:
        print(f"  procs {r['procs']}: {r['warps_s']:8.1f} warps/s "
              f"({r['scaling']:.2f}x vs 1 proc, "
              f"{r['shards_used']} shard(s) serving)")
    if procs >= 2:
        two = next(r for r in prows if r["procs"] == 2)
        cpus = _available_cpus()
        if cpus < 2:
            print(f"  gate: 2-proc scaling {two['scaling']:.2f}x — "
                  f"SKIPPED ({cpus} CPU visible; two shard processes "
                  f"cannot scale on one core)")
        else:
            gate = two["scaling"] >= 1.5
            print(f"  gate: 2-proc scaling {two['scaling']:.2f}x >= "
                  f"1.50x -> {'OK' if gate else 'FAIL'}")
            if not gate:
                failures.append(
                    f"proc scaling {two['scaling']:.2f}x < 1.5x")

    print(f"\n== warm start: restarted service, hot hanoi_jax "
          f"signature ==")
    w = warm_start_report()
    print(f"  cold serve: {w['cold_s']:.2f}s ({w['cold_ok']} ok, "
          f"{w['cold_misses']} trace(s))")
    print(f"  warm serve: {w['warm_s']:.2f}s ({w['warm_ok']} ok) — "
          f"manifest {w['warm_signatures']} sig(s), "
          f"{w['warm_loaded']} deserialized + {w['warm_retraced']} "
          f"re-traced at warm time, {w['serve_misses']} serve-time "
          f"trace(s)")
    print(f"  gate: zero serve-time re-trace -> "
          f"{'OK' if w['zero_retrace'] else 'FAIL'}\n")
    if not w["zero_retrace"]:
        failures.append("warm-start restart re-traced at serve time")
    return failures


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="small CI sweep (one batch size per mix); with "
                         "--procs, enforces the scaling + warm-start gates")
    ap.add_argument("--workers", type=int, default=2)
    ap.add_argument("--procs", type=int, default=0,
                    help="also sweep the process tier at 1..N shard "
                         "processes on the numpy mix")
    args = ap.parse_args()
    install_jax_cache()
    # best-of-3 even in smoke mode: JAX's background threads occasionally
    # stall Python thread wakeups ~300ms on small containers, and a single
    # repeat can land entirely inside one such stall
    repeats = 3
    failures = proc_tier_gates(args.procs, repeats) if args.procs else []

    sizes = (16,) if args.smoke else BATCH_SIZES
    rows = sweep_rows(batch_sizes=sizes, workers=args.workers,
                      repeats=repeats)
    hdr = ("mix", "batch", "loop_warps_s", "batch_warps_s",
           "service_warps_s", "coalesced_speedup")
    print(",".join(hdr))
    for r in rows:
        print(",".join(f"{r[k]:.1f}" if isinstance(r[k], float) else str(r[k])
                       for k in hdr))
    homog = [r for r in rows if r["mix"] == "hanoi_jax"]
    print(f"\n== homogeneous hanoi_jax: coalesced vs per-request loop ==")
    for r in homog:
        print(f"  batch {r['batch']:3d}: service {r['service_warps_s']:8.1f} "
              f"warps/s vs loop {r['loop_warps_s']:8.1f} "
              f"({r['coalesced_speedup']:.2f}x)")
    # the acceptance gate sits at the largest batch size: coalescing is a
    # batch-amortization play (at batch 4 there is nothing to coalesce and
    # queue overhead shows); the speedup must be >= 1 where batching is in
    # play and should grow with batch size
    at_scale = max(homog, key=lambda r: r["batch"])
    status = "OK" if at_scale["coalesced_speedup"] >= 1.0 else "BELOW PAR"
    print(f"  at batch {at_scale['batch']}: "
          f"{at_scale['coalesced_speedup']:.2f}x -> {status} "
          f"(acceptance: coalesced >= per-request loop)")

    if args.smoke and failures:
        raise SystemExit("bench gates FAILED: " + "; ".join(failures))


if __name__ == "__main__":
    main()
