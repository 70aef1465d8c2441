"""Paper-table benchmarks for the Hanoi control-flow engine.

All measurements flow through the unified ``repro.engine`` API:

* Fig 9  — control-flow trace discrepancy (Levenshtein %) Hanoi vs. the
           Turing-oracle ("hardware") traces across the benchmark suite,
           via ``Simulator.compare``;
* Fig 10 — relative IPC difference via the trace-driven timing model,
           including the BFSD outlier (+SIMD-utilization gain);
* SS IX-A — hardware storage cost vs. a SIMT-Stack (432 B / ~43% claim);
* engine throughput: vectorized JAX mechanism (vmap ``run_batch``) vs. the
  numpy reference mechanism, warps/second;
* warp-level SIMD utilization of every registered mechanism on BFSD.
"""
from __future__ import annotations

import functools
import time

import numpy as np

from repro.core import MachineConfig, hardware_cost_bytes
from repro.core.programs import make_suite
from repro.core.timing import TimingConfig
from repro.engine import CompareReport, Simulator, available_mechanisms

CFG = MachineConfig(n_threads=32, mem_size=256, max_steps=60_000)
PAIR = ("hanoi", "turing_oracle")

_SIM = Simulator("hanoi")


@functools.lru_cache(maxsize=1)
def _suite():
    # benchmarks are frozen and engines never mutate the shared program /
    # init_mem arrays, so one suite instance serves every table
    return make_suite(CFG, datasets=2)


def compare_report() -> CompareReport:
    """One engine-API call computes both Fig 9 and Fig 10 inputs."""
    return _SIM.compare(list(PAIR), _suite(), CFG, pairs=[PAIR],
                        timing_warps=4, timing_cfg=TimingConfig())


def trace_discrepancy_rows(report: CompareReport | None = None) -> list[dict]:
    """Fig 9: per-execution trace discrepancy vs the hardware oracle."""
    report = report or compare_report()
    families = {b.name: b.family for b in _suite()}
    return [{"bench": row.program, "family": families[row.program],
             "discrepancy_pct": row.discrepancy_pct,
             "trace_len": row.trace_len_b}
            for row in report.pair(*PAIR)]


def ipc_rows(report: CompareReport | None = None) -> list[dict]:
    """Fig 10: relative IPC (trace-driven GTO model) Hanoi vs hardware."""
    report = report or compare_report()
    return [{"bench": row.program,
             "ipc_hanoi": row.ipc_a, "ipc_hw": row.ipc_b,
             "ipc_delta_pct": row.ipc_delta_pct,
             "util_hanoi": row.util_a, "util_hw": row.util_b}
            for row in report.pair(*PAIR)]


def summary() -> dict:
    """The paper's headline numbers on our suite."""
    report = compare_report()
    dd = trace_discrepancy_rows(report)
    ii = ipc_rows(report)
    zero = sum(1 for r in dd if r["discrepancy_pct"] == 0.0)
    nonzero = [r for r in dd if r["discrepancy_pct"] > 0]
    bfsd_i = next(r for r in ii if r["bench"] == "BFSD")
    return {
        "executions": len(dd),
        "zero_discrepancy": zero,
        "avg_discrepancy_pct": float(np.mean([r["discrepancy_pct"]
                                              for r in dd])),
        "max_discrepancy_pct": float(max(r["discrepancy_pct"] for r in dd)),
        "avg_abs_ipc_delta_pct": float(np.mean([abs(r["ipc_delta_pct"])
                                                for r in ii])),
        "bfsd_ipc_gain_pct": bfsd_i["ipc_delta_pct"],
        "bfsd_util_gain_pct": 100.0 * (bfsd_i["util_hanoi"]
                                       - bfsd_i["util_hw"])
        / max(bfsd_i["util_hw"], 1e-9),
        "nonzero_benches": [r["bench"] for r in nonzero],
    }


def hw_cost_rows() -> list[dict]:
    out = []
    for n_bx in (4, 8, 16):
        c = hardware_cost_bytes(MachineConfig(n_threads=32, n_bx=n_bx))
        out.append({"n_bx": n_bx, **c})
    return out


def mechanism_utilization_rows() -> list[dict]:
    """Warp-level SIMD utilization of each control-flow mechanism on the
    divergence-heavy BFS benchmark, computed through the unified engine
    API."""
    cfg = MachineConfig(n_threads=8, mem_size=64, max_steps=8192)
    bench = next(b for b in make_suite(cfg, datasets=1) if b.name == "BFSD")
    rows = []
    for mech in available_mechanisms():
        res = _SIM.run(bench, cfg, mechanism=mech)
        rows.append({"mechanism": mech, "utilization": res.utilization,
                     "steps": res.steps, "status": res.status.value})
    return rows


def engine_throughput(n_warps: int = 32, reps: int = 3) -> dict:
    """Vectorized JAX mechanism vs numpy mechanism, warps/second.

    Both arms use the same per-warp requests (one randomized memory image
    per warp).  The JAX arm is one ``run_batch`` call (the vmap path,
    including result materialization — the price a service actually pays);
    the numpy arm runs sequentially via ``run`` so the ratio stays
    comparable to the historical single-threaded interpreter numbers
    rather than measuring the thread-pool fan-out.
    """
    cfg = MachineConfig(n_threads=8, mem_size=64, max_steps=2048)
    bench = next(b for b in make_suite(cfg, datasets=1) if b.name == "GAUS0")
    rng = np.random.default_rng(0)
    from repro.engine import SimRequest
    reqs = [SimRequest(program=bench.program, cfg=cfg,
                       init_mem=rng.integers(0, 8, size=cfg.mem_size)
                       .astype(np.int32),
                       record_trace=False, name=f"warp{w}")
            for w in range(n_warps)]

    _SIM.run_batch(reqs, mechanism="hanoi_jax")            # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        _SIM.run_batch(reqs, mechanism="hanoi_jax")
    jax_s = (time.perf_counter() - t0) / reps

    t0 = time.perf_counter()
    for req in reqs:
        _SIM.run(req, mechanism="hanoi")
    np_s = time.perf_counter() - t0
    return {"n_warps": n_warps,
            "jax_warps_per_s": n_warps / jax_s,
            "numpy_warps_per_s": n_warps / np_s,
            "speedup": np_s / jax_s}


def main() -> None:
    from repro.engine import install_jax_cache
    install_jax_cache()
    s = summary()
    print("== Fig 9 (trace discrepancy vs hardware oracle) ==")
    for k, v in s.items():
        print(f"  {k}: {v}")
    print("== SS IX-A hardware cost ==")
    for r in hw_cost_rows():
        print(f"  n_bx={r['n_bx']}: hanoi={r['hanoi_bytes']}B "
              f"simt={r['simt_stack_bytes']}B saving={r['saving_frac']:.1%}")
    print("== engine throughput ==")
    print(f"  {engine_throughput()}")
    print("== SIMD utilization per mechanism (BFSD, repro.engine) ==")
    for r in mechanism_utilization_rows():
        print(f"  {r['mechanism']:14s} util={r['utilization']:6.1%} "
              f"steps={r['steps']:5d} status={r['status']}")


if __name__ == "__main__":
    main()
