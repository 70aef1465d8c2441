"""Run one control-flow cell and print its headline terms.

A cell is one (benchmark x mechanism pair) through the unified
``repro.engine`` API: trace discrepancy, IPC delta and SIMD utilization
for that single benchmark at the paper's 32-lane warp width.

Run:  PYTHONPATH=src python examples/compare_cell.py --bench BFSD \\
          [--mechanisms hanoi,turing_oracle]
"""
import argparse


def run_cf_cell(bench_name: str, mechanisms: list[str]) -> None:
    from repro.core import MachineConfig
    from repro.core.programs import make_suite
    from repro.engine import Simulator

    cfg = MachineConfig(n_threads=32, mem_size=256, max_steps=60_000)
    suite = make_suite(cfg)
    bench = next((b for b in suite if b.name == bench_name), None)
    if bench is None:
        raise SystemExit(f"unknown benchmark {bench_name!r}; available: "
                         + ", ".join(b.name for b in suite))
    a, b = mechanisms
    report = Simulator().compare(mechanisms, [bench], cfg, pairs=[(a, b)])
    row = report.pair(a, b)[0]
    print(f"\n[example] control-flow cell {bench_name} x ({a} vs {b})")
    print(f"  status         {row.status_a} / {row.status_b}")
    print(f"  discrepancy    {row.discrepancy_pct:8.2f} %")
    print(f"  ipc            {row.ipc_a:8.3f} vs {row.ipc_b:8.3f} "
          f"({row.ipc_delta_pct:+.1f}%)")
    print(f"  simd util      {row.util_a:8.3f} vs {row.util_b:8.3f}")
    print(f"  trace lengths  {row.trace_len_a} vs {row.trace_len_b}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--bench", default="BFSD",
                    help="benchmark name from the suite (e.g. BFSD)")
    ap.add_argument("--mechanisms", default="hanoi,turing_oracle",
                    help="comma-separated mechanism pair to compare")
    args = ap.parse_args()

    mechs = [m.strip() for m in args.mechanisms.split(",")]
    if len(mechs) != 2:
        raise SystemExit("--mechanisms needs exactly two names")
    run_cf_cell(args.bench, mechs)


if __name__ == "__main__":
    main()
