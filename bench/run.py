"""Run one benchmark cell once, on the chip this process finds.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Run from the root of a checkout.  The cells, their configurations, traffic
mixes and metrics are listed in ``BENCHMARK.json``; see ``bench/harness.py``
for what a run does and ``PERF.md`` for what each number means.  The last
line of standard output is the result as one JSON object; a run that finds
no TPU, or fewer chips than the cell asks for, exits non-zero without one.
"""
import os
import sys
import time

T0 = time.perf_counter()          # set-up is timed from here

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
# the benchmark is imported as the package ``bench``; its own directory
# must not shadow standard modules (``bench/trace.py``)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from bench.harness import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], t0=T0))
