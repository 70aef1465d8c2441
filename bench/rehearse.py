"""Compile every cell's device programs for a described TPU v5e, here,
without the chip, and print what the compiler says they need.

    JAX_PLATFORMS=cpu python3 bench/rehearse.py [--workload <name>]

For each cell of ``BENCHMARK.json`` it builds unit 1 of seed 0 with the
cell's own generator, works out the shapes the timed entry will compile
(the lane step's batch class of distinct rows and padding class; for grids
the scheduler's cells, unique rows and slot capacity, from the plain
reference's trace lengths), compiles each program for one chip of a
described ``v5e:2x2`` topology and prints its ``memory_analysis``.  Nothing
runs: this proves shapes and memory, not results or times.  Run it by hand
before a chip call that changes shapes; it is not a test (the repository's
compile tests own the topology fixture).
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:] = [p for p in sys.path if os.path.abspath(p or ".") != HERE]
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import numpy as np  # noqa: E402

from bench import harness  # noqa: E402
from bench.check import Checker  # noqa: E402
from bench.generator import Mix  # noqa: E402


def _bytes(compiled) -> dict:
    m = compiled.memory_analysis()
    return {"arguments": m.argument_size_in_bytes,
            "outputs": m.output_size_in_bytes,
            "temporaries": m.temp_size_in_bytes,
            "code": m.generated_code_size_in_bytes}


def _rows(reqs, cfg, L) -> int:
    """The lane step's batch for ``reqs``: its distinct rows' class."""
    from repro.engine.adapters import _batch_arrays, _dedupe_rows, batch_class
    first, _ = _dedupe_rows(*_batch_arrays(reqs, cfg, L))
    return batch_class(len(first))


def shapes(mix: Mix, unit) -> list:
    """``(program, statics)`` the timed entry compiles for ``unit``."""
    from repro.core.isa import MachineConfig
    from repro.engine.adapters import padded_len
    from repro.engine.mechanisms.sm_jax import _out_capacity
    cfg = MachineConfig(**mix.machine)
    if not unit.grid:
        # one lane-step batch per padding class, as ``hanoi_jax`` runs them
        groups: dict = {}
        for req in unit.requests:
            groups.setdefault(padded_len(int(req.program.shape[0])),
                              []).append(req)
        return [("lane", (cfg, _rows(reqs, cfg, L), L))
                for L, reqs in groups.items()]
    reqs = [mix.request(w, skips=False, name="rehearse")
            for cell in unit.cells for w in cell]
    L = padded_len(max(int(r.program.shape[0]) for r in reqs))
    n_uniq = _rows(reqs, cfg, L)
    checker = Checker(mix)
    per_cell = [sum(checker.reference(w).steps for w in cell)
                for cell in unit.cells]
    out_cap = _out_capacity(max(per_cell))
    return [("lane", (cfg, n_uniq, L)),
            ("scheduler", (len(unit.cells), len(unit.cells[0]), n_uniq,
                           cfg.max_steps, L, out_cap))]


def compile_for_v5e(kind, statics, one_chip):
    import jax
    import jax.numpy as jnp
    sds = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt,  # noqa: E731
                                                 sharding=one_chip)
    if kind == "lane":
        from repro.engine.adapters import _jitted_batch_runner
        cfg, n, L = statics
        W = cfg.n_threads
        return _jitted_batch_runner(cfg, True).lower(
            sds((n, L, 8), jnp.int32), sds((n, L), jnp.bool_),
            sds((n, W, cfg.n_regs), jnp.int32),
            sds((n, cfg.mem_size), jnp.int32),
            sds((n, W), jnp.int32)).compile()
    from repro.engine.mechanisms.sm_jax import (_GTO, _cell_scheduler,
                                                _latency_tables)
    from repro.timing import CycleConfig
    cells, warps, n_uniq, T, L, out_cap = statics
    lat, is_mem = _latency_tables(CycleConfig(scoreboard=False))
    # the unique rows' opcode columns and traces are shared by every cell,
    # as ``sm_jax._compiled_grid_scheduler`` compiles them
    fn = jax.jit(jax.vmap(_cell_scheduler(warps, out_cap, _GTO, lat, is_mem),
                          in_axes=(0, 0, None, None, None)))
    return fn.lower(sds((cells, warps), jnp.int32),
                    sds((cells, warps), jnp.int32),
                    sds((n_uniq, L), jnp.int32),
                    sds((n_uniq, T), jnp.int32),
                    sds((n_uniq, T), jnp.uint32)).compile()


def main(argv=None) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", default=None)
    args = ap.parse_args(argv)
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    manifest = harness.load_manifest(ROOT)
    for w in manifest["workloads"]:
        if args.workload and w["name"] != args.workload:
            continue
        spec = harness.cell_spec(manifest, w["name"])
        mix = Mix(harness.load_json(os.path.join(ROOT, spec["config"]["file"])),
                  harness.load_json(os.path.join(HERE, "traffic",
                                                 f"{w['traffic']}.json")))
        for kind, statics in shapes(mix, mix.unit(0, 1)):
            compiled = compile_for_v5e(kind, statics, one_chip)
            shown = statics[1:] if kind == "lane" else statics
            print(f"{w['name']} {kind} {shown}: {_bytes(compiled)}",
                  flush=True)
    return 0


if __name__ == "__main__":
    np.set_printoptions(linewidth=120)
    sys.exit(main())
