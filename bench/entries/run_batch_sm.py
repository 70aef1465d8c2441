"""Driver: one request per SM, replicated over the configuration's
``warps_per_sm`` warps, all in one ``Simulator.run_batch`` call under the
mix's ``mechanism``.  The reference check also compares the ``SimResult``
the façade assembled around each SM.  See ``bench/generator.py`` for what a
driver holds."""
from __future__ import annotations

from bench.generator import Unit


def unit(mix, seed: int, k: int) -> Unit:
    cells = mix.grid_cells(mix.rng(seed, k))
    out = Unit(k, cells, grid=True)
    meta = {"sm_warps": int(mix.config["warps_per_sm"]),
            "sm_policy": mix.config["policy"], "sm_inner": "hanoi_jax"}
    out.requests = [mix.request(cell[0], skips=False,
                                name=f"{cell[0].program}/sm{c}", meta=meta)
                    for c, cell in enumerate(cells)]
    return out


def call(mix, unit: Unit):
    from repro.engine import Simulator
    return Simulator("hanoi_jax").run_batch(unit.requests,
                                            mechanism=mix.mix["mechanism"])


def sm_results(mix, raw):
    return [r.meta["sm"] for r in raw]


def cell_differs(mix, raw, c: int, want) -> bool:
    got = raw[c]
    return int(got.steps) != len(want.sm_trace) or \
        list(got.trace) != [(pc, m) for _, pc, m in want.sm_trace]


def account(mix, unit: Unit, raw):
    return mix.grid_outcome(unit, raw)
