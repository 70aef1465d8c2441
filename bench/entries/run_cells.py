"""Driver: one grid of SM cells, each warp its own request, through
``sm_jax.run_cells`` under the configuration's issue policy.  See
``bench/generator.py`` for what a driver holds."""
from __future__ import annotations

from bench.generator import Unit


def unit(mix, seed: int, k: int) -> Unit:
    cells = mix.grid_cells(mix.rng(seed, k))
    out = Unit(k, cells, grid=True)
    out.requests = [[mix.request(w, skips=False,
                                 name=f"{w.program}/sm{c}/w{i}")
                     for i, w in enumerate(cell)]
                    for c, cell in enumerate(cells)]
    return out


def call(mix, unit: Unit):
    from repro.engine.mechanisms.sm_jax import run_cells
    return run_cells(unit.requests, policy=mix.config["policy"])


def sm_results(mix, raw):
    return raw


def account(mix, unit: Unit, raw):
    return mix.grid_outcome(unit, raw)
