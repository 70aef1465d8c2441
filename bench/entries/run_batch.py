"""Driver: one request per warp, all in one ``Simulator.run_batch`` call
under the mix's ``mechanism``; a unit is one warp per execution the
configuration lists.  See ``bench/generator.py`` for what a driver holds."""
from __future__ import annotations

from bench.generator import Unit


def unit(mix, seed: int, k: int) -> Unit:
    cells = mix.executions(mix.rng(seed, k))
    out = Unit(k, cells, grid=False)
    out.requests = [mix.request(cell[0], skips=True,
                                name=f"{cell[0].program}/u{k}/x{i}")
                    for i, cell in enumerate(cells)]
    return out


def call(mix, unit: Unit):
    from repro.engine import Simulator
    return Simulator("hanoi_jax").run_batch(unit.requests,
                                            mechanism=mix.mix["mechanism"])


def account(mix, unit: Unit, raw):
    return mix.warps_outcome(unit, raw)
