"""The comparison that decides ``correct``.

It compares what the timed entry returned, unit by unit, with the plain
references under ``bench/reference``: every warp's result with the Hanoi
reference run on the same inputs, and every SM cell's schedule with the GTO
reference run on the reference traces.  Both comparisons are exact, so each
number compared is a count of results that differ, with the limit 0.
"""
from __future__ import annotations

import numpy as np

from .generator import seed_words, warp_key
from .reference.hanoi_ref import Machine, run_warp
from .reference.sm_ref import Latencies, schedule


def warp_differs(got, want) -> bool:
    """Whether the program's warp result differs from the reference's in
    any field the benchmark compares."""
    return (got.status.value != want.status or int(got.steps) != want.steps
            or int(got.fuel_left) != want.fuel_left
            or int(got.finished) != want.finished
            or got.error != want.error
            or list(got.trace) != want.trace
            or not np.array_equal(np.asarray(got.regs), want.regs)
            or not np.array_equal(np.asarray(got.preds), want.preds)
            or not np.array_equal(np.asarray(got.mem), want.mem))


def sm_differs(sm, want) -> bool:
    """Whether an SM result differs from the reference schedule."""
    return (list(sm.sm_trace) != want.sm_trace
            or int(sm.steps) != len(want.sm_trace)
            or int(sm.cycles) != want.cycles
            or int(sm.thread_instructions) != want.thread_instructions
            or int(sm.busy_cycles) != want.busy_cycles
            or int(sm.issue_stall_cycles) != want.issue_stall_cycles
            or int(sm.scoreboard_stall_cycles)
            != want.scoreboard_stall_cycles
            or int(sm.memory_stall_cycles) != want.memory_stall_cycles)


class Checker:
    """Reference runs for one configuration, memoized by warp inputs."""

    def __init__(self, mix):
        self.mix = mix
        cfg = mix.config
        self.machine = Machine(**cfg["machine"])
        self.majority_first = bool(cfg["guarantees"]["majority_first"])
        lat = cfg.get("latencies")
        self.latencies = Latencies(**lat) if lat else Latencies()
        self._memo: dict = {}

    def reference(self, warp):
        key = warp_key(warp)
        hit = self._memo.get(key)
        if hit is None:
            hit = run_warp(self.mix.programs[warp.program]["words"],
                           self.machine, mem=warp.mem,
                           lane_ids=warp.lane_ids,
                           majority_first=self.majority_first)
            self._memo[key] = hit
        return hit

    def check(self, outcome) -> dict:
        """``{"warps_differing", "cells_differing", "warps", "cells"}`` for
        one finished unit."""
        unit, raw = outcome.unit, outcome.raw
        warps_bad = cells_bad = n_warps = 0
        if not unit.grid:
            results = list(raw)
            if len(results) != len(unit.cells):
                return {"warps_differing": len(unit.cells),
                        "cells_differing": 0, "warps": len(unit.cells),
                        "cells": 0}
            for cell, got in zip(unit.cells, results):
                n_warps += 1
                warps_bad += warp_differs(got, self.reference(cell[0]))
            return {"warps_differing": warps_bad, "cells_differing": 0,
                    "warps": n_warps, "cells": 0}
        sms = self.mix.sm_results(raw)
        if len(sms) != len(unit.cells):
            n = sum(len(c) for c in unit.cells)
            return {"warps_differing": n, "cells_differing": len(unit.cells),
                    "warps": n, "cells": len(unit.cells)}
        for c, (cell, sm) in enumerate(zip(unit.cells, sms)):
            wants = [self.reference(w) for w in cell]
            n_warps += len(cell)
            if len(sm.warps) != len(cell):
                warps_bad += len(cell)
                cells_bad += 1
                continue
            warps_bad += sum(warp_differs(g, w)
                             for g, w in zip(sm.warps, wants))
            ops = [self.mix.programs[w.program]["words"][:, 0] for w in cell]
            sched = schedule([w.trace for w in wants], ops, self.latencies)
            cells_bad += sm_differs(sm, sched) or \
                self.mix.cell_differs(raw, c, sched)
        return {"warps_differing": warps_bad, "cells_differing": cells_bad,
                "warps": n_warps, "cells": len(unit.cells)}


def sample_units(outcomes: list, how, seed: int) -> list:
    """The window's units the reference checks: all of them, or ``how``
    drawn from the seed, always with the unit that did the most work."""
    if how == "all" or len(outcomes) <= int(how):
        return list(outcomes)
    rng = np.random.default_rng([seed_words(seed), 2 ** 32])
    longest = max(range(len(outcomes)),
                  key=lambda i: outcomes[i].warp_instr)
    rest = [i for i in range(len(outcomes)) if i != longest]
    pick = rng.choice(len(rest), size=int(how) - 1, replace=False)
    chosen = sorted([longest] + [rest[int(i)] for i in pick])
    return [outcomes[i] for i in chosen]
