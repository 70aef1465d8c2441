"""``chip_smoke.py``: its phases at a tiny size on the CPU, and its refusal
to report a result anywhere but on a TPU."""
from __future__ import annotations

import jax
import numpy as np
import pytest

import chip_smoke
from repro.core.isa import MachineConfig
from repro.core.programs import make_suite

CFG = MachineConfig(n_threads=8, mem_size=64, max_steps=1024)
SUITE = make_suite(CFG, datasets=1)


def test_main_exits_nonzero_without_tpu(monkeypatch):
    """Past a passing process tier, a host whose JAX has no TPU fails the
    device check: no further phase runs and no result line is printed."""
    def never(*a, **k):
        raise AssertionError("no phase may run after the device check")

    monkeypatch.setattr(chip_smoke, "install_jax_cache", lambda: None)
    monkeypatch.setattr(chip_smoke, "phase_process_tier", lambda *a: {
        "mismatches": 0, "parent_touched_jax": False, "jax_shards": [0],
        "platforms": ["tpu"]})
    for name in ("phase_suite", "phase_service", "phase_grid"):
        monkeypatch.setattr(chip_smoke, name, never)
    assert jax.devices()[0].platform != "tpu"
    with pytest.raises(SystemExit) as ei:
        chip_smoke.main([])
    assert ei.value.code not in (0, None)
    assert "TPU" in str(ei.value.code)


def test_phase_process_tier_tiny():
    rec = chip_smoke.phase_process_tier(SUITE, CFG)
    assert rec["compared"] == 2 * len(SUITE)
    assert rec["mismatches"] == 0
    assert rec["jax_shards"] == [0]                 # the device shard only
    assert rec["host_shards"] == [0, 1]             # numpy still spreads
    assert rec["platforms"] == [jax.devices()[0].platform]


def test_phase_suite_tiny():
    rec = chip_smoke.phase_suite(SUITE, CFG)
    assert rec["compared"] == len(SUITE) and rec["mismatches"] == 0
    assert rec["ok"] == len(SUITE)


def test_phase_service_tiny():
    rec = chip_smoke.phase_service(SUITE, CFG, np.random.default_rng(0), n=8)
    assert rec["compared"] == 8 and rec["mismatches"] == 0
    assert rec["native_batches"] == rec["batches"] >= 1


def test_phase_grid_tiny():
    rec = chip_smoke.phase_grid(SUITE, CFG, seed=0, n_cells=3, n_warps=2,
                                n_sample=2)
    assert rec["warps"] == rec["unique_rows"] == 6
    assert rec["mismatches"] == 0 and rec["cell_mismatches"] == 0
    assert len(rec["cells_sampled"]) == 2
    assert sum(rec["statuses"].values()) == 6
