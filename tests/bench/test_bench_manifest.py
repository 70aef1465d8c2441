"""The benchmark's manifest (``BENCHMARK.json``) and the files it names.

Pure data checks, no device: the manifest's shape, the characters of its
names and units, that every metric's ``workloads`` and ``moves`` point at
what exists, that every name resolves to a file found by that name, and
that the table of peaks refuses a device it does not hold.
"""
from __future__ import annotations

import json
import os
import re

import pytest

from bench import peaks
from bench.harness import cell_spec, load_manifest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")
UNIT = re.compile(r"[A-Za-z0-9_/%.\-]{1,16}")
PATH = re.compile(r"[A-Za-z0-9_.\-/]{1,200}")
KEYS = {
    "top": {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"},
    "configs": {"name", "source", "file", "reduced", "why"},
    "workloads": {"name", "config", "traffic", "chips", "why"},
    "end_to_end": {"name", "unit", "better", "bound", "source"},
    "per_layer": {"name", "unit", "better", "source", "layer", "moves"},
}


@pytest.fixture(scope="module")
def manifest():
    return load_manifest(ROOT)


def _line(text: str) -> bool:
    return 1 <= len(text) <= 200 and "\n" not in text and "\t" not in text


def test_manifest_keys_and_sizes(manifest):
    assert set(manifest) == KEYS["top"]
    assert os.path.getsize(os.path.join(ROOT, "BENCHMARK.json")) <= 64 << 10
    assert 1 <= manifest["run_seconds"] <= 51
    assert isinstance(manifest["run_seconds"], int)
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in manifest[section]:
            extra = set(entry) - KEYS[section]
            assert extra <= ({"workloads"} if section in (
                "end_to_end", "per_layer") else set()), (section, extra)
            assert KEYS[section] <= set(entry), (section, entry["name"])


def test_command_and_paths(manifest):
    cmd, paths = manifest["command"], manifest["paths"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    assert 1 <= len(paths) <= 16
    for p in paths:
        assert PATH.fullmatch(p) and not p.startswith("/") and ".." not in p
        assert os.path.isdir(os.path.join(ROOT, p))
    for word in cmd:
        if "/" in word:     # a file of the repo: under the benchmark's paths
            assert any(word.startswith(p + "/") for p in paths), word
            assert os.path.exists(os.path.join(ROOT, word))


def test_names_units_and_lines(manifest):
    seen: dict = {}
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        names = [e["name"] for e in manifest[section]]
        assert len(names) == len(set(names)), section
        for e in manifest[section]:
            assert NAME.fullmatch(e["name"]), e["name"]
            if "unit" in e:
                assert UNIT.fullmatch(e["unit"]), e["unit"]
                assert e["better"] in ("lower", "higher")
            for key in ("why", "layer", "source"):
                if key in e:
                    assert _line(e[key]), (e["name"], key)
        seen[section] = names
    metric_names = seen["end_to_end"] + seen["per_layer"]
    assert len(metric_names) == len(set(metric_names))
    for w in manifest["workloads"]:
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    for c in manifest["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.fullmatch(key)
            assert not re.search(r"(_dim|_rank|hidden|intermediate|"
                                 r"latent|width|head|n_threads)", key)


def test_cells_configs_and_chips(manifest):
    configs = {c["name"] for c in manifest["configs"]}
    used = {w["config"] for w in manifest["workloads"]}
    assert used == configs
    pairs = [(w["config"], w["traffic"]) for w in manifest["workloads"]]
    assert len(pairs) == len(set(pairs))
    four = sum(w["chips"] == 4 for w in manifest["workloads"])
    assert all(w["chips"] in (1, 4) for w in manifest["workloads"])
    assert four <= max(1, len(manifest["workloads"]) // 2)
    for w in manifest["workloads"]:
        assert _line(w["why"])


def test_bounds(manifest):
    for m in manifest["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    setup = [m for m in manifest["end_to_end"] if m["name"] == "setup_s"]
    assert setup and setup[0]["bound"] <= 0.25


def test_every_metric_points_at_what_exists(manifest):
    cells = {w["name"] for w in manifest["workloads"]}
    e2e = {m["name"] for m in manifest["end_to_end"]}
    for m in manifest["per_layer"] + manifest["end_to_end"]:
        assert m["source"] in ("device_trace", "program_span",
                               "program_counter", "host_clock")
        assert set(m.get("workloads", ())) <= cells, m["name"]
    for m in manifest["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", sorted(cells)):
            reported = {x["name"] for x in cell_spec(manifest,
                                                     cell)["end_to_end"]}
            assert m["moves"] in reported, (m["name"], cell)
    layers: dict = {}
    for m in manifest["per_layer"]:
        layers.setdefault(m["name"].split(".")[0], set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values()), layers


def test_every_cell_reports_enough(manifest):
    for w in manifest["workloads"]:
        spec = cell_spec(manifest, w["name"])
        names = {m["name"] for m in spec["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert spec["per_layer"], w["name"]


def test_names_resolve_to_files(manifest):
    for c in manifest["configs"]:
        path = os.path.join(ROOT, c["file"])
        assert c["file"].startswith("bench/configs/")
        with open(path) as f:
            cfg = json.load(f)
        assert cfg["name"] == c["name"]
        assert set(cfg["reduced"]) == set(c["reduced"])
        for ref in cfg["reference"]:
            assert os.path.exists(os.path.join(ROOT, ref))
        assert os.path.exists(os.path.join(
            ROOT, "bench", "programs", f"{cfg['programs']}.json"))
    for w in manifest["workloads"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "traffic", f"{w['traffic']}.json"))
    for m in manifest["end_to_end"] + manifest["per_layer"]:
        assert os.path.exists(os.path.join(
            ROOT, "bench", "metrics", f"{m['name']}.py")), m["name"]


def test_full_check_fits_its_budget(manifest):
    # a later PR may add cells up to 24 under the same run length
    runs = 2 + 14 * 24
    need = runs * (manifest["run_seconds"] + 60) + 24 * 2 * 90 + 1200
    assert need <= 43200


def test_peaks_refuse_an_unknown_device():
    assert peaks.lookup("TPU v5 lite")["hbm_bytes"] == 16e9
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.lookup("TPU v99 imaginary")
