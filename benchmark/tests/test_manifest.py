"""The manifest names only files that exist, every name and unit keeps to
the allowed characters, and each metric's reader declares what its entry
says."""

import importlib.util
import json
import re
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent
MANIFEST = json.loads((REPO / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRICS = MANIFEST["end_to_end"] + MANIFEST["per_layer"]


def reader(name):
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_top_level_keys_and_command():
    assert set(MANIFEST) == {"command", "paths", "run_seconds", "configs",
                             "workloads", "end_to_end", "per_layer"}
    assert MANIFEST["paths"] == ["benchmark"]
    assert (REPO / MANIFEST["command"][1]).is_file()
    assert 1 <= MANIFEST["run_seconds"] <= 51
    assert (REPO / "BENCHMARK.json").stat().st_size <= 64 * 1024


def test_names_and_units_keep_to_the_allowed_characters():
    names = [c["name"] for c in MANIFEST["configs"]]
    names += [w["name"] for w in MANIFEST["workloads"]]
    names += [w["traffic"] for w in MANIFEST["workloads"]]
    names += [m["name"] for m in METRICS]
    assert all(NAME.match(n) for n in names), names
    assert all(UNIT.match(m["unit"]) for m in METRICS)
    assert all(m["better"] in ("lower", "higher") for m in METRICS)
    assert len({m["name"] for m in METRICS}) == len(METRICS)
    for w in MANIFEST["workloads"] + MANIFEST["configs"]:
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]


@pytest.mark.parametrize("cell", MANIFEST["workloads"],
                         ids=lambda w: w["name"])
def test_a_cell_names_only_files_that_exist(cell):
    configs = {c["name"]: c for c in MANIFEST["configs"]}
    cfg_path = REPO / configs[cell["config"]]["file"]
    assert cfg_path.is_file() and BENCH in cfg_path.parents
    cfg = json.loads(cfg_path.read_text())
    assert (REPO / cfg["reference"]).is_file()
    mix = json.loads((BENCH / "mixes" / f"{cell['traffic']}.json")
                     .read_text())
    assert mix["tenants"] and int(mix["chips"]) == cell["chips"]
    for t in mix["tenants"]:
        assert (BENCH / "tenants" / f"{t['role']}.py").is_file()
        assert (BENCH / "checks" / f"{t['role']}.py").is_file()
        assert NAME.match(t["name"])
    limits = json.loads((BENCH / "limits" / f"{cell['name']}.json")
                        .read_text())
    assert limits["numbers"]
    reported = [m for m in MANIFEST["end_to_end"]
                if "workloads" not in m or cell["name"] in m["workloads"]]
    assert "setup_s" in [m["name"] for m in reported] and len(reported) >= 2
    assert any(cell["name"] in m.get("workloads", [cell["name"]])
               for m in MANIFEST["per_layer"])


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_a_metric_has_a_reader_that_declares_the_same(metric):
    mod = reader(metric["name"])
    assert callable(mod.read)
    assert mod.UNIT == metric["unit"] and mod.SOURCE == metric["source"]
    if metric in MANIFEST["per_layer"]:
        assert mod.KIND == "per_layer"
        assert mod.LAYER == metric["layer"] and mod.MOVES == metric["moves"]
        moved = [m for m in MANIFEST["end_to_end"]
                 if m["name"] == metric["moves"]]
        assert moved, "moves names an end-to-end metric"
        cells = [w["name"] for w in MANIFEST["workloads"]]
        for cell in metric.get("workloads", cells):
            assert cell in moved[0].get("workloads", cells)
    else:
        assert mod.KIND == "end_to_end" and mod.BETTER == metric["better"]
        assert 0 < metric["bound"] <= 0.1


def test_peaks_are_keyed_by_device_kind_with_a_source():
    peaks = json.loads((BENCH / "peaks.json").read_text())["kinds"]
    assert set(peaks) == {"TPU v5 lite"}
    v5e = peaks["TPU v5 lite"]
    assert v5e["bf16_flops_per_s"] == 197e12
    assert v5e["hbm_bytes_per_s"] == 819e9 and v5e["source"]
