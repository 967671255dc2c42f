"""The control at a size a test run can hold: the plain reference put in
the program's place and computed in int8 has to FAIL the comparison, and
so has each fault planted in it; the reference itself reads nought."""

import importlib.util
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import helpers  # noqa: E402

LIMITS = {k: v["limit"] for k, v in helpers.TINY_LIMITS["numbers"].items()}


@pytest.fixture(scope="module")
def readings(tmp_path_factory):
    root = helpers.make_tree(tmp_path_factory.mktemp("control"))
    helpers.forget_other_trees(root)
    spec = importlib.util.spec_from_file_location(
        "bench_control", root / "benchmark" / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return {seed: mod.readings(root, "tiny-score-vs-train", seed, 3.0,
                               sample_requests=6)
            for seed in (5, 2**31 + 9, 77)}


def fails(numbers: dict) -> list[str]:
    return [k for k, v in numbers.items() if v is None or v > LIMITS[k]]


def test_the_control_fails_the_comparison_on_every_seed(readings):
    for seed, got in readings.items():
        assert "loss_gap" in fails(got["trainer"]["control"]), (seed, got)


def test_each_planted_fault_fails_the_comparison_on_every_seed(readings):
    for seed, got in readings.items():
        faults = got["trainer"]["faults"]
        assert "grad_norm_gap" in fails(faults["half_batch"]), (seed, got)
        assert fails(faults["state_unchanged"]) == ["update_norm_gap"]
        assert faults["state_unchanged"]["update_norm_gap"] == 1.0


def test_the_scorer_control_reads_above_nought(readings):
    for got in readings.values():
        assert got["scorer"]["control"]["score_gap"] > 0
        assert got["scorer"]["control"]["unanswered"] == 0
