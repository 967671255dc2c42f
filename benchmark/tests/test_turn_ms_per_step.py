"""``turn_ms_per_step`` on the recorded ``usage`` triples: they date from
before the shim reported its turn-around, so as recorded the reader says
nothing; with the counter written in, it reads what plain arithmetic
gives: per trainer, its gain over its steps inside the counted window,
averaged over the trainers (a scorer's is left out)."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
FILES = ("usage_tiny_pair.json", "usage_tiny_score_vs_train.json")


def read(run):
    spec = importlib.util.spec_from_file_location(
        "m_turn_ms_per_step", BENCH / "metrics" / "turn_ms_per_step.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def load(file):
    return json.loads((DATA / file).read_text())


def steps(run, t):
    """Steps inside ``[0, counted_s]``, a straddling one by its part."""
    total, prev, end = 0.0, 0.0, run["trace"]["counted_s"]
    for done in t["done"]["done_at_s"]:
        if prev < end:
            total += (min(done, end) - prev) / (done - prev)
        prev = done
    return total


@pytest.mark.parametrize("file", FILES)
def test_a_program_without_the_counter_reads_nothing(file):
    assert read(load(file)) is None


@pytest.mark.parametrize("file", FILES)
def test_turn_around_per_step_averaged_over_the_trainers(file):
    run = load(file)
    per_ms = {t["pod"]: 4.0 + i for i, t in enumerate(run["tenants"])}
    for snap in run["usage"].values():
        for pod, sess in snap["chip"]["sessions"].items():
            sess["turn_ms_total"] = per_ms.get(pod, 0.0) * sess["exec_count"]
    want = []
    for t in run["tenants"]:
        if t["role"] != "train":
            continue
        at = run["usage"]
        execs = (at["mid"]["chip"]["sessions"][t["pod"]]["exec_count"]
                 - at["begin"]["chip"]["sessions"][t["pod"]]["exec_count"])
        want.append(per_ms[t["pod"]] * execs / steps(run, t))
    assert want
    assert read(run) == pytest.approx(sum(want) / len(want))
    # a trainer whose session is not in the report: nothing
    trainer = [t for t in run["tenants"] if t["role"] == "train"][-1]
    del run["usage"]["begin"]["chip"]["sessions"][trainer["pod"]]
    assert read(run) is None
