"""``out_recycled_pct`` on the recorded ``usage`` triples: they date from
before the proxy counted its outputs, so as recorded the reader says
nothing (a parent of PR 35); with the counters written in, it reads what
plain arithmetic gives."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
FILES = ("usage_tiny_pair.json", "usage_tiny_score_vs_train.json")


def read(run):
    spec = importlib.util.spec_from_file_location(
        "m_out_recycled_pct", BENCH / "metrics" / "out_recycled_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def load(file):
    return json.loads((DATA / file).read_text())


@pytest.mark.parametrize("file", FILES)
def test_a_program_without_the_counters_reads_nothing(file):
    assert read(load(file)) is None


@pytest.mark.parametrize("file", FILES)
def test_recycled_over_produced_inside_the_counted_window(file):
    """Every session makes 3 outputs a call and recycles 2 of them after
    its first call: the counters gain from ``begin`` to ``mid`` (a traced
    run), summed over the tenants, so the share is 2/3 of the window's
    outputs; a session without the counters in the report: nothing."""
    run = load(file)
    for snap in run["usage"].values():
        for sess in snap["chip"]["sessions"].values():
            n = sess["exec_count"]
            sess["out_count"] = 3 * n
            sess["out_recycled"] = 2 * max(n - 1, 0)
    begin, mid = (run["usage"][k]["chip"]["sessions"] for k in ("begin", "mid"))
    made = sum(mid[t["pod"]]["out_count"] - begin[t["pod"]]["out_count"]
               for t in run["tenants"])
    recycled = sum(mid[t["pod"]]["out_recycled"]
                   - begin[t["pod"]]["out_recycled"] for t in run["tenants"])
    assert read(run) == pytest.approx(100.0 * recycled / made)
    assert 0.0 < read(run) <= 100.0 * 2 / 3
    del begin[run["tenants"][-1]["pod"]]["out_recycled"]
    assert read(run) is None
