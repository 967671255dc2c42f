"""``dispatch_ms_per_exec`` on the recorded ``usage`` triples: they date
from before the proxy split ``exec_ms_total``, so as recorded the reader
says nothing; with the counter written in, it reads what plain arithmetic
gives."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
FILES = ("usage_tiny_pair.json", "usage_tiny_score_vs_train.json")


def read(run):
    spec = importlib.util.spec_from_file_location(
        "m_dispatch_ms_per_exec", BENCH / "metrics" / "dispatch_ms_per_exec.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def load(file):
    return json.loads((DATA / file).read_text())


@pytest.mark.parametrize("file", FILES)
def test_a_program_without_the_counter_reads_nothing(file):
    assert read(load(file)) is None


@pytest.mark.parametrize("file", FILES)
def test_dispatch_time_over_executions_inside_the_counted_window(file):
    """Each session's dispatch time is a third of its ``exec_ms_total``:
    the reader gives the tenants' gain in it, from ``begin`` to ``mid`` (a
    traced run), over their executions there, the observer's left out."""
    run = load(file)
    for snap in run["usage"].values():
        for sess in snap["chip"]["sessions"].values():
            sess["dispatch_ms_total"] = sess["exec_ms_total"] / 3.0
    at = run["usage"]
    pods = [t["pod"] for t in run["tenants"]]

    def gained(key):
        return sum(at["mid"]["chip"]["sessions"][p][key]
                   - at["begin"]["chip"]["sessions"][p][key] for p in pods)

    assert gained("exec_count") > 0
    assert read(run) == pytest.approx(
        gained("exec_ms_total") / 3.0 / gained("exec_count"))
    # a tenant whose session is not in the report: nothing
    del at["begin"]["chip"]["sessions"][pods[-1]]
    assert read(run) is None
