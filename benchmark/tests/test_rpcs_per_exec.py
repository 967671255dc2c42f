"""``rpcs_per_exec`` on the recorded ``usage`` triples: they date from
before the proxy counted its requests, so as recorded the reader says
nothing; with the counter written in, it reads what plain arithmetic
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
        "m_rpcs_per_exec", BENCH / "metrics" / "rpcs_per_exec.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def load(file):
    return json.loads((DATA / file).read_text())


@pytest.mark.parametrize("file", FILES)
def test_a_program_without_the_counter_reads_nothing(file):
    assert read(load(file)) is None


@pytest.mark.parametrize("file", FILES)
@pytest.mark.parametrize("per_call,setup", [(1, 40), (6, 3)])
def test_requests_over_executions_inside_the_counted_window(
        file, per_call, setup):
    """A session that made ``setup`` requests before its first execution
    and ``per_call`` a call reads ``per_call``: counters are differences
    from ``begin`` to ``mid`` (a traced run), summed over the tenants."""
    run = load(file)
    for snap in run["usage"].values():
        for sess in snap["chip"]["sessions"].values():
            sess["rpc_count"] = setup + per_call * sess["exec_count"]
    assert read(run) == pytest.approx(per_call)
    # one tenant's session calls usage once inside the window: above 1
    pod = run["tenants"][0]["pod"]
    run["usage"]["mid"]["chip"]["sessions"][pod]["rpc_count"] += 1
    execs = sum(
        run["usage"]["mid"]["chip"]["sessions"][t["pod"]]["exec_count"]
        - run["usage"]["begin"]["chip"]["sessions"][t["pod"]]["exec_count"]
        for t in run["tenants"])
    assert read(run) == pytest.approx(per_call + 1.0 / execs)
    # a tenant whose session is not in the report: nothing
    del run["usage"]["begin"]["chip"]["sessions"][run["tenants"][-1]["pod"]]
    assert read(run) is None
