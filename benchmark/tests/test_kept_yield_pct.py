"""``kept_yield_pct`` on the recorded ``usage`` triples: they date from
before the proxy counted its kept holds, so as recorded the reader says
nothing; with the counters written in, it reads what plain arithmetic
gives, and nothing where no hold was kept inside the window."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
FILES = ("usage_tiny_pair.json", "usage_tiny_score_vs_train.json")


def read(run):
    spec = importlib.util.spec_from_file_location(
        "m_kept_yield_pct", BENCH / "metrics" / "kept_yield_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def load(file):
    return json.loads((DATA / file).read_text())


def count(run, kept, yielded, early):
    """Every session keeps ``kept(n)`` holds, hands on ``yielded(n)`` of
    them, and is back too soon after ``early(n)`` of those, by its
    ``n``-th program."""
    for snap in run["usage"].values():
        for sess in snap["chip"]["sessions"].values():
            n = sess["exec_count"]
            sess["kept_count"] = kept(n)
            sess["kept_yielded"] = yielded(n)
            sess["kept_early"] = early(n)


def window(run, key):
    begin, mid = (run["usage"][k]["chip"]["sessions"] for k in ("begin", "mid"))
    return sum(mid[t["pod"]][key] - begin[t["pod"]][key]
               for t in run["tenants"])


@pytest.mark.parametrize("file", FILES)
def test_a_program_without_the_counters_reads_nothing(file):
    assert read(load(file)) is None


@pytest.mark.parametrize("file", FILES)
@pytest.mark.parametrize("key", ["kept_yielded", "kept_early"])
def test_yielded_less_early_over_kept_inside_the_counted_window(file, key):
    """The counters gain from ``begin`` to ``mid`` (a traced run), summed
    over the tenants; a hand-over its holder was back from too soon does
    not count; a session without one of the counters in the report:
    nothing."""
    run = load(file)
    count(run, lambda n: 2 * n, lambda n: n // 2, lambda n: n // 8)
    kept, yielded, early = (window(run, k) for k in
                            ("kept_count", "kept_yielded", "kept_early"))
    assert kept > 0 and early > 0
    assert read(run) == pytest.approx(100.0 * (yielded - early) / kept)
    assert 0.0 < read(run) <= 100.0
    del run["usage"]["begin"]["chip"]["sessions"][
        run["tenants"][-1]["pod"]][key]
    assert read(run) is None


@pytest.mark.parametrize("file", FILES)
def test_no_hold_kept_inside_the_window_reads_nothing(file):
    """``kept_count`` the same at both ends of the window (here: a count
    that stopped before it): no share to give, whatever was kept before."""
    run = load(file)
    count(run, lambda n: 7, lambda n: 3, lambda n: 1)
    assert window(run, "kept_count") == 0
    assert read(run) is None
