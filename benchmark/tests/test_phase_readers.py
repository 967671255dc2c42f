"""The readers of the proxy's phase counters on recorded ``usage``
triples (a traced CPU rehearsal of each tiny cell, cut to what the readers
use), and on the same triples as a program without the counters reports
them: there each reader says nothing."""

import copy
import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"
PHASE_KEYS = ("self_ms_total", "idle_attach_ms_total", "idle_gate_ms_total",
              "idle_proxy_ms_total", "shim_ms_total", "wire_ms_total")

#: what the rehearsal that recorded the triple printed for each reader
RECORDED = {
    "usage_tiny_pair.json": {
        "idle_attach_pct": 31.1801, "idle_gate_pct": 12.5675,
        "idle_proxy_pct": 0.0431, "proxy_self_ms_per_exec": 0.3301,
        "shim_ms_per_step": 3.469, "wire_ms_per_exec": 2.5468},
    "usage_tiny_score_vs_train.json": {
        "idle_attach_pct": 42.4301, "idle_gate_pct": 6.1613,
        "idle_proxy_pct": 0.0431, "proxy_self_ms_per_exec": 0.2926,
        "shim_ms_per_step": 4.2107, "wire_ms_per_exec": 3.2483},
}
CASES = [(f, m) for f, per in RECORDED.items() for m in per]


def reader(name):
    spec = importlib.util.spec_from_file_location(
        "m_" + name, BENCH / "metrics" / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(file):
    return json.loads((DATA / file).read_text())


def gained(run, pod, key):
    at = lambda which: run["usage"][which]["chip"]["sessions"][pod][key]  # noqa: E731
    return at("mid") - at("begin")


@pytest.mark.parametrize("file,metric", CASES)
def test_reader_on_a_recorded_usage_triple(file, metric):
    assert reader(metric).read(load(file)) == pytest.approx(
        RECORDED[file][metric], abs=5e-5)


@pytest.mark.parametrize("file", list(RECORDED))
def test_the_readers_by_hand(file):
    """The same numbers from the triple by plain arithmetic: counters are
    read from ``begin`` to ``mid`` (a traced run), the window is
    ``counted_s``."""
    run = load(file)
    pods = [t["pod"] for t in run["tenants"]]
    window_ms = 1e3 * run["trace"]["counted_s"]
    execs = sum(gained(run, p, "exec_count") for p in pods)
    assert execs > 0
    for metric, key in (("idle_attach_pct", "idle_attach_ms_total"),
                        ("idle_gate_pct", "idle_gate_ms_total"),
                        ("idle_proxy_pct", "idle_proxy_ms_total")):
        by_hand = 100.0 * sum(gained(run, p, key) for p in pods) / window_ms
        assert reader(metric).read(run) == pytest.approx(by_hand)
        assert 0.0 <= by_hand <= 100.0
    for metric, key in (("proxy_self_ms_per_exec", "self_ms_total"),
                        ("wire_ms_per_exec", "wire_ms_total")):
        by_hand = sum(gained(run, p, key) for p in pods) / execs
        assert reader(metric).read(run) == pytest.approx(by_hand)
        assert by_hand > 0.0


@pytest.mark.parametrize("file,metric", CASES)
def test_reader_says_nothing_where_the_program_has_no_such_counter(
        file, metric):
    run = load(file)
    for snap in run["usage"].values():
        for sess in snap["chip"]["sessions"].values():
            for key in PHASE_KEYS:
                sess.pop(key, None)
    assert reader(metric).read(run) is None
    # ...nor where a tenant's session (the last is a trainer in both
    # cells) is not in the report at all
    run = load(file)
    del run["usage"]["begin"]["chip"]["sessions"][run["tenants"][-1]["pod"]]
    assert reader(metric).read(run) is None


def test_an_untraced_run_reads_the_whole_window():
    run = load("usage_tiny_pair.json")
    whole = copy.deepcopy(run)
    whole["trace"] = None
    del whole["proxy"]["mid"]
    pods = [t["pod"] for t in run["tenants"]]
    by_hand = 100.0 * sum(
        whole["usage"]["end"]["chip"]["sessions"][p]["idle_gate_ms_total"]
        - whole["usage"]["begin"]["chip"]["sessions"][p]["idle_gate_ms_total"]
        for p in pods) / (1e3 * whole["window_s"])
    assert reader("idle_gate_pct").read(whole) == pytest.approx(by_hand)
