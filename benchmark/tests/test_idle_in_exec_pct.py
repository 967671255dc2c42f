"""``idle_in_exec_pct`` on a run dict: as recorded (no trace directory)
it says nothing; with ``programtime.py``'s list of the window's programs
in place, it reads the brackets' length less their device time, over the
traced window."""

import importlib.util
import json
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = Path(__file__).resolve().parent / "data"


def read(run):
    spec = importlib.util.spec_from_file_location(
        "m_idle_in_exec_pct", BENCH / "metrics" / "idle_in_exec_pct.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read(run)


def traced(tmp_path, programs, window_s=3.0):
    """A traced run whose trace directory already holds the programs'
    reading (``programtime.CACHE``), so no trace is parsed."""
    (tmp_path / "programtime.json").write_text(
        json.dumps({"programs": programs}))
    return {"trace": {"window_s": window_s, "busy_s": 1.0},
            "proxy": {"trace": {"dir": str(tmp_path)}}}


def program(start_s, bracket_s, device_s, session="trainer/pod-0"):
    return {"session": session, "start_mono_s": start_s,
            "end_mono_s": start_s + bracket_s, "device_s": device_s,
            "scopes": {}}


@pytest.mark.parametrize("file", ["usage_tiny_pair.json",
                                  "usage_tiny_score_vs_train.json"])
def test_a_run_without_a_trace_directory_reads_nothing(file):
    run = json.loads((DATA / file).read_text())
    assert read(run) is None
    run["trace"] = None
    assert read(run) is None


def test_bracket_less_device_time_over_the_window(tmp_path):
    run = traced(tmp_path, [program(10.0, 0.120, 0.110),
                            program(10.13, 0.005, 0.0025, "scorer/pod-0"),
                            program(10.14, 0.118, 0.1155)])
    assert read(run) == pytest.approx(
        100.0 * (0.010 + 0.0025 + 0.0025) / 3.0)


def test_a_window_without_a_whole_program_reads_nothing(tmp_path):
    assert read(traced(tmp_path, [])) is None


def test_a_trace_with_no_op_on_a_chip_reads_nothing(tmp_path):
    """A CPU rehearsal: the brackets are there, the chip's plane is not."""
    run = traced(tmp_path, [program(10.0, 0.120, 0.0)])
    run["trace"]["busy_s"] = 0.0
    assert read(run) is None
