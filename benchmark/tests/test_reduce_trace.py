"""The trace reduction on a small trace recorded on the v5e (two steps of
a two-layer model of width 128 through the same scopes, PR 23's probe
call), checked in beside this file."""

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))
import reduce_trace  # noqa: E402

TRACE = Path(__file__).resolve().parent / "data" / "tiny_v5e.xplane.pb"


@pytest.fixture(scope="module")
def reduced():
    return reduce_trace.reduce(str(TRACE))


def test_device_plane_and_busy_time(reduced):
    assert reduced["devices"] == 1
    # 2 steps of ~0.17 ms of device ops each, read off the dump by hand
    assert reduced["busy_s"] == pytest.approx(335.6e-6, rel=1e-3)
    ops = reduced["device_ops"]
    assert len(ops) == 10
    # the scopes' totals and the rest first, then single ops by time
    assert ops[0][0].startswith("all ops under the scope bench_attn")
    assert ops[1][0].startswith("all ops under the scope bench_opt")
    assert ops[2][0] == "all ops outside those scopes"
    assert sum(o[1] for o in ops[:3]) == pytest.approx(
        reduced["busy_s"], rel=0.02)     # ops barely overlap on one core
    assert ops[3][1] >= ops[4][1] > 0


def test_scoped_ops_are_found_through_the_metadata(reduced):
    attn, opt = reduced["scopes"]["bench_attn"], reduced["scopes"]["bench_opt"]
    # 2 layers x (forward, dQ, dK/dV) x 2 steps; 26 leaves x 2 steps
    assert attn["ops"]["%branch_0_fun"] == 12
    assert opt["ops"]["%branch_0_fun"] == 52
    assert attn["seconds"] == pytest.approx(77.4e-6, rel=1e-2)
    assert opt["seconds"] == pytest.approx(106.3e-6, rel=1e-2)
    assert "[bench_attn]" in reduced["device_ops"][3][0]


def test_the_gap_between_the_two_steps_is_the_longest(reduced):
    name, seconds = reduced["idle_gaps"][0]
    assert seconds == pytest.approx(6.41e-3, rel=1e-2)
    assert isinstance(name, str) and name


def test_event_scopes_reads_the_wire_format(reduced):
    tables = reduce_trace.event_scopes(str(TRACE))
    assert list(tables) == ["/device:TPU:0"]
    texts = " ".join(tables["/device:TPU:0"].values())
    assert "jit(step)/jvp(bench_attn)/jit(_flash_fwd)" in texts
    assert "jit(step)/bench_opt/jit(_fused_flat)" in texts
