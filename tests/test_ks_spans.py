"""``scripts/ks_spans.py`` on a synthetic trace: every idle piece of the
chip gets the phase its events give it, the phases sum to the idle time,
and the device's axis is put on the host's by an offset measured from the
trace itself, not assumed."""

import importlib.util
from pathlib import Path

import pytest

SCRIPT = Path(__file__).resolve().parent.parent / "scripts" / "ks_spans.py"
MS = 1_000_000
#: the device's axis runs this far ahead of the host's in the synthetic
#: trace; no constant of the script knows it
AHEAD_NS = 300_000


def ks_spans():
    spec = importlib.util.spec_from_file_location("ks_spans", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def ev(name, session, lo_ms, hi_ms, **stats):
    return {"name": "ks." + name, "lo": lo_ms * MS, "hi": hi_ms * MS,
            "session": session, **stats}


def op(lo_ms, hi_ms, name="%fusion.1 = f32[8]"):
    """An op at host times ``[lo_ms, hi_ms]``, as the device line has it."""
    return (lo_ms * MS + AHEAD_NS, hi_ms * MS + AHEAD_NS, name)


def trace():
    """Host times in ms. A trainer ``a`` and a scorer ``b``; a program the
    trace caught without its bracket at each end, a ``put`` whose device
    work ends a gap, and one op that nothing explains."""
    ks = [
        # a's first program: its execute began before the trace did
        ev("device", "a", 10, 40), ev("dispatch", "a", 10, 10.4),
        ev("barrier", "a", 10.4, 40),
        # b asked at 38, was granted at 40.8; its first op starts with
        # its bracket (the least lag: the offset)
        ev("rpc", "b", 38, 46, op="execute"), ev("gate_wait", "b", 38.1, 40.8),
        ev("device", "b", 41, 45), ev("dispatch", "b", 41, 41.3),
        # b's put holds the device lock; its device work starts at 47
        ev("rpc", "b", 45.8, 48.2, op="put"),
        ev("dlock_wait", "b", 45.9, 46, op="put", bytes=131072),
        ev("xfer", "b", 46, 48, op="put", bytes=131072),
        # a comes back at 49, still holding the token
        ev("rpc", "a", 49, 91, op="execute"), ev("device", "a", 50, 90),
        ev("dispatch", "a", 50, 50.5),
        ev("rpc", "b", 93.5, 96.5, op="execute"), ev("device", "b", 94, 96),
        ev("dispatch", "b", 94, 94.2),
    ]
    ops = [op(1, 3),                            # bracket lost at the start
           op(10.6, 20), op(20.2, 39.5),        # a
           op(41, 44.8),                        # b
           op(47, 47.5, "%copy.1 = s32[32768]"),    # the put's
           op(51, 70), op(70.1, 89),            # a
           op(92, 93),                          # nothing's
           op(94.5, 95.9),                      # b
           op(97, 99)]                          # bracket lost at the end
    return ks, ops


EXPECTED_MS = {
    # (phase, session): ms
    ("trace_edge", ""): 7.0 + 1.0,      # [3, 10] (no rpc), [96, 97]
    ("dispatch", "a"): 0.4 + 0.5,       # [10, 10.4], [50, 50.5]
    ("launch", "a"): 0.2 + 0.5,         # [10.4, 10.6], [50.5, 51]
    ("in_program", "a"): 0.2 + 0.1,     # [20, 20.2], [70, 70.1]
    ("barrier", "a"): 0.5 + 1.0,        # [39.5, 40], [89, 90]
    ("gate", "b"): 0.8,                 # [40, 40.8]: asked before a's end
    ("proxy", "b"): 0.2 + 0.5,          # [40.8, 41], [93.5, 94]
    ("barrier", "b"): 0.2 + 0.1,        # [44.8, 45], [95.9, 96]
    ("xfer", "b"): 2.0,                 # [45, 47]
    ("attach", "a"): 1.5,               # [47.5, 49]
    ("proxy", "a"): 1.0,                # [49, 50]
    ("unattributed", ""): 2.0,          # [90, 92]
    ("attach", "b"): 0.5,               # [93, 93.5]
    ("dispatch", "b"): 0.2,             # [94, 94.2]
    ("launch", "b"): 0.3,               # [94.2, 94.5]
}


def test_every_piece_of_idle_has_its_phase():
    got = ks_spans().split(*trace())
    span_ms = 99.0 - 1.0
    assert got["span_s"] == pytest.approx(span_ms / 1e3)
    idle_ms = sum(EXPECTED_MS.values())
    assert got["idle_pct"] == pytest.approx(100.0 * idle_ms / span_ms)
    phases = got["idle_by_phase"]
    for (phase, who), ms in EXPECTED_MS.items():
        assert phases[phase]["by_session_pct"].get(who) == pytest.approx(
            100.0 * ms / span_ms), (phase, who)
    # nothing else, and the phases sum to the idle time
    assert {(p, w) for p, v in phases.items()
            for w in v["by_session_pct"]} == set(EXPECTED_MS)
    assert sum(v["pct"] for v in phases.values()) == pytest.approx(
        got["idle_pct"])
    assert sum(v["ms"] for v in phases.values()) == pytest.approx(idle_ms)
    assert got["programs_by_session"] == {"a": 2, "b": 2}
    assert got["ks_events"]["ks.xfer"] == 1
    assert got["longest_gaps"][0]["gap_ms"] == pytest.approx(7.6)
    assert got["longest_gaps"][0]["trace_edge_ms"] == pytest.approx(7.0)


def test_the_offset_is_measured_from_the_programs_first_ops():
    """The least lag of a first op behind its bracket (b's, 0 on the host's
    axis), whatever the stray ops before a bracket offer as a first op: the
    op nothing explains lies 1.7 ms before b's second bracket."""
    mod = ks_spans()
    ks, ops = trace()
    got = mod.split(ks, ops)["clock"]
    assert got["offset_us"] == pytest.approx(AHEAD_NS / 1e3)
    # b's second program ends 0.1 ms before its bracket: the offset could
    # be that much lower and every op would still fit
    assert got["lowest_offset_us"] == pytest.approx(AHEAD_NS / 1e3 - 100.0)
    assert got["programs"] == 4
    # beyond the offset: b 0, b 0.5, a 0.6, a 1.0 ms
    assert got["first_op_us"]["p10"] == pytest.approx(0.0)
    assert got["first_op_us"]["max"] == pytest.approx(1000.0)
    # the same trace with the device's axis BEHIND the host's
    behind = [(lo - 2 * AHEAD_NS, hi - 2 * AHEAD_NS, n) for lo, hi, n in ops]
    again = mod.split(ks, behind)
    assert again["clock"]["offset_us"] == pytest.approx(-AHEAD_NS / 1e3)
    assert again["idle_by_phase"]["unattributed"]["pct"] == pytest.approx(
        100.0 * 2.0 / 98.0)


def test_a_trace_without_brackets_is_all_trace_edge():
    """The parent's events or none: nothing is lost, nothing is guessed."""
    _, ops = trace()
    got = ks_spans().split([], ops)
    assert got["clock"]["offset_us"] == 0.0
    phases = got["idle_by_phase"]
    assert phases["trace_edge"]["pct"] == pytest.approx(got["idle_pct"])
    assert all(v["pct"] == 0.0 for p, v in phases.items()
               if p != "trace_edge")


def steady(ahead_ns, programs):
    """``(ks, ops)``: back-to-back programs ``[(session, bracket_ms,
    device_ms)]``, each program's ops 0.2 ms into its bracket (0.1 ms for
    the first, the least lag) and a 2 ms host gap between brackets; the
    trace caught the first program's ops but not its bracket, and the
    last program's bracket but not its ops (the device's line ends
    first)."""
    ks, ops, t = [], [], 0.0
    for i, (who, bracket_ms, device_ms) in enumerate(programs):
        lag = 0.1 if i == 1 else 0.2
        if i:
            ks += [ev("rpc", who, t - 1.0, t + bracket_ms + 0.1,
                      op="execute"),
                   ev("device", who, t, t + bracket_ms),
                   ev("dispatch", who, t, t + 0.05)]
        for k in range(4 if i < len(programs) - 1 else 0):
            lo = t + lag + k * device_ms / 4    # four ops, 10 us apart
            ops.append((lo * MS + ahead_ns,
                        (lo + device_ms / 4 - 0.01) * MS + ahead_ns, "%f"))
        t += bracket_ms + 2.0
    return ks, sorted(ops)


@pytest.mark.parametrize("ahead_ns", [400_000, -400_000, 3_000_000])
def test_a_steady_pair_is_not_taken_a_period_off(ahead_ns):
    """Two tenants' identical steps alternate: a shift of one period
    holds one step MORE (the first, whose bracket the trace lost, fills
    the second's, and so on to the last bracket, whose ops the trace
    lost). The shift nearest 0 is the device's."""
    ks, ops = steady(ahead_ns, [("ab"[i % 2], 66.0, 63.0)
                                for i in range(13)])
    got = ks_spans().split(ks, ops)
    assert got["clock"]["offset_us"] == pytest.approx(
        ahead_ns / 1e3 + 100.0)
    assert got["idle_by_phase"]["unattributed"]["pct"] == 0.0
    assert got["programs_by_session"] == {"a": 5, "b": 5}


def test_a_long_program_finds_an_offset_far_from_0():
    """The device's axis 116 ms behind the host's (as one traced run of
    the long-document cell read it): short programs would fit a shift
    near 0 by chance, the long one only the real one."""
    programs = [("chat", 5.0, 2.4)] * 3 + [("docs", 668.0, 664.0)] + [
        ("chat", 5.0 + i % 3 * 4.0, 2.4 + i % 3 * 3.0) for i in range(40)]
    ks, ops = steady(-116_000_000, programs)
    got = ks_spans().split(ks, ops)
    assert got["clock"]["offset_us"] == pytest.approx(-116_000.0 + 100.0)
    assert got["idle_by_phase"]["unattributed"]["pct"] == 0.0


def test_a_kernel_is_timed_by_its_own_ops_not_by_those_that_read_it():
    """An op that reads a kernel's result, or calls the computation that
    holds it, names the kernel among its operands: it is not the kernel."""
    ks, ops = trace()
    ops = sorted(ops + [
        op(20.3, 20.5, "%moe_rows.3 = bf16[32768,2048] custom-call(...)"),
        op(20.6, 20.9, "%fusion.7 = bf16[8192,2048] fusion(%moe_rows.3)"),
        op(21.0, 21.4, "%branch_0_fun.2 = (bf16[8]) call(), to_apply="
                       "%moe_rows.4")])
    got = ks_spans().split(ks, ops)["pallas"]
    assert list(got) == ["moe_rows"]
    assert got["moe_rows"]["events"] == 1
    assert got["moe_rows"]["seconds"] == pytest.approx(0.2e-3)
