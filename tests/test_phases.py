"""The sharing path measured where the work happens: the proxy's phase
counters in ``usage`` (idle split, self time, the shim's report), the
``phase`` helper of ``obs/trace.py`` and its ``ks.*`` events in a
profiler trace.

The idle split is driven on a patched ``proxy._now_ms``: the test owns
the clock, so every gap has one right answer.
"""

import subprocess
import sys
import threading
import time
from pathlib import Path

import numpy as np
import pytest

from kubeshare_tpu.isolation import protocol
from kubeshare_tpu.isolation import proxy as proxy_mod
from kubeshare_tpu.isolation.client import ProxyClient, ShimClock
from kubeshare_tpu.isolation.proxy import _PHASE_KEYS, ChipProxy
from kubeshare_tpu.isolation.tokensched import TokenScheduler
from kubeshare_tpu.obs import trace as obs_trace

REPO = Path(__file__).resolve().parent.parent
IDLE_KEYS = ("idle_attach_ms_total", "idle_gate_ms_total",
             "idle_proxy_ms_total")
DEVICE_MS = 50.0


def wait_for(cond, what, timeout=10.0):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {what}"
        time.sleep(0.002)


class Rig:
    """A proxy on a clock the test owns. Every program takes DEVICE_MS of
    it; nothing else moves it but ``at``."""

    def __init__(self, monkeypatch):
        self.t = 1000.0
        monkeypatch.setattr(proxy_mod, "_now_ms", lambda: self.t)
        # no idle release unless a test asks: hand-overs happen by renew
        self.proxy = ChipProxy(scheduler=TokenScheduler(1000.0, 100.0, 10.0),
                               idle_release_ms=1e12)
        run = self.proxy._run_to_completion

        def run_on_clock(fn, args, sync_out):
            outs = run(fn, args, sync_out)
            self.t += DEVICE_MS
            return outs

        self.proxy._run_to_completion = run_on_clock
        self.proxy.serve()
        # asks for usage on a connection no tenant's call can block
        self.clients = {"observer": ProxyClient(
            "127.0.0.1", self.proxy.port, "observer", 0.01, 0.01)}

    def at(self, t):
        assert t >= self.t
        self.t = float(t)

    def tenant(self, name):
        c = ProxyClient("127.0.0.1", self.proxy.port, name, 0.5, 1.0)
        x = c.put(np.ones((4, 4), np.float32))
        exe = c.compile(lambda a: a * 2.0, x)
        self.clients[name] = c
        return lambda: exe(x)

    def counters(self, name):
        return self.clients["observer"].usage()["chip"]["sessions"][name]

    def idle(self, name):
        return tuple(self.counters(name)[k] for k in IDLE_KEYS)

    def close(self):
        # the proxy first: a test that failed half-way may have left a
        # call blocked at the gate, and only closing the scheduler ends it
        self.proxy.close()
        for c in self.clients.values():
            c._conn.close()


@pytest.fixture
def rig(monkeypatch):
    r = Rig(monkeypatch)
    yield r
    r.close()


def test_first_program_of_a_chip_counts_no_gap(rig):
    step = rig.tenant("a")
    rig.at(5000)                # however long the chip sat before it
    step()
    c = rig.counters("a")
    assert rig.idle("a") == (0.0, 0.0, 0.0)
    assert c["exec_count"] == 1 and c["exec_ms_total"] == DEVICE_MS
    assert set(_PHASE_KEYS) <= set(c)


def test_dispatch_and_barrier_split_the_device_time(rig, monkeypatch):
    """``exec_ms`` in two parts taken on the same stamps: until the call
    of the executable returns, and from there until the barrier's host
    read returns. Per session their sums are ``exec_ms_total`` exactly."""
    step_a, step_b = rig.tenant("a"), rig.tenant("b")
    single = rig.proxy._single_fn

    def slow_dispatch(exe):
        fn = single(exe)

        def dispatch(*args):
            rig.t += 3.0
            return fn(*args)
        return dispatch

    monkeypatch.setattr(rig.proxy, "_single_fn", slow_dispatch)
    step_a()
    step_a()
    sess_a = rig.proxy._session("a")
    with sess_a.lock:           # a's token idles out (the watchdog's act)
        sess_a.holding = False
    rig.proxy.scheduler.release("a", sess_a.used_ms)
    step_b()
    for name, execs in (("a", 2), ("b", 1)):
        c = rig.counters(name)
        assert c["exec_count"] == execs
        assert c["dispatch_ms_total"] == 3.0 * execs
        assert c["barrier_ms_total"] == DEVICE_MS * execs
        assert (c["dispatch_ms_total"] + c["barrier_ms_total"]
                == c["exec_ms_total"])
    # a program whose dispatch fails is all dispatch, and still adds up
    monkeypatch.setattr(rig.proxy, "_single_fn", lambda exe: _refuse)
    with pytest.raises(RuntimeError, match="refused at dispatch"):
        step_b()
    c = rig.counters("b")
    assert c["dispatch_ms_total"] + c["barrier_ms_total"] == c["exec_ms_total"]
    assert c["barrier_ms_total"] == DEVICE_MS


def _refuse(*args):
    raise RuntimeError("refused at dispatch")


def test_same_session_turn_around_is_all_attach(rig):
    step = rig.tenant("a")
    step()                      # ends at 1050
    rig.at(1080)                # the tenant comes back 30 ms later
    step()
    assert rig.idle("a") == (30.0, 0.0, 0.0)
    # it still held the token: no wait at the gate to take out, and on
    # this clock the handler itself takes no time
    assert rig.counters("a")["self_ms_total"] == 0.0


def test_waiter_granted_after_the_holders_renew_is_gate(rig):
    step_a, step_b = rig.tenant("a"), rig.tenant("b")
    step_a()                    # a holds the token; program ends at 1050
    rig.at(1060)
    tb = threading.Thread(target=step_b, daemon=True)
    tb.start()                  # b asks at 1060 and waits: a holds
    sess_a = rig.proxy._session("a")
    sess_b = rig.proxy._session("b")
    wait_for(lambda: sess_b.busy, "b at the gate")
    wait_for(lambda: "b" in rig.proxy.scheduler.waiting(),
             "b waiting for the token")
    sess_a.used_ms = sess_a.quota_ms        # a's quantum is spent
    rig.at(1090)                # a comes back after 40 ms and must renew
    ta = threading.Thread(target=step_a, daemon=True)
    ta.start()
    tb.join(10.0)
    assert not tb.is_alive()
    # the gap [1050, 1090] is b's: nobody asked for 10 ms, then b asked
    # and the chip sat idle 30 ms until a's renew handed the token over
    assert rig.idle("b") == (10.0, 30.0, 0.0)
    # the 30 ms at the gate are a wait, not the handler's own time
    assert rig.counters("b")["self_ms_total"] == 0.0
    # b's program ended at 1140; a, blocked in renew since 1090, gets the
    # token when b's idles out (what the watchdog does, done by hand on
    # this clock): all of that gap is the gate's
    with sess_b.lock:
        sess_b.holding = False
    rig.at(1160)
    rig.proxy.scheduler.release("b", sess_b.used_ms)
    ta.join(10.0)
    assert not ta.is_alive()
    assert rig.idle("a") == (0.0, 20.0, 0.0)
    assert rig.counters("a")["self_ms_total"] == 0.0    # 70 ms in renew


@pytest.mark.parametrize("a_used_ms", [0.0, 300.0],
                         ids=["holder-behind", "holder-ahead"])
def test_a_contended_program_end_asks_the_weighted_pick(rig, a_used_ms):
    """Where a program ends while another tenant waits, the scheduler
    says at once who holds, the holder standing in the pick with what it
    has used. A holder the pick prefers keeps the token (its burst); else
    the waiter's program starts at that stamp, under the holder's
    turn-around, and nobody idles out or comes back first."""
    step_a, step_b = rig.tenant("a"), rig.tenant("b")
    sess_a, sess_b = rig.proxy._session("a"), rig.proxy._session("b")
    for _ in range(3):          # b has used 150 ms at request 0.5
        step_b()
    with sess_b.lock:           # and gives the token back, by hand
        sess_b.holding = False
    rig.proxy.scheduler.release("b", sess_b.used_ms)
    step_a()                    # a holds the token; ends at 1200
    sess_a.used_ms += a_used_ms     # what it used before, in this hold
    started, finish = threading.Event(), threading.Event()
    run = rig.proxy._run_to_completion

    def in_flight(fn, args, sync_out):
        if not started.is_set():
            started.set()
            assert finish.wait(10.0)
        return run(fn, args, sync_out)

    rig.proxy._run_to_completion = in_flight
    ta = threading.Thread(target=step_a, daemon=True)
    ta.start()
    assert started.wait(10.0)   # a's second program is on the chip
    tb = threading.Thread(target=step_b, daemon=True)
    tb.start()                  # b asks while it runs, and waits
    wait_for(lambda: "b" in rig.proxy.scheduler.waiting(),
             "b waiting for the token")
    finish.set()                # a's program ends at 1250
    ta.join(10.0)
    assert not ta.is_alive()
    # either way a's usage went on the scheduler's books at the boundary
    assert rig.clients["a"].usage()["used_ms"] == 2 * DEVICE_MS + a_used_ms
    assert sess_a.used_ms == 0.0
    if a_used_ms:
        # a at (100 + 300) / 0.5 is ahead of b's 150 / 0.5: b's turn, and
        # its program started where a's ended: no gap for anyone to own
        tb.join(10.0)
        assert not tb.is_alive()
        assert not sess_a.holding and sess_b.holding
        assert rig.idle("b") == (0.0, 0.0, 0.0)
    else:
        # a at 100 / 0.5 is behind b's 150 / 0.5: it keeps the token, with
        # a new quantum, and b waits until a idles out
        assert sess_a.holding and sess_a.quota_ms == 100.0
        assert "b" in rig.proxy.scheduler.waiting()
        rig.at(1280)
        with sess_a.lock:       # the watchdog's act, by hand
            sess_a.holding = False
        rig.proxy.scheduler.release("a", sess_a.used_ms)
        tb.join(10.0)
        assert not tb.is_alive()
        assert rig.idle("b") == (0.0, 30.0, 0.0)


def test_a_wait_for_a_contended_token_is_never_charged(rig):
    """What a tenant is charged (``exec_ms_total``, and ``used_ms``, what
    its next renew reports to the gate) grows by device time only, however
    long its execute waited for a token the neighbour held."""
    step_a, step_b = rig.tenant("a"), rig.tenant("b")
    step_a()                    # a holds the token; program ends at 1050
    tb = threading.Thread(target=step_b, daemon=True)
    tb.start()
    wait_for(lambda: "b" in rig.proxy.scheduler.waiting(),
             "b waiting for the token")
    rig.at(1450)                # b has waited 400 ms, eight programs long
    sess_a, sess_b = rig.proxy._session("a"), rig.proxy._session("b")
    with sess_a.lock:           # a's token idles out (the watchdog's act)
        sess_a.holding = False
    rig.proxy.scheduler.release("a", sess_a.used_ms)
    tb.join(10.0)
    assert not tb.is_alive()
    b = rig.counters("b")
    assert b["exec_count"] == 1 and b["exec_ms_total"] == DEVICE_MS
    assert sess_b.used_ms == DEVICE_MS
    assert rig.clients["b"].usage()["used_ms"] == 0.0   # nothing released yet
    # the wait is on the books as the chip's idle time at the gate
    assert rig.idle("b") == (0.0, 400.0, 0.0)
    assert b["self_ms_total"] == 0.0


def test_dlock_held_elsewhere_is_proxy(rig):
    step = rig.tenant("a")
    step()                      # ends at 1050
    rig.proxy._dlock.acquire()  # as a neighbour's put or compile holds it
    rig.at(1070)
    t = threading.Thread(target=step, daemon=True)
    t.start()
    wait_for(lambda: rig.proxy._dlock._waiters, "the execute at _dlock")
    rig.at(1095)
    rig.proxy._dlock.release()
    t.join(10.0)
    assert not t.is_alive()
    assert rig.idle("a") == (20.0, 0.0, 25.0)
    # the 25 ms behind _dlock are a wait too
    assert rig.counters("a")["self_ms_total"] == 0.0


def test_an_inline_inputs_put_is_the_handlers_own_time(rig, monkeypatch):
    """A host leaf that rode in on the execute goes to the device under
    the program's _dlock hold, BEFORE the device_start stamp: the chip's
    idle gap counts it as the proxy's, the handler as its own, and the
    tenant is not charged for it."""
    c = ProxyClient("127.0.0.1", rig.proxy.port, "a", 0.5, 1.0)
    rig.clients["a"] = c
    w = c.put(np.ones((4,), np.float32))
    exe = c.compile(lambda w, x: w + x, w, np.zeros((4,), np.float32))
    exe(w, np.ones((4,), np.float32))           # ends at 1050
    real = rig.proxy._jax.device_put

    def put_on_clock(x, device=None):
        rig.t += 7.0            # both leaves go in one call
        return real(x, device)

    with monkeypatch.context() as m:
        m.setattr(rig.proxy._jax, "device_put", put_on_clock)
        rig.at(1080)
        out = exe(w, np.ones((4,), np.float32))
    np.testing.assert_array_equal(c.get(out), np.full((4,), 2.0))
    a = rig.counters("a")
    assert rig.idle("a") == (30.0, 0.0, 7.0)
    assert a["self_ms_total"] == 7.0
    assert a["exec_count"] == 2 and a["exec_ms_total"] == 2 * DEVICE_MS
    assert rig.proxy._session("a").used_ms == 2 * DEVICE_MS
    assert a["inline_in_total"] == 2 and a["inline_out_total"] == 2


def test_the_three_parts_equal_the_gap(rig):
    """Whatever order the stamps come in, the parts are each >= 0 and sum
    to the gap (bounds are clamped into it)."""
    p = rig.proxy
    p._last_device_end = 100.0
    for arrived, granted, start in ((130, 130, 131), (40, 120, 125),
                                    (40, 60, 110), (105, 300, 140),
                                    (150, 120, 160), (100, 100, 100)):
        parts = p._split_idle({"arrived": arrived, "granted": granted},
                              start)
        assert all(x >= 0.0 for x in parts), parts
        assert sum(parts) == start - 100.0, (arrived, granted, start)
    assert p._split_idle({}, 500.0) == (0.0, 0.0, 0.0)


# -- real clock ---------------------------------------------------------------

@pytest.fixture
def proxy():
    p = ChipProxy(scheduler=TokenScheduler(1000.0, 100.0, 10.0))
    p.serve()
    yield p
    p.close()


def connect(proxy, name):
    return ProxyClient("127.0.0.1", proxy.port, name, 0.5, 1.0)


def test_usage_grows_monotonically_and_self_time_is_never_negative(proxy):
    """Two concurrent sessions, one fetching every result and one
    threading its state: every counter of every session only grows, so no
    execution ever added a negative self time, wait or gap."""
    stop = threading.Event()
    errors: list = []

    def plain():
        try:
            with connect(proxy, "plain") as c:
                x = c.put(np.ones((64, 64), np.float32))
                exe = c.compile(lambda a: a @ a * 0.01, x)
                while not stop.is_set():
                    out = exe(x)
                    c.get(out)
                    c.free(out)
        except Exception as exc:
            errors.append(exc)

    def stepper():
        try:
            with connect(proxy, "stepper") as c:
                carry = c.put(np.zeros((32,), np.float32))
                exe = c.compile(lambda s: s + 1.0, carry)
                while not stop.is_set():
                    carry = exe(carry, donate=True)
        except Exception as exc:
            errors.append(exc)

    threads = [threading.Thread(target=plain),
               threading.Thread(target=stepper)]
    for t in threads:
        t.start()
    seen: dict = {}
    with connect(proxy, "observer") as obs:
        t_start = time.monotonic()

        def enough():
            # both have run a while and closed a call
            return (time.monotonic() - t_start > 1.0 and all(
                seen.get(n, {}).get("exec_count", 0) > 2
                and seen[n]["self_ms_total"] > 0.0
                for n in ("plain", "stepper")))

        while (time.monotonic() - t_start < 30.0 and not errors
               and not enough()):
            for name, c in obs.usage()["chip"]["sessions"].items():
                prev = seen.get(name, {})
                for k in _PHASE_KEYS + ("exec_ms_total", "exec_count",
                                        "rpc_count", "inline_in_total",
                                        "inline_out_total"):
                    assert c[k] >= prev.get(k, 0.0), (name, k, prev, c)
                seen[name] = c
            time.sleep(0.005)
    stop.set()
    for t in threads:
        t.join(20.0)
    assert not errors, errors
    for name in ("plain", "stepper"):
        c = seen[name]
        assert c["exec_count"] > 2
        assert c["self_ms_total"] > 0.0
        for k in _PHASE_KEYS:
            assert c[k] >= 0.0, (name, k, c[k])
    assert seen["observer"]["exec_count"] == 0
    # every request is counted, whatever the op: the observer only asked
    # for usage; "plain" made a free of its own for each execute
    assert seen["observer"]["rpc_count"] > 2
    assert seen["plain"]["rpc_count"] >= 2 * seen["plain"]["exec_count"]
    assert seen["stepper"]["inline_in_total"] == 0
    assert (seen["stepper"]["inline_out_total"]
            == seen["stepper"]["exec_count"])
    assert all(seen["observer"][k] == 0.0 for k in _PHASE_KEYS)


def test_execute_with_and_without_the_shims_key(proxy):
    with connect(proxy, "c") as c:
        x = c.put(np.ones((4, 4), np.float32))
        exe = c.compile(lambda a: a + 1.0, x)
        mine = lambda: c.usage()["chip"]["sessions"]["c"]  # noqa: E731
        bare = {"op": "execute", "name": "c", "exec_id": exe._exec_id,
                "args": [x.handle]}
        # an old client's request: served as ever, nothing reported
        reply, _ = c._conn.call(dict(bare))
        assert reply["ok"] and len(reply["handles"]) == 1
        assert mine()["exec_count"] == 1
        assert mine()["shim_ms_total"] == 0.0
        assert mine()["wire_ms_total"] == 0.0
        assert mine()["turn_ms_total"] == 0.0
        # not the shim's report: ignored, the call is served
        reply, _ = c._conn.call(dict(bare, **{protocol.SHIM_KEY: "junk"}))
        assert reply["ok"] and mine()["shim_ms_total"] == 0.0
        # the shim's: its own time is added as sent; the round trip less
        # the handler time of the previous execute is the wire
        handler_ms = proxy._session("c").last_handler_ms
        assert handler_ms is not None and handler_ms > 0.0
        reply, _ = c._conn.call(dict(bare, **{protocol.SHIM_KEY: {
            "shim_ms": 1.5, "rtt_ms": handler_ms + 0.25}}))
        assert reply["ok"]
        assert mine()["shim_ms_total"] == 1.5
        assert mine()["wire_ms_total"] == pytest.approx(0.25)
        # a report without the turn-around adds nothing to it; one with it
        # adds it as sent
        assert mine()["turn_ms_total"] == 0.0
        handler_ms = proxy._session("c").last_handler_ms
        reply, _ = c._conn.call(dict(bare, **{protocol.SHIM_KEY: {
            "shim_ms": 0.0, "rtt_ms": handler_ms, "turn_ms": 7.25}}))
        assert reply["ok"] and mine()["turn_ms_total"] == 7.25
        # the client sends it by itself on every execute: the CPU time
        # its thread spent in the shim's sections since the execute before
        with c.shim_clock:
            burn(0.005)
            c.get(exe(x))
        first = mine()
        assert first["shim_ms_total"] >= 1.5 + 5.0
        time.sleep(0.06)        # the tenant's own work, and a wait: neither
        with c.shim_clock:
            c.get(exe(x))
        after = mine()
        assert after["exec_count"] == 6
        assert 0.0 < after["shim_ms_total"] - first["shim_ms_total"] < 50.0
        assert first["wire_ms_total"] == pytest.approx(0.25)
        assert after["wire_ms_total"] > first["wire_ms_total"]
        # ...but the wall clock between the reply and the next send holds
        # both, and the usage round trips in between
        assert after["turn_ms_total"] - first["turn_ms_total"] >= 60.0


def burn(cpu_s):
    """Spend ``cpu_s`` of this thread's CPU time."""
    until = time.thread_time() + cpu_s
    while time.thread_time() < until:
        pass


def test_shim_clock_counts_the_threads_cpu_time_and_no_wait():
    clock = ShimClock()
    with clock:
        burn(0.02)
        with clock:                         # nested: counted once
            burn(0.01)
        time.sleep(0.03)                    # a put's reply: a wait
        number, t_send, first = clock.send()
        time.sleep(0.05)                    # the execute round trip
        clock.replied(number, t_send, t_send + 0.05)
        burn(0.01)
    burn(0.03)                              # outside: the tenant's own
    number2, t_send2, second = clock.send()
    assert (number, number2) == (1, 2)
    assert 30.0 <= first["shim_ms"] < 40.0 and "rtt_ms" not in first
    assert "turn_ms" not in first
    assert 10.0 <= second["shim_ms"] < 20.0
    assert second["rtt_ms"] == 50.0
    # from the reply coming in to this send, wall clock: the 40 ms of CPU
    # after it, in the shim and out of it
    assert second["turn_ms"] == pytest.approx(
        (t_send2 - (t_send + 0.05)) * 1e3, abs=1e-3)
    assert second["turn_ms"] >= 40.0
    # a reply that came in long before the caller asked for it: the
    # round trip ends where it came in
    time.sleep(0.02)
    clock.replied(number2, t_send2, t_send2 + 0.004)
    report = clock.send()[2]
    assert 3.9 <= report["rtt_ms"] <= 4.1
    assert report["turn_ms"] >= 20.0 - 4.0
    # only the execute before this one has a round trip to report
    clock.send()
    report = clock.send()[2]
    assert "rtt_ms" not in report and "turn_ms" not in report
    # another thread's section is its own
    t = threading.Thread(target=lambda: (clock.__enter__(), burn(0.01),
                                         clock.__exit__(None, None, None)))
    t.start()
    t.join()
    assert 10.0 <= clock.send()[2]["shim_ms"] < 20.0


# -- the phase helper ---------------------------------------------------------

def test_phase_records_the_old_spans_only_with_a_trace_id():
    tracer = obs_trace.install_tracer(obs_trace.Tracer())
    try:
        with obs_trace.phase("rpc", "ns/pod", "tid-1", op="execute"):
            pass
        with obs_trace.phase("gate_wait", "ns/pod", "tid-1", chip="c0"):
            pass
        with obs_trace.phase("rpc", "ns/pod", op="execute"):
            pass
        with obs_trace.phase("gate_wait", "ns/pod"):
            pass
        with obs_trace.phase("device", "ns/pod", "tid-1"):
            pass                # no span of the timeline has that name
        spans = tracer.spans()
    finally:
        obs_trace.uninstall_tracer()
    assert [s.name for s in spans] == ["execute", "token-grant"]
    assert all(s.trace_id == "tid-1" for s in spans)
    assert spans[0].attrs == {"op": "execute", "client": "ns/pod"}
    assert spans[1].attrs == {"chip": "c0", "client": "ns/pod"}
    # one clock: CLOCK_MONOTONIC milliseconds, whichever tracer asks
    now = time.monotonic() * 1000.0
    for s in spans:
        assert now - 5000.0 < s.start_ms <= s.end_ms <= now + 1.0
    assert abs(obs_trace.Tracer().now_ms() - obs_trace.now_ms()) < 50.0


def test_phase_does_not_import_jax():
    code = (
        "import sys\n"
        "from kubeshare_tpu.obs import trace\n"
        "t = trace.install_tracer(trace.Tracer())\n"
        "with trace.phase('rpc', 's', 'tid', op='execute'): pass\n"
        "with trace.phase('device', 's'): pass\n"
        "assert [s.name for s in t.spans()] == ['execute']\n"
        "assert 'jax' not in sys.modules, 'phase imported jax'\n"
        "print('ok')\n")
    out = subprocess.run([sys.executable, "-c", code], cwd=str(REPO),
                         capture_output=True, text=True, timeout=60)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "ok"


_PROFILED = r'''
import glob, json, sys, time
import numpy as np
import jax
from jax.profiler import ProfileData
from kubeshare_tpu.isolation.client import ProxyClient
from kubeshare_tpu.isolation.proxy import ChipProxy
from kubeshare_tpu.isolation.tokensched import TokenScheduler

out = sys.argv[1]
proxy = ChipProxy(scheduler=TokenScheduler(1000.0, 100.0, 10.0))
proxy.serve()
with ProxyClient("127.0.0.1", proxy.port, "ns/pod-0", 0.5, 1.0) as c:
    x = c.put(np.ones((8, 8), np.float32))
    exe = c.compile(lambda a: a @ a, x)
    exe(x)
    lo = time.monotonic_ns() // 1000
    jax.profiler.start_trace(out)
    for _ in range(3):
        proxy._session("ns/pod-0").used_ms = 1e9    # quantum spent: renew
        exe(x)
    y = c.put(np.ones((16, 16), np.float32))
    c.get(y)
    jax.profiler.stop_trace()
    hi = time.monotonic_ns() // 1000
proxy.close()
path = sorted(glob.glob(out + "/**/*.xplane.pb", recursive=True))[-1]
found = {}
for plane in ProfileData.from_file(path).planes:
    for line in plane.lines:
        for ev in line.events:
            if ev.name.startswith("ks."):
                found.setdefault(ev.name, []).append(
                    {"_lo": ev.start_ns, "_hi": ev.start_ns + ev.duration_ns,
                     **{k: v for k, v in ev.stats}})
print(json.dumps({"lo": lo, "hi": hi, "events": found}))
'''


def test_profiler_trace_holds_ks_events_with_session_and_mono_us(tmp_path):
    """One CPU-backend profiler run, in a process of its own (the
    profiler's state is the process's) and under its own time limit."""
    out = subprocess.run(
        [sys.executable, "-c", _PROFILED, str(tmp_path / "trace")],
        cwd=str(REPO), capture_output=True, text=True, timeout=240)
    assert out.returncode == 0, out.stderr[-3000:]
    import json
    got = json.loads(out.stdout.strip().splitlines()[-1])
    events = got["events"]
    assert {"ks.rpc", "ks.gate_wait", "ks.dlock_wait",
            "ks.device"} <= set(events), sorted(events)
    device = events["ks.device"]
    assert len(device) == 3
    for stats in device:
        assert stats["session"] == "ns/pod-0"
        assert got["lo"] <= int(stats["mono_us"]) <= got["hi"]
    assert len(events["ks.gate_wait"]) == 3
    assert all(s["session"] == "ns/pod-0" for s in events["ks.gate_wait"])
    executes = [s for s in events["ks.rpc"] if s.get("op") == "execute"]
    assert len(executes) == 3
    assert all(s["session"] == "ns/pod-0" for s in executes)
    # ks.device in two: the executable's call, then the barrier's read,
    # one after the other inside it
    for name in ("ks.dispatch", "ks.barrier"):
        assert len(events[name]) == 3, name
        assert all(s["session"] == "ns/pod-0" for s in events[name])
    for dev, disp, bar in zip(*(sorted(events[n], key=lambda s: s["_lo"])
                                for n in ("ks.device", "ks.dispatch",
                                          "ks.barrier"))):
        assert dev["_lo"] <= disp["_lo"] <= disp["_hi"] <= bar["_lo"]
        assert bar["_lo"] <= bar["_hi"] <= dev["_hi"]
    # a put and a get: the device lock asked for and held, by op and size
    xfers = events["ks.xfer"]
    assert [(s["op"], s["bytes"]) for s in xfers] == [("put", 1024),
                                                      ("get", 1024)]
    assert all(s["session"] == "ns/pod-0" for s in xfers)
    waits = [s for s in events["ks.dlock_wait"] if "op" in s]
    assert [(s["op"], s["bytes"]) for s in waits] == [("put", 1024),
                                                      ("get", 1024)]
    for wait, xfer in zip(waits, xfers):
        assert wait["_hi"] <= xfer["_lo"]
