"""Token-scheduler core + façade + server tests.

Validates Gemini-parity semantics (quota/window/limit —
``docker/kubeshare-gemini-scheduler/launcher.py:75-80``) on both the native
C++ core and the pure-Python spec, cross-checking the two.
"""

import threading
import time

import pytest

from kubeshare_tpu.isolation import protocol, tokensched
from kubeshare_tpu.isolation.tokensched import (
    NativeTokenCore, PyTokenCore, TokenScheduler, make_core)

WINDOW = 1000.0
BASE = 100.0
MIN = 10.0


def cores():
    out = [PyTokenCore(WINDOW, BASE, MIN)]
    try:
        out.append(NativeTokenCore(WINDOW, BASE, MIN))
    except RuntimeError:
        pass
    return out


@pytest.fixture(params=["py", "native"])
def core(request):
    if request.param == "py":
        return PyTokenCore(WINDOW, BASE, MIN)
    try:
        return NativeTokenCore(WINDOW, BASE, MIN)
    except RuntimeError:
        pytest.skip("native core unavailable (no g++)")


def test_native_core_builds():
    """The native library must build in this image (g++ is baked in)."""
    assert isinstance(make_core(), NativeTokenCore)


def test_single_client_grant_and_quota(core):
    core.add_client("a", 0.5, 1.0)
    core.request_token("a")
    name, quota = core.poll(0.0)
    assert name == "a"
    assert quota == BASE  # full base quota available
    assert core.holder() == "a"
    # token is exclusive: nobody else can be granted meanwhile
    core.add_client("b", 0.5, 1.0)
    core.request_token("b")
    assert core.poll(1.0) == float("inf")
    core.release_token("a", 50.0, 50.0)
    name, _ = core.poll(50.0)
    assert name == "b"


def test_stride_shares_converge_to_requests(core):
    """0.75 vs 0.25 requests → device-time shares converge to 3:1."""
    core.add_client("big", 0.75, 1.0)
    core.add_client("small", 0.25, 1.0)
    now = 0.0
    used = {"big": 0.0, "small": 0.0}
    for _ in range(200):
        core.request_token("big")
        core.request_token("small")
        granted = core.poll(now)
        assert isinstance(granted, tuple)
        name, quota = granted
        burst = min(quota, 20.0)
        now += burst
        core.release_token(name, burst, now)
        used[name] += burst
    share = used["big"] / (used["big"] + used["small"])
    assert 0.70 <= share <= 0.80


def _hold(core, name, now, ms):
    """*name* asks, is granted at *now* and releases *ms* later."""
    core.request_token(name)
    granted = core.poll(now)
    assert isinstance(granted, tuple) and granted[0] == name, granted
    core.release_token(name, ms, now + ms)
    return now + ms


@pytest.mark.parametrize("first", ["short", "long"])
def test_virtual_time_forgets_beyond_one_quantum(core, first):
    """Usage from before the other client first asked decides no pick for
    longer than one quantum. ``short`` (request 0.3) warms up alone for 2 s
    of device time, or ``long`` (0.7) does: either way, once both keep
    asking, the one that stayed away is owed ``BASE`` ms of device time at
    most, and after that the split is the requests'."""
    req = {"short": 0.3, "long": 0.7}
    for name, request in req.items():
        core.add_client(name, request, 1.0)
    other = "long" if first == "short" else "short"
    now = 0.0
    for _ in range(40):                     # 2,000 ms alone, half the time
        now = _hold(core, first, now, 50.0) + 50.0
    now += 20_000.0                         # out of every window
    used = {"short": 0.0, "long": 0.0}
    for i in range(400):
        core.request_token("short")
        core.request_token("long")
        name, _ = core.poll(now)
        now += 10.0
        core.release_token(name, 10.0, now)
        used[name] += 10.0
        if used[first] == 0.0:
            # the returning client's head start: one quantum and its side
            # of the 50 ms hold the first made last, not of the 2 s
            assert used[other] <= BASE + 50.0 * req[other] / req[first] \
                + 10.0, (i, used)
    share = used["long"] / (used["long"] + used["short"])
    assert 0.66 <= share <= 0.74, used


def test_a_client_that_kept_asking_is_never_lifted(core):
    """The bound is on what a RETURNING client is owed: two clients that
    keep asking, one of them in steps three times the quantum, get their
    requests' split exactly as before."""
    core.add_client("big", 0.75, 1.0)
    core.add_client("small", 0.25, 1.0)
    now, used = 0.0, {"big": 0.0, "small": 0.0}
    for _ in range(3000):
        core.request_token("big")
        core.request_token("small")
        name, _ = core.poll(now)
        step = 3.0 * BASE if name == "small" else 10.0
        now += step
        # out of the window at once: the cap is not what is tested here
        core.release_token(name, step, now - 2 * WINDOW)
        used[name] += step
    share = used["big"] / (used["big"] + used["small"])
    assert 0.74 <= share <= 0.76, used


def test_limit_cap_enforced(core):
    """limit=0.3 client alone on the chip is held to ≤30% of the window."""
    core.add_client("capped", 0.3, 0.3)
    now = 0.0
    used_total = 0.0
    # Drive for 3 windows of wall time.
    while now < 3 * WINDOW:
        core.request_token("capped")
        granted = core.poll(now)
        if isinstance(granted, tuple):
            _, quota = granted
            now += quota
            core.release_token("capped", quota, now)
            used_total += quota
        else:
            assert granted != float("inf"), "waiter starved with no wake time"
            # idle until the window frees up
            now = max(granted, now + 1.0)
    assert used_total <= 0.3 * (3 * WINDOW) * 1.05
    # window usage itself never exceeded the cap
    assert core.window_usage("capped", now) <= 0.3 * WINDOW + 1e-6


def test_quota_clamped_to_remaining_allowance(core):
    core.add_client("c", 0.5, 0.5)  # cap 500ms of the 1000ms window
    core.request_token("c")
    _, q1 = core.poll(0.0)
    core.release_token("c", 450.0, 450.0)  # 50ms of allowance left
    core.request_token("c")
    granted = core.poll(450.0)
    assert isinstance(granted, tuple)
    assert granted[1] == pytest.approx(50.0, abs=1e-6)


def test_below_min_quota_is_ineligible_with_wake_time(core):
    core.add_client("c", 0.5, 0.5)
    core.request_token("c")
    core.poll(0.0)
    core.release_token("c", 495.0, 495.0)  # 5ms left < MIN
    core.request_token("c")
    wake = core.poll(495.0)
    assert not isinstance(wake, tuple)
    assert wake < float("inf")
    # at the wake time, a grant must be possible
    granted = core.poll(wake + 1e-3)
    assert isinstance(granted, tuple)


def test_usage_expires_from_window(core):
    core.add_client("c", 1.0, 1.0)
    core.request_token("c")
    core.poll(0.0)
    core.release_token("c", 100.0, 100.0)
    assert core.window_usage("c", 100.0) == pytest.approx(100.0)
    assert core.window_usage("c", 600.0) == pytest.approx(100.0)
    assert core.window_usage("c", 1050.0) == pytest.approx(50.0)
    assert core.window_usage("c", 1200.0) == pytest.approx(0.0)


def test_client_validation(core):
    with pytest.raises(ValueError):
        core.add_client("x", 0.0, 1.0)
    with pytest.raises(ValueError):
        core.add_client("x", 0.6, 0.5)  # request > limit
    with pytest.raises(ValueError):
        core.add_client("x", 0.5, 1.5)  # limit > 1
    core.add_client("x", 0.5, 1.0)
    with pytest.raises(ValueError):
        core.add_client("x", 0.5, 1.0)  # duplicate


def test_remove_holder_frees_token(core):
    core.add_client("a", 0.5, 1.0)
    core.add_client("b", 0.5, 1.0)
    core.request_token("a")
    core.request_token("b")
    name, _ = core.poll(0.0)
    core.remove_client(name)
    granted = core.poll(1.0)
    assert isinstance(granted, tuple)
    assert granted[0] != name


def test_cores_agree_on_trace():
    """Drive both cores through one deterministic trace; states must match."""
    try:
        native = NativeTokenCore(WINDOW, BASE, MIN)
    except RuntimeError:
        pytest.skip("native core unavailable")
    py = PyTokenCore(WINDOW, BASE, MIN)
    for c in (native, py):
        c.add_client("a", 0.6, 0.8)
        c.add_client("b", 0.2, 0.4)
    now = 0.0
    for i in range(300):
        for c in (native, py):
            c.request_token("a" if i % 3 else "b")
        gn, gp = native.poll(now), py.poll(now)
        assert type(gn) is type(gp) or (isinstance(gn, tuple) == isinstance(gp, tuple))
        if isinstance(gn, tuple):
            assert gn[0] == gp[0]
            assert gn[1] == pytest.approx(gp[1], abs=1e-6)
            burst = min(gn[1], 37.0)
            now += burst
            native.release_token(gn[0], burst, now)
            py.release_token(gp[0], burst, now)
        else:
            assert gn == pytest.approx(gp, abs=1e-3)
            now = max(now + 1.0, gn if gn < float("inf") else now + 1.0)
        assert native.window_usage("a", now) == pytest.approx(
            py.window_usage("a", now), abs=1e-6)
        assert native.window_usage("b", now) == pytest.approx(
            py.window_usage("b", now), abs=1e-6)


def test_blocking_facade_serializes_holders():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    sched.add_client("a", 0.5, 1.0)
    sched.add_client("b", 0.5, 1.0)
    order: list[str] = []
    lock = threading.Lock()

    def worker(name):
        for _ in range(5):
            sched.acquire(name, timeout=5.0)
            with lock:
                order.append(name)
            sched.release(name, 1.0)

    threads = [threading.Thread(target=worker, args=(n,)) for n in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=10.0)
    assert len(order) == 10
    assert sorted(order.count(n) for n in ("a", "b")) == [5, 5]


def test_renew_preserves_stride_shares():
    """Steady-state renew must yield request-proportional shares.

    Regression: a release-then-acquire pair hands the freed token to
    whoever else waits in the gap, collapsing 0.7/0.3 to round-robin;
    the atomic renew keeps this client in contention.
    """
    sched = TokenScheduler(WINDOW, BASE, MIN)
    sched.add_client("big", 0.7, 1.0)
    sched.add_client("small", 0.3, 1.0)
    used = {"big": 0.0, "small": 0.0}
    lock = threading.Lock()
    budget = 900.0  # total granted ms across both clients (< window cap)

    def worker(name):
        quota = sched.acquire(name, timeout=5.0)
        while True:
            burst = min(quota, 10.0)
            with lock:
                if sum(used.values()) >= budget:
                    break
                used[name] += burst
            time.sleep(burst / 1000.0)  # hold the token for real wall time
            quota = sched.renew(name, burst, timeout=5.0)
        sched.release(name, 0.0)

    threads = [threading.Thread(target=worker, args=(n,)) for n in ("big", "small")]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    share = used["big"] / (used["big"] + used["small"])
    assert 0.62 <= share <= 0.78, share


@pytest.mark.parametrize("big,small", [(0.75, 0.25), (0.5, 0.5)])
def test_renew_or_yield_keeps_shares_at_every_program_boundary(big, small):
    """Two closed-loop holders of LONG programs (each longer than the
    quantum) that never exhaust a quota at the gate: at every program's end
    with the other waiting, ``renew_or_yield`` makes the weighted pick, so
    the shares are the requests', not an alternation."""
    sched = TokenScheduler(10_000.0, 20.0, 2.0)
    sched.add_client("big", big, 1.0)
    sched.add_client("small", small, 1.0)
    used = {"big": 0.0, "small": 0.0}
    stop = threading.Event()

    def worker(name):
        while not stop.is_set():
            sched.acquire(name, timeout=10.0)
            holding = True
            while holding and not stop.is_set():
                time.sleep(0.005)           # a 25 ms program, in 5 ms
                used[name] += 25.0
                if sched.contended(name):
                    holding = sched.renew_or_yield(name, 25.0) is not None
                else:                       # nobody asked: the hold goes on
                    sched.release(name, 25.0)
                    holding = False
            if holding:
                sched.release(name, 0.0)

    threads = [threading.Thread(target=worker, args=(n,), daemon=True)
               for n in used]
    for t in threads:
        t.start()
    time.sleep(1.5)
    stop.set()
    for t in threads:
        t.join(timeout=15.0)
    assert sum(used.values()) >= 2_000.0, used
    share = used["big"] / sum(used.values())
    assert abs(share - big) <= 0.05, used


def test_renew_or_yield_outcomes():
    """Kept: a new quantum, the usage on the books, the waiter still
    waiting. Yielded: None, the waiter granted, the ex-holder's request
    withdrawn (it is not asking: it comes back through acquire)."""
    sched = TokenScheduler(WINDOW, BASE, MIN)
    sched.add_client("a", 0.5, 1.0)
    sched.add_client("b", 0.5, 1.0)
    assert sched.acquire("a", timeout=5.0) == BASE
    assert not sched.contended("a")
    got: list = []
    tb = threading.Thread(
        target=lambda: got.append(sched.acquire("b", timeout=10.0)),
        daemon=True)
    tb.start()
    deadline = time.monotonic() + 5.0
    while not sched.contended("a"):
        assert time.monotonic() < deadline
        time.sleep(0.002)
    assert not sched.contended("b")         # nobody else waits for b
    # b (vtime 0) is behind a's 30 / 0.5: the token goes over
    assert sched.renew_or_yield("a", 30.0) is None
    tb.join(5.0)
    assert got == [BASE] and sched.core.holder() == "b"
    assert sched.window_usage("a") == pytest.approx(30.0)
    assert sched.waiting() == []
    # a asks again and waits; b at 20 / 0.5 stays behind a's 60: it keeps
    ta = threading.Thread(
        target=lambda: got.append(sched.acquire("a", timeout=10.0)),
        daemon=True)
    ta.start()
    while not sched.contended("b"):
        assert time.monotonic() < deadline
        time.sleep(0.002)
    assert sched.renew_or_yield("b", 20.0) == BASE
    assert sched.core.holder() == "b" and sched.waiting() == ["a"]
    assert sched.window_usage("b") == pytest.approx(20.0)
    # at 20 + 50 it is ahead: a's turn
    assert sched.renew_or_yield("b", 50.0) is None
    ta.join(5.0)
    assert got == [BASE, BASE] and sched.core.holder() == "a"
    sched.release("a", 0.0)
    # a lone holder at its window cap loses the token and is not left asking
    sched.add_client("capped", 0.1, 0.1)
    sched.acquire("capped", timeout=5.0)
    assert sched.renew_or_yield("capped", 95.0) is None
    assert sched.core.holder() is None
    assert sched.core.poll(sched.now_ms()) == float("inf")


def test_concurrent_waiters_same_name_fifo():
    """One client = one token stream, but a pipelined connection issues
    gated ops concurrently: same-name waiters must QUEUE and be granted
    strictly in arrival order — every waiter served, no lost grants."""
    sched = TokenScheduler(WINDOW, BASE, MIN)
    sched.add_client("a", 0.5, 1.0)
    sched.add_client("b", 0.5, 1.0)
    sched.acquire("a")  # a holds the token; b's waiters will block
    order: list[str] = []
    errs: list[Exception] = []

    def waiter(tag: str, entered: threading.Event):
        entered.set()
        try:
            sched.acquire("b", timeout=10.0)
            order.append(tag)
            time.sleep(0.02)
            sched.release("b", 1.0)
        except Exception as e:
            errs.append(e)

    threads = []
    for tag in ("first", "second", "third"):
        ev = threading.Event()
        t = threading.Thread(target=waiter, args=(tag, ev))
        t.start()
        ev.wait()
        time.sleep(0.05)  # serialize queue entry so arrival order is known
        threads.append(t)
    sched.release("a", 1.0)
    for t in threads:
        t.join(timeout=10.0)
        assert not t.is_alive()
    assert not errs, errs
    assert order == ["first", "second", "third"]


def test_waiter_errors_when_client_removed():
    """A blocked waiter whose client is removed must error, not hang."""
    sched = TokenScheduler(WINDOW, BASE, MIN)
    sched.add_client("a", 0.5, 1.0)
    sched.add_client("b", 0.5, 1.0)
    sched.acquire("a")  # b will block behind a
    errs: list[Exception] = []

    def waiter():
        try:
            sched.acquire("b")  # no timeout: must still be woken
        except Exception as e:
            errs.append(e)

    t = threading.Thread(target=waiter)
    t.start()
    time.sleep(0.05)
    sched.remove_client("b")
    t.join(timeout=5.0)
    assert not t.is_alive(), "waiter hung after client removal"
    assert errs and "removed" in str(errs[0])


def test_facade_acquire_timeout_cancels():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    sched.add_client("a", 0.5, 1.0)
    sched.add_client("b", 0.5, 1.0)
    sched.acquire("a", timeout=1.0)  # a holds the token
    with pytest.raises(TimeoutError):
        sched.acquire("b", timeout=0.05)
    sched.release("a", 1.0)
    # b's withdrawn request must not have consumed the freed token
    assert sched.core.holder() is None
    # and b can acquire normally afterwards
    assert sched.acquire("b", timeout=1.0) > 0


def test_tcp_server_roundtrip():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    server = tokensched.serve(sched)
    port = server.server_address[1]
    try:
        with protocol.Connection("127.0.0.1", port) as conn:
            conn.call({"op": "register", "name": "p", "request": 0.5, "limit": 1.0})
            reply, _ = conn.call({"op": "acquire", "name": "p"})
            assert reply["quota_ms"] == BASE
            conn.call({"op": "release", "name": "p", "used_ms": 42.0})
            reply, _ = conn.call({"op": "usage", "name": "p"})
            assert reply["used_ms"] == pytest.approx(42.0, abs=5.0)
            assert reply["window_ms"] == WINDOW
        # disconnect cleans the client up
        deadline = time.monotonic() + 2.0
        while sched.core.client_count() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched.core.client_count() == 0
    finally:
        server.shutdown()


def test_tcp_server_error_reply():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    server = tokensched.serve(sched)
    try:
        with protocol.Connection("127.0.0.1", server.server_address[1]) as conn:
            with pytest.raises(RuntimeError, match="unknown op"):
                conn.call({"op": "nope"})
            with pytest.raises(RuntimeError):
                conn.call({"op": "register", "name": "x",
                           "request": 2.0, "limit": 1.0})
    finally:
        server.shutdown()


def test_cores_agree_on_cancel_and_timeout_trace():
    """Drive both cores through a deterministic request/cancel/poll
    trace — grants, wake times, and holder state must match, including
    cancel of an unknown name (silent no-op), cancel of the current
    holder (no effect on the hold), and cancel-then-re-request (the
    façade's acquire-timeout path)."""
    try:
        native = NativeTokenCore(WINDOW, BASE, MIN)
    except RuntimeError:
        pytest.skip("native core unavailable")
    py = PyTokenCore(WINDOW, BASE, MIN)
    for c in (native, py):
        c.add_client("a", 0.5, 1.0)
        c.add_client("b", 0.3, 0.6)
    now = 0.0
    for i in range(200):
        step = i % 10
        for c in (native, py):
            if step in (0, 4):
                c.request_token("a")
            if step in (0, 6):
                c.request_token("b")
            if step == 2:
                c.cancel_request("b")      # withdraw mid-wait
            if step == 3:
                c.cancel_request("ghost")  # unknown: silent no-op
            if step == 5:
                c.cancel_request(c.holder() or "a")  # holder: no effect
        gn, gp = native.poll(now), py.poll(now)
        assert isinstance(gn, tuple) == isinstance(gp, tuple), (i, gn, gp)
        if isinstance(gn, tuple):
            assert gn[0] == gp[0], i
            assert gn[1] == pytest.approx(gp[1], abs=1e-6)
            burst = min(gn[1], 23.0)
            now += burst
            native.release_token(gn[0], burst, now)
            py.release_token(gp[0], burst, now)
        else:
            # identical wake times (both may be inf when nobody waits)
            assert gn == pytest.approx(gp, abs=1e-3), i
            now += 7.0
        assert native.holder() == py.holder(), i
        for name in ("a", "b"):
            assert native.window_usage(name, now) == pytest.approx(
                py.window_usage(name, now), abs=1e-6), (i, name)
