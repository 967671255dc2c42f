"""Chip-proxy + client + pod-manager integration tests.

The proxy runs on the CPU backend here — the identical code path serves the
real chip (the proxy is backend-agnostic; ``chip_smoke.py`` and
``benchmark/run.py`` are the on-hardware proof). These are the tests the
reference never had for its Gemini stack (SURVEY §4: the de-facto
integration test was a manual harness).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeshare_tpu.isolation import protocol
from kubeshare_tpu.isolation import proxy as proxy_mod
from kubeshare_tpu.isolation.client import ExecutionGate, ProxyClient
from kubeshare_tpu.isolation.podmgr import PodManager
from kubeshare_tpu.isolation.proxy import ChipProxy
from kubeshare_tpu.isolation.tokensched import TokenScheduler, serve

WINDOW = 1000.0
BASE = 100.0
MIN = 10.0


@pytest.fixture
def proxy():
    p = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN))
    p.serve()
    yield p
    p.close()


def connect(proxy, name, request=0.5, limit=1.0, memory=0):
    return ProxyClient("127.0.0.1", proxy.port, name, request, limit,
                       memory=memory)


def test_put_get_free_roundtrip(proxy):
    with connect(proxy, "c") as c:
        arr = np.arange(12, dtype=np.float32).reshape(3, 4)
        buf = c.put(arr)
        assert buf.shape == (3, 4) and buf.dtype == "float32"
        np.testing.assert_array_equal(c.get(buf), arr)
        assert c.usage()["hbm_used"] == arr.nbytes
        c.free(buf)
        assert c.usage()["hbm_used"] == 0


def test_hbm_cap_enforced_at_put(proxy):
    with connect(proxy, "c", memory=100) as c:
        c.put(np.zeros(20, np.float32))  # 80 bytes
        with pytest.raises(RuntimeError, match="HBM cap"):
            c.put(np.zeros(20, np.float32))  # would be 160


def test_compile_execute_device_resident(proxy):
    with connect(proxy, "c") as c:
        x = np.ones((4, 4), np.float32)
        exe = c.compile(lambda a, b: {"y": a @ b, "s": jnp.sum(a)}, x, x)
        bx = c.put(x)
        out = exe(bx, bx)
        assert set(out) == {"y", "s"}
        np.testing.assert_allclose(c.get(out["y"]), x @ x)
        assert float(c.get(out["s"])) == 16.0
        # outputs are device-resident: feed them back without download
        out2 = exe(out["y"], bx)
        np.testing.assert_allclose(c.get(out2["y"]), (x @ x) @ x)


def test_execute_charges_and_donate_frees(proxy):
    with connect(proxy, "c") as c:
        x = np.ones((8, 8), np.float32)
        bx = c.put(x)
        base = c.usage()["hbm_used"]
        exe = c.compile(lambda a: a * 2.0, bx)
        out = exe(bx)
        assert c.usage()["hbm_used"] == base + x.nbytes
        out2 = exe(out, donate=True)  # frees `out` after success
        assert c.usage()["hbm_used"] == base + x.nbytes
        np.testing.assert_allclose(c.get(out2), x * 4.0)


def test_hbm_cap_enforced_at_execute(proxy):
    x = np.zeros((16, 16), np.float32)  # 1024 bytes
    with connect(proxy, "c", memory=1600) as c:
        bx = c.put(x)
        exe = c.compile(lambda a: a + 1.0, bx)
        with pytest.raises(RuntimeError, match="HBM cap"):
            exe(bx)  # output another 1024 > cap
        # failed execute must not leak the pre-charge
        assert c.usage()["hbm_used"] == x.nbytes


def test_training_loop_through_proxy(proxy):
    """A linear-regression loop entirely through the proxy converges."""
    rng = np.random.default_rng(0)
    w_true = rng.normal(size=(4,)).astype(np.float32)
    xs = rng.normal(size=(64, 4)).astype(np.float32)
    ys = xs @ w_true

    def step(w, xb, yb):
        def loss(w):
            return jnp.mean((xb @ w - yb) ** 2)
        l, g = jax.value_and_grad(loss)(w)
        return w - 0.1 * g, l

    with connect(proxy, "trainer") as c:
        w = c.put(np.zeros(4, np.float32))
        bx, by = c.put(xs), c.put(ys)
        exe = c.compile(step, w, bx, by)
        for _ in range(60):
            w, l = exe(w, bx, by)
        assert float(c.get(l)) < 1e-3
        np.testing.assert_allclose(c.get(w), w_true, atol=1e-2)
        u = c.usage()
        assert u["exec_count"] == 60
        assert u["exec_ms_total"] > 0


@pytest.mark.slow  # XLA-compile-heavy: transformer chunk + pallas export
def test_transformer_flash_trains_through_proxy(proxy):
    """The long-context family rides the sharing runtime: a transformer
    train step whose attention is the PALLAS FLASH KERNEL ships through
    the proxy (jax.export round-trip included) and converges — the two
    halves of the framework in one test."""
    import optax

    from kubeshare_tpu.models import transformer
    from kubeshare_tpu.ops.flash_attention import flash_attention

    key = jax.random.PRNGKey(0)
    params = transformer.init(key, seq_len=32, vocab=64, dim=32, layers=1)
    tokens = np.asarray(jax.random.randint(
        jax.random.fold_in(key, 1), (2, 33), 0, 64))
    batch = (tokens[:, :-1], tokens[:, 1:])
    optimizer = optax.adam(1e-2)
    flash = lambda q, k, v: flash_attention(q, k, v, block_q=16,
                                            block_k=16)

    def train_chunk(carry, xb, yb):
        p, opt = carry
        loss, grads = jax.value_and_grad(
            lambda p: transformer.loss_fn(p, (xb, yb), attn_fn=flash))(p)
        updates, opt = optimizer.update(grads, opt, p)
        return (optax.apply_updates(p, updates), opt), loss

    with connect(proxy, "lc-trainer") as c:
        carry = (c.put_tree(jax.tree_util.tree_map(np.asarray, params)),
                 c.put_tree(jax.tree_util.tree_map(
                     np.asarray, optimizer.init(params))))
        bx, by = c.put(batch[0]), c.put(batch[1])
        exe = c.compile(train_chunk, carry, bx, by)

        def step(carry):
            new_carry, loss = exe(carry, bx, by)
            c.free(carry)       # the state threads; the batch persists
            return new_carry, loss

        carry, first = step(carry)
        l0 = float(c.get(first))
        for _ in range(40):
            carry, loss = step(carry)
            c.free(loss)
        carry, last = step(carry)
        assert float(c.get(last)) < l0
        assert c.usage()["exec_ms_total"] > 0


def test_session_is_connection_bound(proxy):
    """A connection can only act on the session it registered (no quota /
    buffer theft by naming another client)."""
    with connect(proxy, "victim") as victim:
        bv = victim.put(np.zeros(10, np.float32))
        with protocol.Connection("127.0.0.1", proxy.port) as rogue:
            with pytest.raises(RuntimeError, match="not registered"):
                rogue.call({"op": "free", "name": "victim",
                            "handles": [bv.handle]})
        assert victim.usage()["hbm_used"] == 40


def test_host_uploads_freed_per_call(proxy):
    """Host-array args auto-uploaded by a call don't accumulate on the
    proxy."""
    x = np.ones((8, 8), np.float32)
    with connect(proxy, "c") as c:
        exe = c.compile(lambda a, b: a + b, x, x)
        bx = c.put(x)
        out1 = exe(bx, x)   # b uploaded per call
        used1 = c.usage()["hbm_used"]
        out2 = exe(bx, x)
        used2 = c.usage()["hbm_used"]
        assert used2 - used1 == x.nbytes  # only out2 remains, not the upload
        np.testing.assert_allclose(c.get(out2), 2 * x)
        c.free(out1, out2)


def test_disconnect_frees_session(proxy):
    # resumable sessions park for detach_grace_ms before the watchdog
    # reclaims them; shrink the grace so the drop lands within the poll
    proxy.detach_grace_ms = 100.0
    c = connect(proxy, "gone")
    c.put(np.zeros(10, np.float32))
    c._conn.close()  # hard drop, no unregister
    deadline = time.monotonic() + 2.0
    while proxy.scheduler.core.client_count() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert proxy.scheduler.core.client_count() == 0
    # name is reusable after cleanup
    with connect(proxy, "gone") as c2:
        assert c2.usage()["hbm_used"] == 0


def test_legacy_disconnect_frees_immediately(proxy):
    """A ``reconnect=None`` client requests no resume token, so its hard
    drop frees the session without waiting out the detach grace."""
    c = ProxyClient("127.0.0.1", proxy.port, "legacy", request=0.5,
                    limit=1.0, reconnect=None)
    assert "resume" not in c.features
    c.put(np.zeros(10, np.float32))
    c._conn.close()
    deadline = time.monotonic() + 2.0
    while proxy.scheduler.core.client_count() and time.monotonic() < deadline:
        time.sleep(0.01)
    assert proxy.scheduler.core.client_count() == 0


def _greedy_client(proxy, name, request, stop, used_out, nloops=20):
    with connect(proxy, name, request=request, limit=1.0) as c:
        x = np.ones((192, 192), np.float32)
        bx = c.put(x)

        def burn(a):
            def body(_, acc):
                return acc @ a / 192.0
            return jax.lax.fori_loop(0, nloops, body, a)

        exe = c.compile(burn, bx)
        while not stop.is_set():
            bx = exe(bx, donate=True)
        usage = c.usage()
        used_out[name] = usage["exec_ms_total"]
        used_out[name + ".count"] = usage["exec_count"]


@pytest.mark.parametrize("big,small,nloops", [
    (0.75, 0.25, 20), (0.5, 0.5, 20), (0.75, 0.25, 200), (0.75, 0.25, 1000)],
    ids=["3to1", "even", "3to1-long-programs", "3to1-programs-over-a-quantum"])
def test_colocated_shares_follow_requests(proxy, big, small, nloops):
    """Two greedy closed-loop clients of plain executes → device-time
    shares (``exec_ms_total``) within 0.15 of the requested ones: 3:1,
    and even for equal requests (every execute renews at the gate, so
    neither holds the chip past its quota). The same 3:1 for programs
    longer than the smallest quota, and longer than a whole quantum: where
    one ends with the other tenant waiting, the weighted pick says who
    holds, and it is not an alternation."""
    stop = threading.Event()
    used: dict = {}
    threads = [
        threading.Thread(target=_greedy_client,
                         args=(proxy, "big", big, stop, used, nloops)),
        threading.Thread(target=_greedy_client,
                         args=(proxy, "small", small, stop, used, nloops)),
    ]
    for t in threads:
        t.start()
    time.sleep(2.5)
    stop.set()
    for t in threads:
        t.join(timeout=15.0)
    share = used["big"] / (used["big"] + used["small"])
    assert abs(share - big) <= 0.15, used
    if nloops > 20:
        # the programs were as long as claimed
        per_program = {n: used[n] / used[n + ".count"] for n in ("big", "small")}
        assert min(per_program.values()) >= (MIN if nloops == 200 else BASE), \
            per_program


def test_limit_cap_holds_solo_client(proxy):
    """A lone limit=0.3 client gets ≤ ~30% of wall time on the chip."""
    stop = threading.Event()
    used: dict = {}

    def run():
        with connect(proxy, "capped", request=0.3, limit=0.3) as c:
            x = np.ones((192, 192), np.float32)
            bx = c.put(x)

            def burn(a):
                def body(_, acc):
                    return acc @ a / 192.0
                return jax.lax.fori_loop(0, 20, body, a)

            exe = c.compile(burn, bx)
            while not stop.is_set():
                bx = exe(bx, donate=True)
            used["ms"] = c.usage()["exec_ms_total"]

    t = threading.Thread(target=run)
    t.start()
    start = time.monotonic()
    time.sleep(2.5)
    stop.set()
    t.join(timeout=20.0)
    elapsed_ms = (time.monotonic() - start) * 1000.0
    assert used["ms"] / elapsed_ms <= 0.40, used


def test_oversized_put_keeps_session(proxy, monkeypatch):
    """A pre-send frame-size refusal must not tear down the connection —
    the stream never desynced, and closing would drop every device buffer."""
    with connect(proxy, "c") as c:
        buf = c.put(np.ones(4, np.float32))
        monkeypatch.setattr(protocol, "MAX_FRAME", 64)
        with pytest.raises(protocol.FrameTooLarge):
            c.put(np.ones(1024, np.float32))
        monkeypatch.setattr(protocol, "MAX_FRAME", 1 << 30)
        np.testing.assert_array_equal(c.get(buf), np.ones(4, np.float32))


def test_program_cache_shared_across_sessions(proxy):
    """Identical clients export byte-identical programs; the proxy must
    compile them ONCE (sha-keyed _Program): the second session finds the
    first one's compiled program, no duplicate multi-second XLA compile."""
    def step(w, b):
        return w + b, (w * 0.0).sum()

    with connect(proxy, "a") as ca:
        wa = ca.put(np.zeros(4, np.float32))
        ba = ca.put(np.ones(4, np.float32))
        ea = ca.compile(step, wa, ba)
        wa, aux = ea(wa, ba)
        assert len(proxy._programs) == 1
        (prog,) = proxy._programs.values()
        compiled = prog.single
        assert compiled is not None
        # w + b has an input's shape and dtype: its recycling form was
        # started beside it, once for every session
        recycling = prog.recycle
        assert recycling is not None

        with connect(proxy, "b") as cb:
            wb = cb.put(np.zeros(4, np.float32))
            bb = cb.put(np.ones(4, np.float32))
            eb = cb.compile(step, wb, bb)
            assert len(proxy._programs) == 1  # same sha → shared entry
            for _ in range(8):
                old = wb
                wb, auxb = eb(wb, bb)
                cb.free_later(old)            # rides on the next call
            assert prog.single is compiled    # nothing compiled anew
            assert prog.recycle is recycling
            assert proxy._session("b").out_recycled == 7
            assert (proxy._session("b").executables[eb._exec_id].prog
                    is prog)
            np.testing.assert_allclose(cb.get(wb), np.full(4, 8.0))


# -- the one execution path: what a refused or failed execute leaves ---------


def _accounts(proxy, name):
    return proxy.hbm_accounting()[name]


@pytest.mark.parametrize("bad", ["shape", "dtype", "too-few"])
def test_argument_error_is_clean_and_touches_nothing(proxy, bad):
    """A wrong argument is refused BEFORE dispatch: a clean error, every
    buffer still readable, nothing charged, nothing executed."""
    w0, x0 = np.float32(3.0), np.ones(2, np.float32)
    with connect(proxy, "argerr") as c:
        w, x = c.put(w0), c.put(x0)
        exe = c.compile(lambda w, x: (w + 1.0, x * w), w, x)
        wrong = {"shape": c.put(np.ones(5, np.float32)),
                 "dtype": c.put(np.ones(2, np.int32)),
                 "too-few": None}[bad]
        before = c.usage()
        handles = [w.handle] + ([wrong.handle] if wrong else [])
        with pytest.raises(RuntimeError,
                           match="expected 2 args" if wrong is None
                           else "expects"):
            c.execute_async(exe._exec_id, handles).result()
        after = c.usage()
        assert after["hbm_used"] == before["hbm_used"]
        assert after["exec_count"] == before["exec_count"]
        assert _accounts(proxy, "argerr")["balanced"]
        w2, xw = exe(w, x)      # the arguments survived the error
        assert float(c.get(w2)) == 4.0
        np.testing.assert_array_equal(c.get(xw), 3.0 * x0)
        np.testing.assert_array_equal(c.get(x), x0)


def test_execute_past_tpu_mem_is_refused_before_dispatch(proxy):
    """Outputs accumulate against ``tpu_mem``: the execute whose outputs
    would pass the cap is refused before it reaches the gate or the
    device, what was resident stays readable and accounted, and the same
    execute is served once room is freed."""
    x = np.zeros((16, 16), np.float32)      # 1024 bytes
    with connect(proxy, "capped", memory=2500) as c:
        bx = c.put(x)
        exe = c.compile(lambda a: a + 1.0, bx)
        out = exe(bx)                       # 2048 of 2500 resident
        execs = proxy.total_execs
        with pytest.raises(RuntimeError, match="HBM cap"):
            exe(out)                        # would be 3072
        assert proxy.total_execs == execs
        assert c.usage()["exec_count"] == 1
        acct = _accounts(proxy, "capped")
        assert acct["balanced"] and acct["hbm_used"] == 2 * x.nbytes
        np.testing.assert_array_equal(c.get(out), x + 1.0)
        c.free(bx)
        np.testing.assert_array_equal(c.get(exe(out)), x + 2.0)
        assert _accounts(proxy, "capped")["balanced"]


@pytest.mark.parametrize("how", ["scheduler-closed", "client-removed"])
def test_gate_failure_before_dispatch_refunds_the_charge(proxy, how):
    """The token gate fails while the execute waits for a token another
    client holds: nothing was dispatched, so the output charge goes back
    and every buffer is intact."""
    proxy.idle_release_ms = 1e12            # the holder keeps its token
    x = np.ones((8, 8), np.float32)
    with connect(proxy, "holder") as holder, connect(proxy, "waiter") as c:
        hx = holder.put(x)
        holder.compile(lambda a: a * 2.0, hx)(hx)
        bx = c.put(x)
        exe = c.compile(lambda a: a + 1.0, bx)
        fut = exe.call_async(bx)
        deadline = time.monotonic() + 10.0
        while "waiter" not in proxy.scheduler.waiting():
            assert time.monotonic() < deadline, "waiter never reached the gate"
            time.sleep(0.002)
        # the charge is taken before the gate: it must not outlive it
        assert proxy._session("waiter").hbm_used == 2 * x.nbytes
        if how == "scheduler-closed":
            proxy.scheduler.close()
        else:
            proxy.scheduler.remove_client("waiter")
        with pytest.raises(RuntimeError, match="closed|removed"):
            fut.result()
        acct = _accounts(proxy, "waiter")
        assert acct["balanced"] and acct["hbm_used"] == x.nbytes
        assert proxy._session("waiter").exec_count == 0
        np.testing.assert_array_equal(c.get(bx), x)


def _fail_next_program(proxy, monkeypatch):
    """The device fails the next program it is given, once."""
    real = proxy._run_to_completion
    state = {"failed": False}

    def flaky(fn, args, sync_out):
        if not state["failed"]:
            state["failed"] = True
            raise RuntimeError("injected device failure")
        return real(fn, args, sync_out)

    monkeypatch.setattr(proxy, "_run_to_completion", flaky)


def test_device_failure_refunds_and_keeps_the_session(proxy, monkeypatch):
    """A device failure inside an execute: the error reaches the client
    as it is, the output charge is refunded, the arguments are intact
    (the compiled program aliases none) and the session goes on."""
    x = np.ones((8, 8), np.float32)
    with connect(proxy, "c") as c:
        bx = c.put(x)
        exe = c.compile(lambda a: a + 1.0, bx)
        _fail_next_program(proxy, monkeypatch)
        with pytest.raises(RuntimeError, match="injected device failure"):
            exe(bx)
        acct = _accounts(proxy, "c")
        assert acct["balanced"] and acct["hbm_used"] == x.nbytes
        assert proxy.total_execs == 0
        np.testing.assert_array_equal(c.get(bx), x)
        np.testing.assert_array_equal(c.get(exe(bx)), x + 1.0)
        assert proxy.total_execs == 1


@pytest.mark.parametrize("fails", [False, True])
def test_donated_handles_go_only_after_success(proxy, monkeypatch, fails):
    """``donate`` on an execute: after success the handles are gone and
    their bytes refunded; after a failure they are kept as they were."""
    x = np.ones((8, 8), np.float32)
    with connect(proxy, "c") as c:
        bx = c.put(x)
        exe = c.compile(lambda a: a * 2.0, bx)
        if fails:
            _fail_next_program(proxy, monkeypatch)
            with pytest.raises(RuntimeError, match="injected"):
                exe(bx, donate=True)
            np.testing.assert_array_equal(c.get(bx), x)
            assert c.usage()["hbm_used"] == x.nbytes
        else:
            out = exe(bx, donate=True)
            with pytest.raises(RuntimeError):
                c.get(bx)                   # the donated handle is gone
            np.testing.assert_array_equal(c.get(out), 2.0 * x)
            assert c.usage()["hbm_used"] == x.nbytes
        assert _accounts(proxy, "c")["balanced"]


@pytest.mark.parametrize("op,key", [
    ("execute", "repeat"), ("execute", "chain_steps"),
    ("compile", "ncarry"), ("import_program", "ncarry")])
def test_fused_loop_keys_are_refused_by_name(proxy, op, key):
    """An older client's fused-loop keys name an execution path the proxy
    no longer has: a clean error that names the key, nothing charged,
    compiled or run."""
    from jax import export as jax_export

    x = np.ones(3, np.float32)
    with connect(proxy, "old") as c:
        bx = c.put(x)
        exe = c.compile(lambda a: a + 1.0, bx)
        blob = jax_export.export(
            jax.jit(lambda a: a + 1.0), platforms=[proxy.platform])(
                jax.ShapeDtypeStruct((3,), np.float32)).serialize()
        execute = {"op": "execute", "name": "old", "exec_id": exe._exec_id,
                   "args": [bx.handle]}
        sess = proxy._session("old")
        before = (c.usage(), len(sess.executables))
        with pytest.raises(RuntimeError, match=f"'{key}' is not supported"):
            if op == "execute":
                c._conn.call(dict(execute, **{key: 5}))
            elif op == "compile":
                c._conn.call({"op": "compile", "name": "old", key: 1},
                             blob=blob)
            else:
                with protocol.Connection("127.0.0.1", proxy.port) as mover:
                    mover.call({"op": "import_program", "exec_id": 99,
                                "token": c._conn.token, key: 1}, blob=blob)
        after = (c.usage(), len(sess.executables))
        assert after[0]["hbm_used"] == before[0]["hbm_used"]
        assert after[0]["exec_count"] == before[0]["exec_count"] == 0
        assert after[1] == before[1]
        # "repeat": 1 is what such a client sent with every plain call
        reply, _ = c._conn.call(dict(execute, repeat=1))
        assert reply["ok"] and "repeat" not in reply


# --------------------------------------------------------------------------
# Pod manager + gate
# --------------------------------------------------------------------------

def test_podmanager_relays_and_unregisters():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    schd_server = serve(sched)
    mgr = PodManager("127.0.0.1", schd_server.server_address[1],
                     "ns/pod-a", 0.5, 1.0)
    mgr.serve()
    try:
        assert sched.core.client_count() == 1
        with protocol.Connection("127.0.0.1", mgr.port) as conn:
            reply, _ = conn.call({"op": "register", "name": "ignored"})
            assert reply["name"] == "ns/pod-a"
            reply, _ = conn.call({"op": "acquire", "name": "x"})
            assert reply["quota_ms"] == BASE
            conn.call({"op": "release", "name": "x", "used_ms": 30.0})
            reply, _ = conn.call({"op": "usage", "name": "x"})
            assert reply["used_ms"] == pytest.approx(30.0, abs=5.0)
    finally:
        mgr.close()
        assert sched.core.client_count() == 0
        schd_server.shutdown()


def test_execution_gate_accounts_usage():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    schd_server = serve(sched)
    mgr = PodManager("127.0.0.1", schd_server.server_address[1],
                     "ns/pod-g", 0.5, 1.0)
    mgr.serve()
    try:
        conn = protocol.Connection("127.0.0.1", mgr.port)
        conn.call({"op": "register"})
        gate = ExecutionGate(conn, "ns/pod-g")
        for _ in range(5):
            gate()                 # token round-trip before the "step"
            time.sleep(0.03)       # 30ms of simulated device time
        gate.close()
        usage = sched.window_usage("ns/pod-g")
        assert usage == pytest.approx(150.0, rel=0.5)
        conn.close()
    finally:
        mgr.close()
        schd_server.shutdown()


def test_gate_crash_releases_token():
    """A workload that dies while holding the token must not starve the
    chip: the pod manager releases on gate disconnect."""
    sched = TokenScheduler(WINDOW, BASE, MIN)
    schd_server = serve(sched)
    mgr = PodManager("127.0.0.1", schd_server.server_address[1],
                     "ns/crasher", 0.5, 1.0)
    mgr.serve()
    try:
        conn = protocol.Connection("127.0.0.1", mgr.port)
        reply, _ = conn.call({"op": "acquire", "name": "x"})
        assert reply["quota_ms"] == BASE
        assert sched.core.holder() == "ns/crasher"
        conn.close()  # crash: no release
        deadline = time.monotonic() + 2.0
        while sched.core.holder() is not None and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched.core.holder() is None
    finally:
        mgr.close()
        schd_server.shutdown()


def test_two_gate_connections_no_deadlock():
    """Two connections to one pod manager (e.g. a usage-polling sidecar)
    must not wedge the relay while an acquire blocks."""
    sched = TokenScheduler(WINDOW, BASE, MIN)
    schd_server = serve(sched)
    mgr = PodManager("127.0.0.1", schd_server.server_address[1],
                     "ns/pod-m", 0.5, 1.0)
    mgr.serve()
    try:
        c1 = protocol.Connection("127.0.0.1", mgr.port)
        c2 = protocol.Connection("127.0.0.1", mgr.port)
        c1.call({"op": "acquire"})  # pod holds the token
        # second connection can still talk to the scheduler concurrently
        reply, _ = c2.call({"op": "usage"})
        assert reply["window_ms"] == WINDOW
        c1.call({"op": "release", "used_ms": 10.0})
        c1.close()
        c2.close()
    finally:
        mgr.close()
        schd_server.shutdown()


def test_schd_server_identity_is_connection_bound():
    sched = TokenScheduler(WINDOW, BASE, MIN)
    schd_server = serve(sched)
    try:
        owner = protocol.Connection("127.0.0.1", schd_server.server_address[1])
        owner.call({"op": "register", "name": "p", "request": 0.5, "limit": 1.0})
        rogue = protocol.Connection("127.0.0.1", schd_server.server_address[1])
        with pytest.raises(RuntimeError, match="not bound"):
            rogue.call({"op": "release", "name": "p", "used_ms": 5.0})
        with pytest.raises(RuntimeError, match="KeyError"):
            rogue.call({"op": "attach", "name": "nope"})
        with pytest.raises(RuntimeError, match="already bound"):
            owner.call({"op": "register", "name": "q",
                        "request": 0.5, "limit": 1.0})
        # attach binds to the existing client without creating/owning it
        rogue.call({"op": "attach", "name": "p"})
        reply, _ = rogue.call({"op": "usage"})
        assert reply["window_ms"] == WINDOW
        rogue.close()
        time.sleep(0.1)
        assert sched.core.client_count() == 1  # attach drop ≠ unregister
        owner.close()
        rogue = None
        deadline = time.monotonic() + 2.0
        while sched.core.client_count() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert sched.core.client_count() == 0
    finally:
        schd_server.shutdown()


def test_gate_renews_when_quota_exhausted():
    sched = TokenScheduler(WINDOW, base_quota_ms=50.0, min_quota_ms=5.0)
    schd_server = serve(sched)
    try:
        conn = protocol.Connection("127.0.0.1", schd_server.server_address[1])
        conn.call({"op": "register", "name": "g", "request": 0.9, "limit": 1.0})
        gate = ExecutionGate(conn, "g")
        for _ in range(4):
            gate()
            time.sleep(0.03)  # 30ms steps vs 50ms quota → renew mid-loop
        gate.close()
        assert sched.window_usage("g") == pytest.approx(120.0, rel=0.5)
        conn.close()
    finally:
        schd_server.shutdown()


# -- chunked transfer (buffers larger than the wire frame cap) ---------------


def test_sliced_get_roundtrips_over_tiny_frame_cap(proxy, monkeypatch):
    """A buffer bigger than MAX_FRAME streams down in slices — the path the
    old `get` refusal pointed at ("fetch it in slices") but never offered."""
    monkeypatch.setattr(protocol, "MAX_FRAME", 1 << 16)  # 64 KiB wire cap
    with connect(proxy, "c") as c:
        arr = np.random.default_rng(0).standard_normal(
            (512, 256)).astype(np.float32)          # 512 KiB ≫ cap
        buf = c.put(arr)                            # staged upload
        np.testing.assert_array_equal(c.get(buf), arr)  # sliced download
        # Accounting unchanged by the transfer mechanics.
        assert c.usage()["hbm_used"] == arr.nbytes
        c.free(buf)
        assert c.usage()["hbm_used"] == 0


def test_staged_put_respects_hbm_cap(proxy, monkeypatch):
    monkeypatch.setattr(protocol, "MAX_FRAME", 1 << 16)
    with connect(proxy, "c", memory=1 << 16) as c:
        with pytest.raises(RuntimeError, match="HBM cap"):
            c.put(np.zeros(1 << 17, np.uint8))      # 128 KiB > 64 KiB cap
        # The refused staging was aborted — a fitting put still works.
        small = np.arange(64, dtype=np.float32)
        np.testing.assert_array_equal(c.get(c.put(small)), small)


def test_sliced_get_cache_is_per_handle(proxy, monkeypatch):
    """Interleaved sliced reads of two handles must not serve stale bytes."""
    monkeypatch.setattr(protocol, "MAX_FRAME", 1 << 14)
    with connect(proxy, "c") as c:
        a = np.full((100, 100), 1, np.float32)
        b = np.full((100, 100), 2, np.float32)
        ba, bb = c.put(a), c.put(b)
        np.testing.assert_array_equal(c.get(ba), a)
        np.testing.assert_array_equal(c.get(bb), b)
        np.testing.assert_array_equal(c.get(ba), a)


def test_proxy_crash_fails_client_cleanly_and_resume_works():
    """Fault injection the reference never had (SURVEY §5: 'no fault
    injection'): the chip proxy dies mid-session; the client must get a
    clean connection error (no hang), and a replacement proxy must accept
    a re-register + re-put so training resumes from host state."""
    sched = TokenScheduler(WINDOW, BASE, MIN)
    p1 = ChipProxy(scheduler=sched)
    p1.serve()
    c = connect(p1, "phoenix")
    w = c.put(np.float32(1.0))
    w = c.compile(lambda w: w + 1.0, w)(w)
    host_w = float(c.get(w))           # checkpoint to host
    p1.close()                          # crash

    assert float(c.get(w)) == host_w    # came with the reply: no request
    with pytest.raises((RuntimeError, OSError)):
        c.usage()                       # dead proxy: clean error, no hang
    c.close()

    p2 = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN))
    p2.serve()
    try:
        with connect(p2, "phoenix") as c2:   # same name: fresh incarnation
            w2 = c2.put(np.float32(host_w))
            w2 = c2.compile(lambda w: w + 1.0, w2)(w2)
            assert float(c2.get(w2)) == host_w + 1.0
    finally:
        p2.close()


def test_idle_watchdog_races_gated_execution_stress():
    """The advisor flagged proxy-side token state (holding/used) as the
    spot most likely to breed deadlocks: the idle watchdog manipulates it
    under sess.lock concurrently with _gated. Hammer that exact interleaving
    — 4 clients, sub-burst idle_release, short window — and require
    everyone to make steady progress with sane accounting."""
    sched = TokenScheduler(window_ms=200.0, base_quota_ms=20.0,
                           min_quota_ms=2.0)
    p = ChipProxy(scheduler=sched, idle_release_ms=5.0)  # watchdog fires hot
    p.serve()
    errors: list = []
    counts: dict = {}

    def worker(name):
        try:
            with connect(p, name, request=0.25, limit=1.0) as c:
                x = c.put(np.ones(16, np.float32))
                exe = c.compile(lambda a: a + 1.0, x)
                n = 0
                deadline = time.monotonic() + 3.0
                while time.monotonic() < deadline:
                    x = exe(x, donate=True)
                    n += 1
                    if n % 7 == 0:
                        time.sleep(0.012)  # go idle past idle_release_ms
                counts[name] = n
                u = c.usage()
                assert u["exec_count"] == n + 0  # every dispatch accounted
                mine = u["chip"]["sessions"][name]
                # a kept hold is handed on by its grace at most once
                assert mine["kept_yielded"] <= mine["kept_count"]
                assert mine["kept_early"] <= mine["kept_yielded"]
        except Exception as e:  # pragma: no cover - failure reporting
            errors.append((name, e))

    threads = [threading.Thread(target=worker, args=(f"w{i}",))
               for i in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=30.0)
    assert not any(t.is_alive() for t in threads), "deadlock: worker stuck"
    try:
        assert not errors, errors
        assert len(counts) == 4 and all(n > 10 for n in counts.values()), counts
    finally:
        p.close()


# -- a kept hold lasts its holder's grace ---------------------------------------
#
# The idle timer is a whole second here, far above any grace these tests
# learn: a hand-over well before it is the grace's, and bounds are taken
# against it, never as a ratio of two CPU timings.

IDLE_MS = 1000.0


@pytest.fixture
def graced():
    p = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN),
                  idle_release_ms=IDLE_MS)
    p.serve()
    yield p
    p.close()


def _stepper(proxy, name):
    c = connect(proxy, name)
    x = c.put(np.ones((4, 4), np.float32))
    exe = c.compile(lambda a: a * 2.0, x)
    return c, lambda: exe(x)


def _ahead(proxy, name, step, used_ms=300.0):
    """``name`` runs one program and gives its token back, by hand, as if
    it had used ``used_ms``: the weighted pick then keeps the other
    tenant's hold at every boundary where ``name`` waits."""
    step()
    _let_go(proxy, name, used_ms)


def _learn(step, n, gap_s=0.0):
    """``n`` programs, ``gap_s`` apart: ``n - 1`` turn-arounds on record."""
    for i in range(n):
        if i and gap_s:
            time.sleep(gap_s)
        step()


def _grace(proxy, name):
    sess = proxy._session(name)
    assert sess.grace_ms is not None, "no grace learned"
    return sess.grace_ms


def _let_go(proxy, name, used_ms=None):
    """``name`` gives its idle token back, by hand (the idle timer's act),
    with ``used_ms`` on the books where given."""
    sess = proxy._session(name)
    with sess.lock:
        sess.holding = False
    proxy.scheduler.release(name, sess.used_ms if used_ms is None
                            else used_ms)


def _hold_next_program(proxy, monkeypatch):
    """The next program stays on the "device" until ``finish`` is set."""
    started, finish = threading.Event(), threading.Event()
    run = proxy._run_to_completion

    def held(fn, args, sync_out):
        if not started.is_set():
            started.set()
            assert finish.wait(10.0)
        return run(fn, args, sync_out)

    monkeypatch.setattr(proxy, "_run_to_completion", held)
    return started, finish


def _waiting(proxy, name):
    deadline = time.monotonic() + 10.0
    while name not in proxy.scheduler.waiting():
        assert time.monotonic() < deadline, f"{name} never waited"
        time.sleep(0.002)


def _kept_counts(c, name):
    sess = c.usage()["chip"]["sessions"][name]
    return sess["kept_count"], sess["kept_yielded"], sess["kept_early"]


def _spy_phases(monkeypatch):
    from kubeshare_tpu.obs import trace as obs_trace
    seen = []

    class spy(obs_trace.phase):
        def __init__(self, name, session, trace_id="", **attrs):
            seen.append((name, session, attrs))
            super().__init__(name, session, trace_id, **attrs)

    monkeypatch.setattr(obs_trace, "phase", spy)
    return seen


def test_a_kept_holder_back_inside_its_turn_around_keeps_the_token(graced):
    """A holder that learned a long turn-around (programs 150 ms apart)
    and comes back at once keeps the token at every boundary while the
    other tenant waits; gone quiet, it hands over no sooner than its
    grace, and the counters say so."""
    ca, step_a = _stepper(graced, "a")
    cb, step_b = _stepper(graced, "b")
    _ahead(graced, "b", step_b)
    _learn(step_a, 10, gap_s=0.15)
    grace = _grace(graced, "a")
    assert grace >= 150.0
    tb = threading.Thread(target=step_b, daemon=True)
    tb.start()
    _waiting(graced, "b")
    for _ in range(3):          # back well inside 150 ms each time
        step_a()
    sess_a = graced._session("a")
    assert sess_a.holding and tb.is_alive()
    assert "b" in graced.scheduler.waiting()
    assert _kept_counts(ca, "a") == (3, 0, 0)
    t_end = sess_a.last_end_ms
    tb.join(10.0)               # a is quiet now: its grace runs out
    done = time.monotonic() * 1000.0
    assert not tb.is_alive()
    assert done - t_end >= grace
    assert not sess_a.holding and graced._session("b").holding
    assert _kept_counts(ca, "a") == (3, 1, 0)
    assert _kept_counts(cb, "b") == (0, 0, 0)
    ca.close()
    cb.close()


@pytest.mark.parametrize("kept", [True, False],
                         ids=["kept-at-a-boundary", "idle-when-asked"])
def test_a_quiet_holder_hands_over_within_its_grace(graced, monkeypatch,
                                                    kept):
    """A holder that learned a short turn-around (back-to-back programs)
    and goes quiet while the other tenant waits hands the token over
    within its grace, far under the idle timer: where the pick kept it
    at the boundary of a program that ended while the other waited, and
    where the other came to ask while the hold sat idle (the waiter wakes
    the watchdog itself). A ``ks.gate_yield`` event gives the grace and
    the idle it ended."""
    seen = _spy_phases(monkeypatch)
    ca, step_a = _stepper(graced, "a")
    cb, step_b = _stepper(graced, "b")
    _ahead(graced, "b", step_b)
    _learn(step_a, 12)
    grace = _grace(graced, "a")
    assert grace < IDLE_MS / 4
    tb = threading.Thread(target=step_b, daemon=True)
    if kept:
        started, finish = _hold_next_program(graced, monkeypatch)
        ta = threading.Thread(target=step_a, daemon=True)
        ta.start()
        assert started.wait(10.0)
        tb.start()
        _waiting(graced, "b")
        finish.set()
        ta.join(10.0)
        assert not ta.is_alive()
    else:
        time.sleep(grace / 1000.0 + 0.02)
        tb.start()
    grace = _grace(graced, "a")     # with the held program's turn-around
    t_end = graced._session("a").last_end_ms
    tb.join(10.0)
    done = time.monotonic() * 1000.0
    assert not tb.is_alive()
    # the idle timer would have waited until t_end + IDLE_MS
    assert done - t_end < grace + IDLE_MS / 2
    assert not graced._session("a").holding
    assert _kept_counts(ca, "a") == ((1, 1, 0) if kept else (0, 0, 0))
    yields = [attrs for name, who, attrs in seen
              if name == "gate_yield" and who == "a"]
    assert len(yields) == 1
    assert yields[0]["grace_ms"] == grace
    assert yields[0]["idle_ms"] >= grace
    assert yields[0]["kept"] == int(kept)
    ca.close()
    cb.close()


@pytest.mark.parametrize("back_after_s", [0.0, IDLE_MS / 1000.0 + 0.05],
                         ids=["before-the-timer", "after-the-timer"])
def test_a_kept_hold_the_grace_ended_counts_early_if_its_holder_was_back_first(
        graced, monkeypatch, back_after_s):
    """The holder's next request after its grace ended a kept hold tells
    the proxy whether that was too soon: back before the idle timer would
    have let go, ``kept_early`` counts it; later, it does not."""
    ca, step_a = _stepper(graced, "a")
    cb, step_b = _stepper(graced, "b")
    _ahead(graced, "b", step_b)
    _learn(step_a, 12)
    started, finish = _hold_next_program(graced, monkeypatch)
    ta = threading.Thread(target=step_a, daemon=True)
    ta.start()
    assert started.wait(10.0)
    tb = threading.Thread(target=step_b, daemon=True)
    tb.start()
    _waiting(graced, "b")
    finish.set()
    ta.join(10.0)
    tb.join(10.0)               # a's kept hold goes to b by the grace
    assert not ta.is_alive() and not tb.is_alive()
    sess_a = graced._session("a")
    assert sess_a.graced
    _let_go(graced, "b")
    time.sleep(back_after_s)
    step_a()
    assert not sess_a.graced
    assert _kept_counts(ca, "a") == (1, 1, int(not back_after_s))
    ca.close()
    cb.close()


@pytest.mark.parametrize("gaps, grace", [
    ([2.0] * 7, None),
    ([2.0] * 32, 2.0),
    ([2.0] * 20 + [4.0, 5.0, 6.0, 7.0, 8.0] + [50.0] * 7, 7.0),
    ([2.0] * 40 + [3.0, 4.0, 5.0, 6.0, 7.0, 8.0, 9.0] + [50.0] * 17, 7.0),
    ([2.0] * 12 + [7.0, 9.0], 9.0),
    ([2.0] * 31 + [9.0], 2.0),
    ([12.0] * 31 + [3.0], proxy_mod.GRACE_FLOOR_MS),
    ([0.2] * 32, proxy_mod.GRACE_FLOOR_MS),
], ids=["too-few", "back-to-back", "open-loop", "open-loop-64",
        "under-32-the-largest", "32-leave-one-out",
        "rarely-back-before-the-timer", "floor"])
def test_the_grace_leaves_one_gap_in_32_between_it_and_the_timer(
        gaps, grace):
    """The least idle past which no more than one recorded gap in
    ``GRACE_EARLY`` came back before the idle timer (10 ms here), at
    least ``GRACE_FLOOR_MS``; none under ``GRACE_SAMPLES`` gaps."""
    assert proxy_mod._grace_ms(gaps, 10.0) == grace


def test_with_nobody_waiting_a_quiet_holder_keeps_the_idle_timer(graced):
    """A grace applies only while another tenant waits: a holder with a
    short one learned, gone quiet with nobody asking, keeps the token
    until ``idle_release_ms`` has passed."""
    ca, step_a = _stepper(graced, "a")
    _learn(step_a, 12)
    grace = _grace(graced, "a")
    sess_a = graced._session("a")
    t_end = sess_a.last_end_ms
    time.sleep(grace / 1000.0 + 0.25)
    assert sess_a.holding
    deadline = time.monotonic() + 10.0
    while sess_a.holding:
        assert time.monotonic() < deadline, "the idle timer never fired"
        time.sleep(0.005)
    assert time.monotonic() * 1000.0 - t_end >= IDLE_MS
    assert _kept_counts(ca, "a") == (0, 0, 0)
    ca.close()


def test_a_holder_with_too_few_turn_arounds_waits_out_the_idle_timer(
        graced, monkeypatch):
    """Fewer than ``GRACE_SAMPLES`` turn-arounds on record: no grace, so
    a hold the pick kept with the other tenant waiting lasts until the
    idle timer, as it did before there was a grace."""
    ca, step_a = _stepper(graced, "a")
    cb, step_b = _stepper(graced, "b")
    _ahead(graced, "b", step_b)
    _learn(step_a, proxy_mod.GRACE_SAMPLES - 2)
    started, finish = _hold_next_program(graced, monkeypatch)
    ta = threading.Thread(target=step_a, daemon=True)
    ta.start()
    assert started.wait(10.0)
    tb = threading.Thread(target=step_b, daemon=True)
    tb.start()
    _waiting(graced, "b")
    finish.set()
    ta.join(10.0)
    assert not ta.is_alive()
    sess_a = graced._session("a")
    assert sess_a.grace_ms is None and len(sess_a.gaps) < proxy_mod.GRACE_SAMPLES
    t_end = sess_a.last_end_ms
    tb.join(10.0)
    assert not tb.is_alive()
    assert time.monotonic() * 1000.0 - t_end >= IDLE_MS
    assert _kept_counts(ca, "a") == (1, 0, 0)
    ca.close()
    cb.close()


def test_dump_array_parts_stream_equals_blob():
    """parts = [header, flat data view] must byte-equal the contiguous
    blob for every dtype/shape the wire carries, and slice_buffers must
    reassemble any byte range without materializing the stream."""
    import numpy as np
    from kubeshare_tpu.isolation import protocol

    for arr in (np.arange(23, dtype=np.float32).reshape(23, 1),
                np.asarray(3.5, np.float64),          # 0-d scalar
                np.arange(6, dtype=np.int8)[::2],     # non-contiguous
                np.zeros((0, 4), np.float32)):        # empty
        blob = protocol.dump_array(arr)
        parts = protocol.dump_array_parts(arr)
        assert b"".join(bytes(memoryview(p)) for p in parts) == blob
        n = len(blob)
        for off, length in ((0, n), (1, 7), (n - 3, 3), (5, n)):
            if n == 0:
                continue
            got = b"".join(bytes(memoryview(p)) for p in
                           protocol.slice_buffers(parts, off, length))
            assert got == blob[off:off + length]
        back = protocol.load_array(blob)
        np.testing.assert_array_equal(back, np.asarray(arr))


def test_put_payload_not_copied_on_send():
    """The put path must stream the array's own memory: dump_array_parts
    returns a view over the (C-contiguous) input, not a copy."""
    import numpy as np
    from kubeshare_tpu.isolation import protocol

    arr = np.arange(1024, dtype=np.float32)
    parts = protocol.dump_array_parts(arr)
    data = parts[1]
    assert isinstance(data, memoryview)
    assert data.obj is arr  # same backing memory — zero-copy


# -- pipelined transport (ISSUE 2) ------------------------------------------


def test_old_protocol_client_compat_roundtrip(proxy):
    """An unnegotiated (seed-wire) lockstep client — no `features` key, no
    `_seq` — must round-trip put/execute/get against the pipelined proxy
    byte-for-byte, with the reply shapes it has always seen."""
    import socket as socket_mod

    from jax import export as jax_export

    sock = socket_mod.create_connection(("127.0.0.1", proxy.port))

    def call(msg, blob=None):
        protocol.send_msg(sock, msg, blob)
        reply, rblob = protocol.recv_msg(sock)
        assert reply.get("ok"), reply
        return reply, rblob

    try:
        reply, _ = call({"op": "register", "name": "old", "request": 0.5,
                         "limit": 1.0})
        assert "features" not in reply       # reply shape unchanged
        assert protocol.SEQ_KEY not in reply  # no seq tag on lockstep wire
        arr = np.arange(256, dtype=np.float32)
        reply, _ = call({"op": "put", "name": "old"},
                        blob=bytes(protocol.dump_array(arr)))
        handle = reply["handle"]

        exported = jax_export.export(
            jax.jit(lambda x: x + 1.0),
            platforms=[proxy.platform])(jax.ShapeDtypeStruct((256,),
                                                             np.float32))
        reply, _ = call({"op": "compile", "name": "old"},
                        blob=exported.serialize())
        reply, _ = call({"op": "execute", "name": "old",
                         "exec_id": reply["exec_id"], "args": [handle],
                         "donate": []})
        assert protocol.SEQ_KEY not in reply
        out_handle = reply["handles"][0]

        reply, blob = call({"op": "get", "name": "old",
                            "handle": out_handle, "offset": 0,
                            "length": 1 << 20})
        assert int(reply["total"]) == len(blob)
        # byte-for-byte: the fetched stream is exactly the .npy encoding
        assert bytes(blob) == bytes(protocol.dump_array(
            np.asarray(arr + np.float32(1.0))))
        np.testing.assert_array_equal(protocol.load_array(blob),
                                      arr + 1.0)
    finally:
        sock.close()


def test_register_negotiates_seq_feature(proxy):
    with connect(proxy, "c") as c:
        assert "seq" in c.features
        assert c._conn.pipelined


def test_execute_async_resolves_out_of_submission_wait_order(proxy):
    with connect(proxy, "c") as c:
        x = np.float32(1.0)
        exe = c.compile(lambda a: a + 1.0, x)
        bx = c.put(x)
        futs = [exe.call_async(bx) for _ in range(12)]
        # wait in REVERSE submission order: every future must still
        # resolve (per-seq tagging, not positional matching)
        outs = [f.result() for f in reversed(futs)]
        for o in outs:
            assert float(c.get(o)) == 2.0
        c.free(*outs)


def test_async_failure_surfaces_at_result(proxy):
    with connect(proxy, "c") as c:
        x = np.float32(1.0)
        exe = c.compile(lambda a: a + 1.0, x)
        bx = c.put(x)
        good = exe.call_async(bx)
        c.free(bx)
        bad = exe.call_async(bx)        # handle freed: remote error
        good.result()
        with pytest.raises(Exception):
            bad.result()
        # connection survived the failed op
        assert c.usage()["ok"]


def test_put_abort_mid_window_keeps_session(proxy):
    """A chunk refused mid-window must not desync the stream: later
    in-flight chunks complete, put_abort lands, and the session (and its
    HBM reservation) is fully recovered."""
    with connect(proxy, "c") as c:
        conn = c._conn
        reply, _ = conn.call({"op": "put_begin", "name": "c",
                              "nbytes": 1 << 16})
        sid = reply["staging"]
        reps = [
            conn.submit({"op": "put_chunk", "name": "c", "staging": sid,
                         "offset": 0}, blob=b"x" * 1024),
            # out-of-range: fails server-side while later chunks are in
            # flight behind it
            conn.submit({"op": "put_chunk", "name": "c", "staging": sid,
                         "offset": (1 << 16) - 10}, blob=b"y" * 1024),
            conn.submit({"op": "put_chunk", "name": "c", "staging": sid,
                         "offset": 2048}, blob=b"z" * 1024),
        ]
        outcomes = []
        for r in reps:
            try:
                r.result(timeout=30)
                outcomes.append("ok")
            except RuntimeError:
                outcomes.append("err")
        assert outcomes == ["ok", "err", "ok"]
        conn.call({"op": "put_abort", "name": "c", "staging": sid})
        # the put_begin HBM reservation was released by the abort
        assert c.usage()["hbm_used"] == 0
        arr = np.arange(8, dtype=np.float32)
        np.testing.assert_array_equal(c.get(c.put(arr)), arr)


def test_windowed_put_get_roundtrip_many_chunks(proxy):
    """Windowed streaming with many chunks in flight (window > 2 chunks,
    several windows deep) reassembles exactly."""
    with connect(proxy, "c") as c:
        c.chunk_bytes = 1 << 14          # 16 KiB chunks
        rng = np.random.default_rng(7)
        arr = rng.standard_normal((320, 320)).astype(np.float32)  # ~400 KiB
        buf = c.put(arr)
        np.testing.assert_array_equal(c.get(buf), arr)
        got = c.get(buf)
        assert got.flags.writeable       # user-facing array stays mutable


# -- a call is one round trip (the "inline" feature) --------------------------

def _scorer(c):
    """Resident params + two small host leaves -> one float."""
    params = {"w": c.put(np.linspace(0.0, 1.0, 512, dtype=np.float32))}

    def score(params, toks, n):
        live = jnp.arange(toks.shape[0]) < n
        return jnp.sum(jnp.where(live, params["w"][toks], 0.0))

    toks = np.arange(64, dtype=np.int32)
    exe = c.compile(score, params, toks, np.int32(64))

    def call(i):
        out = exe(params, (toks + i) % 512, np.int32(8 + i))
        value = float(c.get(out))
        c.free_later(out)       # what a collected RemoteArray does
        assert value == pytest.approx(float(np.sum(
            np.linspace(0.0, 1.0, 512, dtype=np.float32)[
                ((toks + i) % 512)[:8 + i]])), rel=1e-5)
    return call, 2


def _trainer(c):
    """Resident state + two host batches -> state + loss, loss read."""
    state = {"w": c.put(np.zeros((16,), np.float32)),
             "count": c.put(np.float32(0.0))}

    def step(state, x, y):
        err = x @ state["w"] - y
        loss = jnp.mean(err ** 2)
        w = state["w"] - 0.01 * (2.0 / x.shape[0]) * (x.T @ err)
        return {"w": w, "count": state["count"] + 1.0}, loss

    rng = np.random.default_rng(0)
    x = rng.normal(size=(32, 16)).astype(np.float32)
    y = rng.normal(size=(32,)).astype(np.float32)
    exe = c.compile(step, state, x, y)
    box = {"state": state, "losses": []}

    def call(i):
        new, loss = exe(box["state"], x, y)
        box["losses"].append(float(c.get(loss)))
        # the count is as small as the loss and comes before it: the
        # barrier reads the LAST of the smallest, the loss
        assert loss.value is not None and new["count"].value is None
        c.free_later(box["state"], loss)
        box["state"] = new
        if len(box["losses"]) > 1:
            assert box["losses"][-1] < box["losses"][-2]
    return call, 2


@pytest.mark.parametrize("shape", [_scorer, _trainer],
                         ids=["scorer", "trainer"])
def test_a_call_of_a_program_is_one_request(proxy, shape):
    """After warm-up a scorer-shaped request and a trainer-shaped step
    each cost exactly ONE request: the small host leaves ride in the
    execute, the value the barrier read comes back in the reply, and the
    frees of the call before ride along. The counters say so, and only
    grow."""
    with connect(proxy, "t") as c:
        assert "inline" in c.features
        call, leaves = shape(c)
        call(0)                                   # warm-up
        sess = proxy._session("t")
        hbm = []
        for i in range(1, 6):
            before = (sess.rpc_count, sess.exec_count,
                      sess.inline_in_total, sess.inline_out_total)
            call(i)
            assert sess.rpc_count == before[0] + 1
            assert sess.exec_count == before[1] + 1
            assert sess.inline_in_total == before[2] + leaves
            assert sess.inline_out_total == before[3] + 1
            hbm.append(sess.hbm_used)
        # nothing accumulates: the inline inputs were dropped and the
        # frees of each call rode in on the next
        assert len(set(hbm)) == 1, hbm
        seen = c.usage()["chip"]["sessions"]["t"]   # flushes the last frees
        assert seen["rpc_count"] == sess.rpc_count == before[0] + 3
        for i in range(6, 9):
            call(i)
            now = c.usage()["chip"]["sessions"]["t"]
            for k in ("rpc_count", "exec_count", "inline_in_total",
                      "inline_out_total"):
                assert now[k] > seen[k], k
            seen = now
        assert proxy.hbm_accounting()["t"]["balanced"]


def test_a_host_leaf_over_the_limit_goes_through_put(proxy):
    with connect(proxy, "c") as c:
        exe_small = c.compile(lambda a: jnp.sum(a * 2.0),
                              np.zeros(protocol.INLINE_MAX // 4, np.float32))
        exe_big = c.compile(lambda a: jnp.sum(a * 2.0),
                            np.zeros(protocol.INLINE_MAX // 4 + 1,
                                     np.float32))
        sess = proxy._session("c")
        small = np.arange(protocol.INLINE_MAX // 4, dtype=np.float32)
        big = np.arange(protocol.INLINE_MAX // 4 + 1, dtype=np.float32)
        r0, i0 = sess.rpc_count, sess.inline_in_total
        assert float(c.get(exe_small(small))) == float(np.sum(small * 2.0))
        assert (sess.rpc_count, sess.inline_in_total) == (r0 + 1, i0 + 1)
        # one byte-count over: put, execute, free of the upload; the
        # result is what the inline path gives
        assert float(c.get(exe_big(big))) == float(np.sum(big * 2.0))
        assert (sess.rpc_count, sess.inline_in_total) == (r0 + 4, i0 + 1)
        assert c.usage()["hbm_used"] == 8     # the two results
        assert proxy.hbm_accounting()["c"]["balanced"]


def test_an_inline_input_over_tpu_mem_is_refused_before_dispatch(proxy):
    with connect(proxy, "c", memory=4096) as c:
        w = c.put(np.ones(512, np.float32))          # 2048 of 4096
        exe = c.compile(lambda w, x: jnp.sum(w) + jnp.sum(x), w,
                        np.zeros(1024, np.float32))
        sess = proxy._session("c")
        used = sess.hbm_used
        with pytest.raises(RuntimeError, match="HBM cap"):
            exe(w, np.ones(1024, np.float32))        # 4096 more: over
        assert sess.exec_count == 0                  # never reached the gate
        assert sess.hbm_used == used and sess.inline_in_total == 0
        assert proxy.hbm_accounting()["c"]["balanced"]
        # one that fits is charged while it lives and gone afterwards
        exe2 = c.compile(lambda w, x: jnp.sum(w) + jnp.sum(x), w,
                         np.zeros(256, np.float32))
        assert float(c.get(exe2(w, np.ones(256, np.float32)))) == 768.0
        assert sess.hbm_used == used + 4
        assert proxy.hbm_accounting()["c"]["balanced"]


@pytest.mark.parametrize("fails", [False, True],
                         ids=["success", "device-failure"])
def test_inline_inputs_are_gone_and_refunded_when_the_call_ends(
        proxy, monkeypatch, fails):
    with connect(proxy, "c", memory=1 << 20) as c:
        w = c.put(np.ones(8, np.float32))
        exe = c.compile(lambda w, x: w + x, w, np.zeros(8, np.float32))
        sess = proxy._session("c")
        used, held = sess.hbm_used, set(sess.buffers)
        charged = []
        real = proxy._run_to_completion

        def run(fn, args, sync_out):
            charged.append(sess.hbm_used)     # inside the call
            if fails:
                raise RuntimeError("device fell over")
            return real(fn, args, sync_out)

        monkeypatch.setattr(proxy, "_run_to_completion", run)
        if fails:
            with pytest.raises(RuntimeError, match="device fell over"):
                exe(w, np.ones(8, np.float32))
            assert sess.hbm_used == used and set(sess.buffers) == held
        else:
            out = exe(w, np.ones(8, np.float32))
            np.testing.assert_array_equal(c.get(out), np.full(8, 2.0))
            # only the output stays: the inline input never had a handle
            assert sess.hbm_used == used + 32
            assert set(sess.buffers) == held | {out.handle}
        # while the program ran, input AND output were charged
        assert charged == [used + 32 + 32]
        assert proxy.hbm_accounting()["c"]["balanced"]


def test_only_the_barriers_pick_comes_back(proxy, monkeypatch):
    """A program with many small outputs makes ONE host read, and that
    one value is what the reply carries."""
    from kubeshare_tpu.isolation import proxy as proxy_mod

    reads = []

    class CountingNumpy:
        def __getattr__(self, name):
            return getattr(np, name)

        @staticmethod
        def asarray(x, *a, **k):
            if isinstance(x, jax.Array):
                reads.append(x.shape)
            return np.asarray(x, *a, **k)

    with connect(proxy, "c") as c:
        x = c.put(np.arange(6, dtype=np.float32))
        exe = c.compile(lambda x: (x, x[:2], x[:1] * 2.0, x[:3], x[1:2] + 5.0),
                        x)
        sess = proxy._session("c")
        monkeypatch.setattr(proxy_mod, "np", CountingNumpy())
        outs = exe(x)
        monkeypatch.undo()
        assert reads == [(1,)]
        # the smallest, and of the two that small the last
        assert [o.value is not None for o in outs] == [
            False, False, False, False, True]
        assert sess.inline_out_total == 1
        r0 = sess.rpc_count
        np.testing.assert_array_equal(c.get(outs[4]), [6.0])   # no request
        value = c.get(outs[4])
        value[0] = -1.0                       # the caller's own copy
        np.testing.assert_array_equal(c.get(outs[4]), [6.0])
        assert sess.rpc_count == r0
        np.testing.assert_array_equal(c.get(outs[2]), [0.0])   # a get
        assert sess.rpc_count == r0 + 1


@pytest.mark.parametrize("op", ["execute", "get", "put", "compile", "usage",
                                "free", "close"])
def test_queued_frees_ride_on_an_execute_and_any_other_op_flushes_them(
        proxy, op):
    c = connect(proxy, "c")
    x = c.put(np.ones(4, np.float32))
    exe = c.compile(lambda a: a + 1.0, x)
    dead = [c.put(np.zeros(64, np.float32)) for _ in range(3)]
    other = c.put(np.zeros(2, np.float32))
    sess = proxy._session("c")
    c.free_later(dead[0], [dead[1], {"k": dead[2]}])      # any pytree
    assert all(b.handle in sess.buffers for b in dead)    # no request yet
    r0, used = sess.rpc_count, sess.hbm_used
    if op == "execute":
        exe(x)          # applied by that call, before it is charged
        assert sess.rpc_count == r0 + 1
        assert sess.hbm_used == used - 3 * 256 + 16
    elif op == "close":
        freed = []
        real = proxy._free_handles
        proxy._free_handles = lambda s, h: (freed.extend(h), real(s, h))
        c.close()
        assert sorted(freed) == sorted(b.handle for b in dead)
        return
    else:
        {"get": lambda: c.get(other), "put": lambda: c.put(np.float32(1)),
         "compile": lambda: c.compile(lambda a: a * 2.0, x),
         "usage": c.usage, "free": lambda: c.free(other)}[op]()
        # a free of its own first (free: the one request carries both)
        assert sess.rpc_count == r0 + (1 if op == "free" else 2)
    assert not any(b.handle in sess.buffers for b in dead)
    c.close()


def test_an_execute_without_the_new_keys_is_served_as_before(
        proxy, monkeypatch):
    """A client and proxy that never agreed on "inline" (an old peer on
    either side) take the path of before: put, execute, free, get; a bare
    execute by handles on a session that did agree is served too."""
    def run(c):
        w = c.put(np.arange(4, dtype=np.float32))
        exe = c.compile(lambda w, x, n: jnp.sum(w * x) * n, w,
                        np.zeros(4, np.float32), np.int32(0))
        out = exe(w, np.ones(4, np.float32), np.int32(3))
        return exe, w, out, float(c.get(out))

    with monkeypatch.context() as m:
        m.setattr(protocol, "FEATURES", ("resume", "seq", "preempt"))
        with connect(proxy, "old") as c:
            assert "inline" not in c.features
            sess = proxy._session("old")
            exe, w, out, value = run(c)
            assert value == 18.0 and out.value is None
            # put, compile | put, put, execute, free | get
            assert sess.rpc_count == 7
            assert (sess.inline_in_total, sess.inline_out_total) == (0, 0)
            c.free_later(out)
            out = exe(w, np.ones(4, np.float32), np.int32(1))
            assert sess.rpc_count == 7 + 5      # a free of its own first
            reply, _ = c._conn.call({"op": "execute", "name": "old",
                                     "exec_id": exe._exec_id,
                                     "args": [w.handle, w.handle,
                                              c.put(np.int32(2)).handle]})
            assert set(reply) == {"ok", "handles"}
    with connect(proxy, "new") as c:
        sess = proxy._session("new")
        exe, w, out, value = run(c)
        assert value == 18.0 and sess.rpc_count == 3
        n = c.put(np.int32(2))
        reply, _ = c._conn.call({"op": "execute", "name": "new",
                                 "exec_id": exe._exec_id,
                                 "args": [w.handle, w.handle, n.handle]})
        assert reply["ok"] and len(reply["handles"]) == 1
        assert sess.inline_in_total == 2        # the first call's two


@pytest.mark.parametrize("bad,match", [
    ({"inline": [[1, "float32", [4]]]}, "left in the blob"),
    ({"inline": []}, "1 nulls in args"),
    ({"inline": [[0, "float32", [4]]], "blob": 16}, "no null in args"),
    ({"inline": [[1, "float32", [5]]], "blob": 20}, "program expects"),
    ({"inline": [[1, "float32", [4]]], "blob": 20}, "execute blob holds"),
    ({"inline": [[1, "float32", [20000]]], "blob": 80000}, "at most 65536"),
], ids=["short-blob", "null-unfilled", "not-a-null", "wrong-shape",
        "long-blob", "over-the-limit"])
def test_a_malformed_inline_input_is_a_clean_error(proxy, bad, match):
    with connect(proxy, "c") as c:
        w = c.put(np.ones(4, np.float32))
        exe = c.compile(lambda w, x: w + x, w, np.zeros(4, np.float32))
        sess = proxy._session("c")
        used = sess.hbm_used
        msg = {"op": "execute", "name": "c", "exec_id": exe._exec_id,
               "args": [w.handle, None], "inline": bad["inline"]}
        blob = bytes(bad["blob"]) if "blob" in bad else None
        with pytest.raises(RuntimeError, match=match):
            c._conn.call(msg, blob=blob)
        assert sess.exec_count == 0 and sess.hbm_used == used
        # the stream is in step and the session serves on
        np.testing.assert_array_equal(
            c.get(exe(w, np.ones(4, np.float32))), np.full(4, 2.0))


def test_frees_queued_from_other_threads_are_each_applied_once(proxy):
    """``free_later`` is what ``RemoteArray.__del__`` calls, on whatever
    thread the collector runs: handles queued while the owner's thread
    keeps executing are neither lost nor sent twice."""
    import sys

    with connect(proxy, "c") as c:
        x = c.put(np.ones(4, np.float32))
        exe = c.compile(lambda a: a + 1.0, x)
        dead = [c.put(np.zeros(8, np.float32)) for _ in range(400)]
        sess = proxy._session("c")
        freed = []
        real = proxy._free_handles
        proxy._free_handles = lambda s, h: (freed.extend(h), real(s, h))[1]
        go, threads = threading.Event(), []
        for k in range(8):
            def drop(mine=dead[k::8]):
                go.wait(5.0)
                for buf in mine:
                    c.free_later(buf)
            threads.append(threading.Thread(target=drop, daemon=True))
        interval = sys.getswitchinterval()
        sys.setswitchinterval(1e-5)
        try:
            for t in threads:
                t.start()
            go.set()
            deadline = time.monotonic() + 20.0
            while (any(t.is_alive() for t in threads)
                   and time.monotonic() < deadline):
                c.free_later(exe(x))
            for t in threads:
                t.join(5.0)
                assert not t.is_alive()
        finally:
            sys.setswitchinterval(interval)
        c.free_later(exe(x))
        c.usage()                               # flushes what is left
        assert sorted(h for h in freed if h in {b.handle for b in dead}) \
            == sorted(b.handle for b in dead)
        assert len(freed) == len(set(freed))
        assert set(sess.buffers) == {x.handle}
        assert proxy.hbm_accounting()["c"]["balanced"]


# -- output recycling: a step's outputs go into the buffers it freed ---------


def _carry_step(w, s, x):
    """A state-carrying step: ``w`` and ``s`` come back in their own shape
    and dtype (recyclable), the loss matches no input."""
    return w * 0.5 + x, s + 1, (w * x).sum()


def _start_carry(c, x=None):
    w = c.put(np.arange(32, dtype=np.float32).reshape(4, 8))
    s = c.put(np.int32(0))
    x = c.put(np.linspace(-1.0, 1.0, 8, dtype=np.float32) if x is None
              else x)
    return w, s, x, c.compile(_carry_step, w, s, x)


def _pointer(proxy, name, buf):
    return proxy._session(name).buffers[buf.handle].unsafe_buffer_pointer()


def _the_program(proxy):
    (prog,) = proxy._programs.values()
    return prog


def test_a_step_writes_its_state_into_the_buffers_it_freed(proxy):
    """Call 3 frees call 2's state on its own ``execute``: its two
    recyclable outputs live where those buffers lived, the counters say
    so, and every value is bitwise what a session that never frees reads.
    The two calls that free nothing run the plain program."""
    with connect(proxy, "c") as c, connect(proxy, "plain") as p:
        w, s, x, exe = _start_carry(c)
        pw, ps, px, pexe = _start_carry(p)
        sess = proxy._session("c")
        w1, s1, _ = exe(w, s, x)
        w2, s2, _ = exe(w1, s1, x)              # frees nothing
        assert (sess.out_count, sess.out_recycled) == (6, 0)
        prog = _the_program(proxy)
        single = prog.single
        assert single is not None and prog.recycle is not None
        freed = {_pointer(proxy, "c", w1), _pointer(proxy, "c", s1)}
        c.free_later(w1, s1)
        w3, s3, loss3 = exe(w2, s2, x)
        assert {_pointer(proxy, "c", w3), _pointer(proxy, "c", s3)} == freed
        assert (sess.out_count, sess.out_recycled) == (9, 2)
        assert prog.single is single and prog.recycle.result()[1] == 2
        usage = c.usage()["chip"]["sessions"]
        assert (usage["c"]["out_count"], usage["c"]["out_recycled"]) == (9, 2)
        for _ in range(3):
            pw, ps, ploss = pexe(pw, ps, px)
        assert proxy._session("plain").out_recycled == 0
        for mine, plain in ((w3, pw), (s3, ps), (loss3, ploss)):
            assert c.get(mine).tobytes() == p.get(plain).tobytes()
        assert _accounts(proxy, "c")["balanced"]


def test_a_scorer_shaped_program_compiles_no_second_executable(proxy):
    """One float out, matching no input: frees on its calls go back to the
    allocator and nothing but the single program is compiled."""
    def score(params, tokens):
        return (params["w"][tokens] * params["b"]).sum()

    with connect(proxy, "scorer") as c:
        params = {"w": c.put(np.ones((16, 4), np.float32)),
                  "b": c.put(np.ones(4, np.float32))}
        tokens = np.arange(6, dtype=np.int32)
        exe = c.compile(score, params, tokens)
        for _ in range(3):
            c.free_later(exe(params, tokens))
        assert proxy._session("scorer").executables[exe._exec_id] \
            .recycle_meta == []
        prog = _the_program(proxy)
        assert prog.single is not None and prog.recycle is None
        sess = proxy._session("scorer")
        assert (sess.out_count, sess.out_recycled) == (3, 0)


def _no_recycling(proxy, monkeypatch):
    """Fails the test if a call runs the recycling form."""
    def refuse(compiled, scratch):
        raise AssertionError("recycled")
    monkeypatch.setattr(proxy, "_recycling", refuse)


@pytest.mark.parametrize("held", [False, True])
def test_a_partial_match_runs_the_plain_program(proxy, monkeypatch, held):
    """Only ``w`` freed, or ``s`` freed while another live handle still
    holds its array: the frees do not cover the outputs, so the plain
    program runs, nothing is donated, and what the tenant still holds
    reads as before."""
    with connect(proxy, "c") as c:
        w, s, x, exe = _start_carry(c)
        w1, s1, _ = exe(w, s, x)
        sess = proxy._session("c")
        if held:
            # the same array under a second handle: donating it would
            # delete what that handle reads
            other = sess.fresh_id()
            sess.buffers[other] = sess.buffers[s1.handle]
            sess.hbm_used += 4
            c.free_later(w1, s1)
        else:
            c.free_later(w1)
        _no_recycling(proxy, monkeypatch)
        w2, s2, _ = exe(w, s, x)
        assert (sess.out_count, sess.out_recycled) == (6, 0)
        if held:
            assert int(np.asarray(sess.buffers[other])) == 1
        else:
            assert int(c.get(s1)) == 1
        assert int(c.get(s2)) == 1 and int(c.get(s)) == 0
        assert _accounts(proxy, "c")["balanced"]


def test_frees_sent_on_a_put_leave_the_next_call_plain(proxy, monkeypatch):
    """A tenant whose input is over ``INLINE_MAX`` puts it before every
    call, and the put carries the frees: the call then frees nothing, runs
    the plain program, and no copy of the state is made for it."""
    big = np.ones((protocol.INLINE_MAX // 4 + 1,), np.float32)

    def step(w, s, x):
        return w * 0.5, s + 1, x.sum()

    with connect(proxy, "c") as c:
        w = c.put(np.arange(32, dtype=np.float32).reshape(4, 8))
        s = c.put(np.int32(0))
        exe = c.compile(step, w, s, c.put(big))
        w, s, _ = exe(w, s, big)
        sess = proxy._session("c")
        real_put, copies = proxy._jax.device_put, []

        def put(x, *a, **kw):
            copies.append(type(x).__name__)
            return real_put(x, *a, **kw)

        _no_recycling(proxy, monkeypatch)
        monkeypatch.setattr(proxy._jax, "device_put", put)
        for _ in range(3):
            w, s, _ = exe(w, s, big)            # frees ride on the put
        assert (sess.out_count, sess.out_recycled) == (12, 0)
        assert copies == ["ndarray"] * 3        # the puts' host arrays
        assert int(c.get(s)) == 4 and _accounts(proxy, "c")["balanced"]


def test_over_tpu_mem_is_refused_before_dispatch_with_frees_riding(proxy):
    """The frees are refunded first and the outputs charged in full before
    anything runs: an execute whose outputs still pass the cap is refused,
    the frees stay applied, and what the tenant holds reads as before."""
    # w 128 + s 4 + x 32 = 164 B; a call's outputs 136 B
    with connect(proxy, "capped", memory=350) as c:
        w, s, x, exe = _start_carry(c)
        w1, s1, loss1 = exe(w, s, x)            # 300 of 350
        pad = c.put(np.zeros(12, np.float32))   # 348
        execs = proxy.total_execs
        c.free_later(w, s)                      # 216 + 136 > 350
        with pytest.raises(RuntimeError, match="HBM cap"):
            exe(w1, s1, x)
        assert proxy.total_execs == execs
        acct = _accounts(proxy, "capped")
        assert acct["balanced"] and acct["hbm_used"] == 216
        np.testing.assert_array_equal(
            c.get(w1), np.arange(32, dtype=np.float32).reshape(4, 8) * 0.5
            + np.linspace(-1.0, 1.0, 8, dtype=np.float32))
        c.free(pad)
        w2, s2, _ = exe(w1, s1, x)
        assert int(c.get(s2)) == 2 and _accounts(proxy, "capped")["balanced"]


@pytest.mark.parametrize("ran", [False, True])
def test_a_device_failure_with_recycled_scratch_leaves_the_books_balanced(
        proxy, monkeypatch, ran):
    """The program fails after the freed buffers were handed over, before
    it ran or after it consumed them: they were freed anyway, the output
    charge goes back, what the tenant still holds is intact, and the next
    call runs."""
    with connect(proxy, "c") as c:
        w, s, x, exe = _start_carry(c)
        w1, s1, loss1 = exe(w, s, x)
        held = c.usage()["hbm_used"] - 132     # w, s are about to go
        if ran:
            real, failed = proxy._run_to_completion, []

            def flaky(fn, args, sync_out):
                done = real(fn, args, sync_out)
                if failed:
                    return done
                failed.append(True)
                raise RuntimeError("injected device failure")

            monkeypatch.setattr(proxy, "_run_to_completion", flaky)
        else:
            _fail_next_program(proxy, monkeypatch)
        c.free_later(w, s)
        with pytest.raises(RuntimeError, match="injected device failure"):
            exe(w1, s1, x)
        acct = _accounts(proxy, "c")
        assert acct["balanced"] and acct["hbm_used"] == held
        assert proxy._session("c").out_recycled == 0
        assert int(c.get(s1)) == 1
        w2, s2, _ = exe(w1, s1, x)
        assert int(c.get(s2)) == 2 and _accounts(proxy, "c")["balanced"]
