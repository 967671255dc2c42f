"""Chip-proxy + client + pod-manager integration tests.

The proxy runs on the CPU backend here — the identical code path serves the
real chip (the proxy is backend-agnostic; ``bench.py`` is the on-hardware
proof). These are the tests the reference never had for its Gemini stack
(SURVEY §4: the de-facto integration test was a manual harness).
"""

import threading
import time

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubeshare_tpu.isolation import protocol
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
    train chunk whose attention is the PALLAS FLASH KERNEL ships through
    the proxy's fused-loop path (jax.export round-trip included) and
    converges — the two halves of the framework in one test."""
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
        loop = c.compile_loop(train_chunk, carry, bx, by)
        carry, first = loop(1, carry, bx, by)
        l0 = float(c.get(first))
        for _ in range(4):
            carry, loss = loop(10, carry, bx, by)
            c.free(loss)
        carry, last = loop(1, carry, bx, by)
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
        used_out[name] = c.usage()["exec_ms_total"]


def test_colocated_shares_follow_requests(proxy):
    """Two greedy clients at 0.75/0.25 → device-time shares ≈ 3:1."""
    stop = threading.Event()
    used: dict = {}
    threads = [
        threading.Thread(target=_greedy_client,
                         args=(proxy, "big", 0.75, stop, used)),
        threading.Thread(target=_greedy_client,
                         args=(proxy, "small", 0.25, stop, used)),
    ]
    for t in threads:
        t.start()
    time.sleep(2.5)
    stop.set()
    for t in threads:
        t.join(timeout=15.0)
    share = used["big"] / (used["big"] + used["small"])
    assert 0.6 <= share <= 0.9, used


def test_cost_model_not_inflated_by_token_contention(proxy):
    """VERDICT r3 weak-5 pin: the burst cost model must be fed gated
    EXECUTION time only — folding the token wait in would make
    _cap_repeat clamp bursts far below the intended budget exactly when
    the chip is contended."""
    def heavy(x):
        def body(i, a):
            return a @ a / jnp.linalg.norm(a)
        return jax.lax.fori_loop(0, 12, body, x)

    def light(x):
        return x @ x / jnp.linalg.norm(x)

    with connect(proxy, "hog", request=0.5) as hog, \
            connect(proxy, "victim", request=0.5) as victim:
        x = np.eye(300, dtype=np.float32) + 0.01
        hog_exe = hog.compile(heavy, x)
        vic_exe = victim.compile(light, x)
        hog_buf, vic_buf = hog.put(x), victim.put(x)
        # solo estimate, uncontended
        for _ in range(3):
            victim.free(*jax.tree_util.tree_leaves(vic_exe(vic_buf)))
        sess = proxy._sessions["victim"]
        solo_ms = sess.executables[vic_exe._exec_id].prog.step_ms
        assert solo_ms > 0

        stop = threading.Event()

        def hammer():
            while not stop.is_set():
                try:
                    hog.free(*jax.tree_util.tree_leaves(hog_exe(hog_buf)))
                except Exception:
                    return

        t = threading.Thread(target=hammer)
        t.start()
        time.sleep(0.2)          # hog owns the token much of the time
        walls = []
        try:
            for _ in range(8):
                t0 = time.monotonic()
                victim.free(*jax.tree_util.tree_leaves(vic_exe(vic_buf)))
                walls.append((time.monotonic() - t0) * 1e3)
        finally:
            stop.set()
            t.join(timeout=10)
        contended_ms = sess.executables[vic_exe._exec_id].prog.step_ms
        mean_wall = sum(walls) / len(walls)
        # the estimate must track device time, not the contended wall
        assert contended_ms < max(4 * solo_ms, 0.5 * mean_wall), (
            solo_ms, contended_ms, mean_wall)


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


def test_compile_loop_fuses_steps(proxy):
    """The fused-loop path runs N optimizer steps per dispatch and matches
    the per-step path's math."""
    rng = np.random.default_rng(1)
    w_true = rng.normal(size=(4,)).astype(np.float32)
    xs = rng.normal(size=(64, 4)).astype(np.float32)
    ys = xs @ w_true

    def step(w, batch):
        xb, yb = batch
        def loss(w):
            return jnp.mean((xb @ w - yb) ** 2)
        l, g = jax.value_and_grad(loss)(w)
        return w - 0.1 * g, l

    with connect(proxy, "looper") as c:
        w = c.put(np.zeros(4, np.float32))
        batch = (c.put(xs), c.put(ys))
        loop = c.compile_loop(step, w, batch)
        # Burst sizing warms up wall-time-bounded: the first dispatch is
        # clamped to ONE step (no time estimate yet); the second sizes
        # itself pessimistically (marginal cost assumed = the measured
        # single-call cost) — on CPU a step is microseconds, far under the
        # budget, so the request is granted in full, rounded DOWN to the
        # static-trip-count bucket (largest power of two ≤ 60).
        w, l = loop(60, w, batch)
        assert loop.last_n == 1
        c.free(l)
        used_before = c.usage()["exec_count"]
        w, l = loop(60, w, batch)
        assert loop.last_n == 32
        assert c.usage()["exec_count"] == used_before + 1  # ONE dispatch
        steps = 1 + 32
        while steps < 63:  # client asks again for the remainder
            c.free(l)
            w, l = loop(63 - steps, w, batch)
            steps += loop.last_n
        assert float(c.get(l)) < 1e-3
        np.testing.assert_allclose(c.get(w), w_true, atol=1e-2)
        # old carry was donated: only w, l, xs, ys alive
        expected = c.get(w).nbytes + c.get(l).nbytes + xs.nbytes + ys.nbytes
        assert c.usage()["hbm_used"] == expected


def test_program_cache_shared_across_sessions(proxy):
    """Identical clients export byte-identical programs; the proxy must
    compile and cost-profile them ONCE (sha-keyed _Program). The second
    session inherits the burst cost model, so its very first dispatch is
    already full-sized — no 1-step warmup, no duplicate multi-second XLA
    compile."""
    def step(w, b):
        return w + b, (w * 0.0).sum()

    with connect(proxy, "a") as ca:
        wa = ca.put(np.zeros(4, np.float32))
        ba = ca.put(np.ones(4, np.float32))
        la = ca.compile_loop(step, wa, ba)
        wa, aux = la(8, wa, ba)
        assert la.last_n == 1
        ca.free(aux)
        wa, aux = la(8, wa, ba)  # seeds the shared cost model
        assert len(proxy._programs) == 1

        with connect(proxy, "b") as cb:
            wb = cb.put(np.zeros(4, np.float32))
            bb = cb.put(np.ones(4, np.float32))
            lb = cb.compile_loop(step, wb, bb)
            assert len(proxy._programs) == 1  # same sha → shared entry
            wb, auxb = lb(8, wb, bb)
            assert lb.last_n == 8  # inherited cost model: no 1-step clamp
            np.testing.assert_allclose(cb.get(wb), np.full(4, 8.0))


def test_compile_loop_repeat_one(proxy):
    with connect(proxy, "one") as c:
        w = c.put(np.float32(2.0))
        loop = c.compile_loop(lambda w: (w * 2.0, w), w)
        w2, aux = loop(1, w)
        assert float(c.get(w2)) == 4.0
        assert float(c.get(aux)) == 2.0


def test_loop_arg_error_preserves_carry(proxy):
    """A shape mismatch must be rejected BEFORE dispatch: the donated
    carry is only consumed by a real device execution, so after a pure
    argument error the carry handles must still work."""
    with connect(proxy, "argerr") as c:
        w = c.put(np.float32(3.0))
        x = c.put(np.ones(2, np.float32))
        loop = c.compile_loop(lambda w, x: (w + 1.0, w), w, x)
        bad = c.put(np.ones(5, np.float32))  # wrong shape for x's slot
        with pytest.raises(RuntimeError, match="expects"):
            loop(1, w, bad)
        w2, aux = loop(1, w, x)  # carry survived the argument error
        assert float(c.get(w2)) == 4.0
        assert float(c.get(aux)) == 3.0


def test_plain_execute_rejects_repeat(proxy):
    with connect(proxy, "c") as c:
        x = np.ones(3, np.float32)
        exe = c.compile(lambda a: a + 1.0, x)
        bx = c.put(x)
        with pytest.raises(RuntimeError, match="loop program"):
            c._execute(exe._exec_id, [bx.handle], repeat=5)


def test_loop_carry_structure_checked(proxy):
    with connect(proxy, "bad") as c:
        w = c.put(np.float32(1.0))
        with pytest.raises(TypeError, match="carry structure"):
            c.compile_loop(lambda w: ((w, w), w), w)


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
    loop = c.compile_loop(lambda w: (w + 1.0, w), w)
    w, aux = loop(1, w)
    c.free(aux)
    host_w = float(c.get(w))           # checkpoint to host
    p1.close()                          # crash

    with pytest.raises((RuntimeError, OSError)):
        c.get(w)                        # dead proxy: clean error, no hang
    c.close()

    p2 = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN))
    p2.serve()
    try:
        with connect(p2, "phoenix") as c2:   # same name: fresh incarnation
            w2 = c2.put(np.float32(host_w))
            loop2 = c2.compile_loop(lambda w: (w + 1.0, w), w2)
            w2, aux2 = loop2(1, w2)
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


def test_chained_loop_matches_stepwise(proxy):
    """loop.chain(n, ...) must land on exactly the state n sequential
    steps produce — the server-side burst chaining changes dispatch
    shape, never math. The reply reports real steps (clamped chains
    are continued by asking again)."""
    def step(w, x):
        return w + x, (w ** 2).sum()

    with connect(proxy, "chain-a") as c:
        w0 = np.zeros(4, np.float32)
        x = np.full(4, 0.5, np.float32)
        wa = c.put(w0.copy())
        xa = c.put(x)
        loop = c.compile_loop(step, wa, xa)
        done = 0
        carry = wa
        while done < 37:
            carry, aux = loop.chain(37 - done, carry, xa)
            assert loop.last_n >= 1
            done += loop.last_n
            if done < 37:
                c.free(aux)
        assert done == 37
        np.testing.assert_allclose(c.get(carry), w0 + 37 * x)
        np.testing.assert_allclose(float(c.get(aux)),
                                   ((w0 + 36 * x) ** 2).sum())
        u = c.usage()
        assert u["exec_count"] >= 1     # every burst charged the gate


@pytest.mark.slow  # 3s measured co-location phase
def test_chained_loop_shares_stay_fair(proxy):
    """Two co-located chained clients still split device time by their
    equal requests — chaining must not let one client hold the chip
    past its quota (every burst renews at the gate)."""
    import jax.numpy as jnp

    def step(w, x):
        return w + jnp.tanh(w) * 0.01 + x * 0.0, (w ** 2).sum()

    results = {}
    barrier = threading.Barrier(2)

    def trainer(name):
        with connect(proxy, name, request=0.5, limit=1.0) as c:
            w = c.put(np.ones((64, 64), np.float32))
            x = c.put(np.zeros((64, 64), np.float32))
            loop = c.compile_loop(step, w, x)
            carry, aux = loop(1, w, x)   # seed the cost model
            c.free(aux)
            barrier.wait()
            deadline = time.monotonic() + 3.0
            while time.monotonic() < deadline:
                carry, aux = loop.chain(512, carry, x)
                c.free(aux)
            results[name] = c.usage()["exec_ms_total"]

    ts = [threading.Thread(target=trainer, args=(f"fair-{i}",))
          for i in range(2)]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    total = sum(results.values())
    assert total > 0
    share = max(results.values()) / total
    assert share <= 0.65, results      # ~50/50 within tolerance


def test_chained_loop_fails_clean_before_first_burst(proxy):
    """A failure BEFORE any burst dispatched leaves every buffer
    intact (normal error, nothing consumed)."""
    def step(w, x):
        return w / x, w.sum()

    with connect(proxy, "chain-err") as c:
        w = c.put(np.ones(4, np.float32))
        bad = c.put(np.zeros(4, np.float32))
        loop = c.compile_loop(step, w, bad)
        # division by zero doesn't raise in XLA; use a shape trap instead:
        # free the const out from under the chain via a second handle? No —
        # simplest deterministic failure: kill the executable's args by
        # freeing the const first, so the chain's arg fetch fails fast
        # BEFORE any burst (buffers intact, normal error).
        c.free(bad)
        with pytest.raises(RuntimeError):
            loop.chain(8, w, bad)
        # w was NOT consumed (failure before burst 0): still usable
        np.testing.assert_allclose(c.get(w), np.ones(4, np.float32))


def test_chained_loop_midchain_failure_consumes_carry(proxy, monkeypatch):
    """A failure AFTER the first burst reports the consumed carry (the
    donated handles are popped, HBM accounting stays clean) — the
    single-burst loop path's contract, chained."""
    def step(w, x):
        return w + x, w.sum()

    with connect(proxy, "chain-mid") as c:
        w = c.put(np.ones(4, np.float32))
        x = c.put(np.full(4, 0.5, np.float32))
        loop = c.compile_loop(step, w, x)

        calls = {"n": 0}
        real = proxy._run_fn

        def flaky(fn, args, timing=None, sync_out=None):
            calls["n"] += 1
            if calls["n"] > 1:           # burst 0 succeeds, burst 1 dies
                raise RuntimeError("injected device failure")
            return real(fn, args, timing, sync_out)

        monkeypatch.setattr(proxy, "_run_fn", flaky)
        with pytest.raises(RuntimeError, match="carry was consumed"):
            loop.chain(10_000, w, x)
        assert calls["n"] == 2
        # the donated carry handle is gone; the const survives
        with pytest.raises(RuntimeError):
            c.get(w)
        np.testing.assert_allclose(c.get(x), np.full(4, 0.5, np.float32))
        assert c.usage()["hbm_used"] == x.nbytes


def test_chained_loop_hbm_cap_returns_partial(proxy):
    """Running out of HBM mid-chain returns the VALID partial chain
    (steps done so far) instead of erroring — the client just sees a
    shorter chain and decides what to free."""
    def step(w, x):
        return w + x, (w * 2.0)          # aux same size as carry

    # cap: w(16)+x(16) resident, one out-set charge (32) fits (64<=72);
    # after burst 0 the donated w releases 16 (48), and burst 1's charge
    # (80>72) trips the cap with bursts>0 -> partial return, not error
    with connect(proxy, "chain-cap", memory=72) as c:
        w = c.put(np.zeros(4, np.float32))
        x = c.put(np.full(4, 1.0, np.float32))
        loop = c.compile_loop(step, w, x)
        carry, aux = loop.chain(10_000, w, x)
        # progress was made, the chain stopped early, the reply is usable
        assert 1 <= loop.last_n < 10_000
        got = c.get(carry)
        np.testing.assert_allclose(got, np.full(4, float(loop.last_n)))


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
