"""Resilience plane: fault matrix, transparent reconnect-and-replay,
crash recovery from the session journal, and live migration.

Every failure here is produced by the deterministic injectors in
``kubeshare_tpu.resilience.faults`` — the suite is reproducible
frame-for-frame, which is what makes "futures never see the failure"
an assertable property instead of a race.
"""

import threading
import time

import numpy as np
import pytest

import jax.numpy as jnp

from kubeshare_tpu.isolation import protocol
from kubeshare_tpu.isolation import proxy as proxy_mod
from kubeshare_tpu.isolation.client import ProxyClient
from kubeshare_tpu.isolation.proxy import ChipProxy
from kubeshare_tpu.isolation.tokensched import TokenScheduler
from kubeshare_tpu.obs.trace import Tracer, install_tracer, uninstall_tracer
from kubeshare_tpu.resilience import faults
from kubeshare_tpu.resilience import reconnect as rc
from kubeshare_tpu.resilience.migrate import migrate_session
from kubeshare_tpu.resilience.reconnect import (ReconnectPolicy, SessionLost,
                                                backoff_delays)

WINDOW = 1000.0
BASE = 100.0
MIN = 10.0

#: tight budget so failure paths resolve in test time, seeded so the
#: jittered backoff schedule is identical run to run
FAST = ReconnectPolicy(max_attempts=8, base_delay_s=0.02, max_delay_s=0.2,
                       dial_timeout_s=1.0, seed=7)


@pytest.fixture(autouse=True)
def _clear_faults():
    yield
    faults.uninstall()


def make_proxy(**kw):
    p = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN), **kw)
    p.serve()
    return p


@pytest.fixture
def proxy():
    p = make_proxy()
    yield p
    p.close()


def connect(p, name, policy=FAST, **kw):
    return ProxyClient("127.0.0.1", p.port, name, 0.5, 1.0,
                       reconnect=policy, **kw)


# -- negotiation --------------------------------------------------------------


def test_register_grants_resume_and_seq(proxy):
    with connect(proxy, "nego") as c:
        assert {"resume", "seq"} <= c.features
        assert c._conn.token
        x = np.arange(16, dtype=np.float32)
        np.testing.assert_array_equal(c.get(c.put(x)), x)


def test_unnegotiated_register_reply_unchanged(proxy):
    """A peer that never sent "features" gets the seed reply shape —
    no features echo, no resume token, no extra keys."""
    with protocol.Connection("127.0.0.1", proxy.port) as conn:
        reply, _ = conn.call({"op": "register", "name": "old", "request": 0.5,
                              "limit": 1.0, "memory": 0})
        assert set(reply) == {"ok", "platforms", "device"}
        reply, _ = conn.call({"op": "usage"})
        assert reply["hbm_used"] == 0
        conn.call({"op": "unregister"})


def test_backoff_delays_deterministic_and_capped():
    import random
    pol = ReconnectPolicy(base_delay_s=0.1, max_delay_s=0.4, jitter=0.5)
    a = [next(d) for d in [backoff_delays(pol, random.Random(42))]
         for _ in range(6)]
    b_gen = backoff_delays(pol, random.Random(42))
    b = [next(b_gen) for _ in range(6)]
    assert a[0] == 0.0
    assert a == b                      # same seed, same schedule
    assert all(x <= 0.4 * 1.5 for x in b)   # capped (plus jitter headroom)


# -- fault injector determinism ----------------------------------------------


def test_fault_injector_is_deterministic():
    spec = faults.FaultSpec(kill_conn_after_frames=3, kill_conn_repeat=2,
                            drop_reply_seq=4, seed=11)
    script = [("t", 1), ("t", 2), ("t", 1), ("t", 3), ("t", 2), ("t", 1)]
    runs = []
    for _ in range(2):
        inj = faults.Injector(spec)
        runs.append([inj.should_kill_connection(t, n) for t, n in script]
                    + [inj.should_drop_reply(s) for s in (1, 4, 4)])
    assert runs[0] == runs[1]
    assert sum(runs[0]) == 3           # 2 kills + 1 drop, never more


def test_fault_spec_from_env():
    inj = faults.from_env({"KUBESHARE_FAULTS":
                           "kill_conn_after_frames=5,kill_conn_tag=x,"
                           "delay_writer_ms=1.5",
                           "KUBESHARE_FAULT_SEED": "9"})
    assert inj.spec.kill_conn_after_frames == 5
    assert inj.spec.kill_conn_tag == "x"
    assert inj.spec.delay_writer_ms == 1.5
    assert inj.spec.seed == 9
    assert faults.from_env({}) is None


# -- reconnect-and-replay ----------------------------------------------------


def test_kill_mid_window_put_is_transparent(proxy):
    """The connection dies mid windowed upload; the caller sees a
    successful put and byte-identical data, never the failure."""
    resumed0 = rc._RECONNECTS.value("resumed")
    c = connect(proxy, "killput", fault_tag="victim", chunk_bytes=8192)
    big = np.arange(65536, dtype=np.float32).reshape(256, 256)
    faults.install(faults.Injector(faults.FaultSpec(
        kill_conn_after_frames=4, kill_conn_tag="victim")))
    buf = c.put(big)
    faults.uninstall()
    np.testing.assert_array_equal(c.get(buf), big)
    assert rc._RECONNECTS.value("resumed") > resumed0
    c.close()


def test_in_flight_execute_future_survives_kill(proxy):
    """An execute dispatched right before the connection dies resolves
    through the replay — the rid dedups against the proxy's reply cache,
    so the step ran exactly once."""
    c = connect(proxy, "killexec", fault_tag="evict")
    x = np.full((32, 32), 3.0, np.float32)
    bx = c.put(x)
    exe = c.compile(lambda a: a * 2.0, bx)
    faults.install(faults.Injector(faults.FaultSpec(
        kill_conn_after_frames=1, kill_conn_tag="evict")))
    fut = exe.call_async(bx)           # this frame triggers the kill
    out = fut.result()
    faults.uninstall()
    np.testing.assert_array_equal(c.get(out), 2.0 * x)
    assert c.usage()["exec_count"] == 1   # replayed, not re-executed
    c.close()


def test_lost_reply_recovered_via_request_timeout(proxy):
    """The server handles the request but its reply is dropped on the
    wire: the presumed-lost timer forces a reconnect and the replayed rid
    is answered from the reply cache."""
    pol = ReconnectPolicy(max_attempts=4, base_delay_s=0.02,
                          max_delay_s=0.1, dial_timeout_s=1.0,
                          request_timeout_s=0.3, seed=5)
    c = connect(proxy, "dropped", policy=pol)
    x = np.arange(64, dtype=np.float32)
    bx = c.put(x)                      # pipelined seq 1
    faults.install(faults.Injector(faults.FaultSpec(drop_reply_seq=2)))
    assert c.usage()["hbm_used"] == x.nbytes   # seq 2: reply dropped
    faults.uninstall()
    np.testing.assert_array_equal(c.get(bx), x)
    c.close()


@pytest.mark.parametrize("how", ["reply-lost", "connection-killed"])
def test_replayed_inline_execute_runs_once_and_gets_the_same_reply(
        proxy, how):
    """An execute is not idempotent, and with "inline" its reply carries
    a value: the reply stays blobless so the replay cache keeps it whole.
    The replayed request (its blob of inline inputs sent again) is
    answered with the SAME handles and the SAME value, and the program
    ran once."""
    pol = ReconnectPolicy(max_attempts=4, base_delay_s=0.02,
                          max_delay_s=0.1, dial_timeout_s=1.0,
                          request_timeout_s=0.3, seed=5)
    c = connect(proxy, "once", policy=pol, fault_tag="once")
    w = c.put(np.arange(8, dtype=np.float32))
    exe = c.compile(lambda w, x, n: jnp.sum(w * x) * n, w,
                    np.zeros(8, np.float32), np.int32(0))
    sess = proxy._session("once")
    served0 = proxy_mod._REPLAY_SERVED.value()
    if how == "reply-lost":
        spec = faults.FaultSpec(
            drop_reply_seq=c._conn._conn._next_seq + 1)
    else:
        spec = faults.FaultSpec(kill_conn_after_frames=1,
                                kill_conn_tag="once")
    faults.install(faults.Injector(spec))
    out = exe(w, np.full(8, 2.0, np.float32), np.int32(3))
    faults.uninstall()
    if how == "reply-lost":     # (a killed connection MAY die unserved)
        assert proxy_mod._REPLAY_SERVED.value() == served0 + 1
    assert sess.exec_count == 1 and sess.inline_in_total == 2
    assert sess.inline_out_total == 1
    # what the replay was answered with is the first reply, value and all
    assert out.handle in sess.buffers and len(sess.buffers) == 2
    assert out.value is not None
    r0 = sess.rpc_count
    assert float(c.get(out)) == 2.0 * 28.0 * 3.0 and sess.rpc_count == r0
    np.testing.assert_array_equal(
        np.asarray(sess.buffers[out.handle]), c.get(out))
    assert proxy.hbm_accounting()["once"]["balanced"]
    c.close()


def test_budget_exhausted_surfaces_session_lost():
    p = make_proxy()
    pol = ReconnectPolicy(max_attempts=2, base_delay_s=0.01,
                          max_delay_s=0.02, dial_timeout_s=0.2, seed=1)
    c = connect(p, "doomed", policy=pol)
    bx = c.put(np.zeros(8, np.float32))
    p.crash()                          # proxy gone for good: listener and
    time.sleep(0.05)                   # every live connection severed
    with pytest.raises(SessionLost):
        c.get(bx)
    assert not c._conn.healthy
    c.close()                          # teardown skips the dead unregister
    p.close()


def test_resume_token_is_required_capability(proxy):
    """A resume with a bogus token is refused permanently (state is
    gone), not retried into the budget."""
    conn = protocol.Connection("127.0.0.1", proxy.port)
    with pytest.raises(RuntimeError, match="unknown resume token"):
        conn.call({"op": "register", "resume": "beef" * 8})
    conn.close()


# -- credit / HBM accounting under repeated kills (regression) ---------------


def test_kill_mid_window_keeps_credit_and_hbm_stable(proxy):
    """Regression for the credit-leak window: a connection dying between
    reader enqueue and writer completion must release its SERVER_CREDIT
    permits and GC half-landed staging sinks. Looping kill-mid-window
    must leave the transport's inflight gauge at zero and the session's
    HBM accounting exact — no creep per kill."""
    big = np.arange(65536, dtype=np.float32).reshape(256, 256)
    c = connect(proxy, "leakcheck", fault_tag="leak", chunk_bytes=8192)
    for _ in range(3):
        faults.install(faults.Injector(faults.FaultSpec(
            kill_conn_after_frames=4, kill_conn_tag="leak")))
        buf = c.put(big)               # dies mid-window, retries, lands
        faults.uninstall()
        assert c.usage()["hbm_used"] == big.nbytes
        c.free(buf)
        assert c.usage()["hbm_used"] == 0
        deadline = time.monotonic() + 2.0
        while (protocol._INFLIGHT.value() != 0.0
               and time.monotonic() < deadline):
            time.sleep(0.01)
        assert protocol._INFLIGHT.value() == 0.0
    # no staged uploads left behind proxy-side either
    sess = proxy._session("leakcheck")
    assert not sess.staging
    c.close()


# -- crash + journal recovery (acceptance) -----------------------------------


def test_proxy_crash_mid_stream_recovers_from_journal(tmp_path):
    """Kill the proxy mid windowed put with an execute in flight; restart
    it from the journal on a NEW port; flip the client's endpoint. Both
    futures resolve byte-identical — the caller never saw the crash."""
    p1 = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN),
                   journal_dir=str(tmp_path))
    p1.serve()
    pol = ReconnectPolicy(max_attempts=30, base_delay_s=0.05,
                          max_delay_s=0.25, dial_timeout_s=1.0, seed=3)
    c = ProxyClient("127.0.0.1", p1.port, "crashy", 0.5, 1.0,
                    reconnect=pol, chunk_bytes=8192)
    x = np.arange(1024, dtype=np.float32)
    bx = c.put(x)                           # journaled (single-frame put)
    exe = c.compile(lambda a: a + 1.0, bx)  # journaled program
    big = np.arange(65536, dtype=np.float32).reshape(256, 256)

    faults.install(faults.Injector(faults.FaultSpec(
        crash_proxy_after_chunks=3)))
    fut = exe.call_async(bx)                # in flight across the crash
    done: dict = {}

    def uploader():
        try:
            done["buf"] = c.put(big)
        except Exception as exc:            # pragma: no cover - failure path
            done["err"] = exc

    t = threading.Thread(target=uploader)
    t.start()
    deadline = time.monotonic() + 10.0
    while not p1._crashed and time.monotonic() < deadline:
        time.sleep(0.01)
    assert p1._crashed                      # kill -9 equivalent: no cleanup
    faults.uninstall()

    p2 = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN),
                   journal_dir=str(tmp_path))
    p2.serve()                              # restores session from journal
    c.set_endpoint("127.0.0.1", p2.port)

    t.join(timeout=60)
    assert not t.is_alive() and "err" not in done, done.get("err")
    out = fut.result()                      # the pre-crash execute resolves
    np.testing.assert_array_equal(c.get(out), x + 1.0)
    np.testing.assert_array_equal(c.get(bx), x)          # journaled buffer
    np.testing.assert_array_equal(c.get(done["buf"]), big)
    # accounting is exact after the replayed/restarted upload
    expected = x.nbytes + big.nbytes + np.asarray(out.shape).prod() * 4
    assert c.usage()["hbm_used"] == int(expected)
    c.close()
    p2.close()
    p1.close()


# -- live migration (acceptance) ---------------------------------------------


def test_live_migration_end_to_end(tmp_path):
    """drain → export → import → endpoint flip: buffers and the compiled
    program survive verbatim, the client transparently follows the moved
    tombstone, the source refuses new sessions, and the migration span is
    recorded."""
    tracer = install_tracer(Tracer())
    p1 = make_proxy()
    p2 = make_proxy()
    try:
        c = connect(p1, "mover")
        x = np.arange(4096, dtype=np.float32).reshape(64, 64)
        bx = c.put(x)
        exe = c.compile(lambda a: a * 3.0, bx)
        out0 = exe(bx)
        np.testing.assert_array_equal(c.get(out0), 3.0 * x)
        c.free(out0)

        token = c._conn.token
        res = migrate_session(("127.0.0.1", p1.port),
                              ("127.0.0.1", p2.port), token,
                              drain=True, trace_id="trc-mig")
        assert res["name"] == "mover" and res["moved"][1] == p2.port

        # the client's next ops ride the tombstone redirect
        out = exe(bx)                       # program cache moved intact
        np.testing.assert_array_equal(c.get(out), 3.0 * x)
        np.testing.assert_array_equal(c.get(bx), x)
        assert c._conn.endpoint == ("127.0.0.1", p2.port)

        # source: session gone, drain refuses newcomers
        assert p1.scheduler.core.client_count() == 0
        with pytest.raises(RuntimeError, match="draining"):
            ProxyClient("127.0.0.1", p1.port, "newbie", 0.5, 1.0)

        spans = {s.name: s for s in tracer.spans("trc-mig")}
        assert spans["migrate"].attrs["outcome"] == "moved"
        assert spans["migrate"].attrs["buffers"] == 1
        assert spans["migrate"].attrs["programs"] == 1
        assert "migrate.buffer" in spans
        c.close()
    finally:
        uninstall_tracer()
        p1.close()
        p2.close()


def test_migration_failure_leaves_source_authoritative():
    """Losing the destination mid-copy must not destroy the source
    session: migrate_finish never ran, so the client keeps working
    against the source after `migrating` clears."""
    p1 = make_proxy()
    try:
        c = connect(p1, "stay")
        x = np.arange(256, dtype=np.float32)
        bx = c.put(x)
        token = c._conn.token
        # destination refuses the dial: nothing past migrate_begin runs
        with pytest.raises(OSError):
            migrate_session(("127.0.0.1", p1.port), ("127.0.0.1", 1), token)
        np.testing.assert_array_equal(c.get(bx), x)
        c.close()
    finally:
        p1.close()


def test_dispatcher_plans_migration_destination():
    """plan_migration reuses the filter→score pipeline to pick a
    destination off the pod's node — advisory, nothing is booked."""
    from kubeshare_tpu import constants as C
    from kubeshare_tpu.scheduler import SchedulerEngine
    from kubeshare_tpu.scheduler.dispatcher import Dispatcher
    from kubeshare_tpu.telemetry import TelemetryRegistry
    from kubeshare_tpu.topology.discovery import FakeTopology

    eng = SchedulerEngine()
    by_host: dict = {}
    for chip in FakeTopology(hosts=2, mesh=(2, 2)).chips():
        by_host.setdefault(chip.host, []).append(chip)
    for host, chips in by_host.items():
        eng.add_node(host, chips)
    disp = Dispatcher(eng, TelemetryRegistry())
    key = disp.submit("ns", "p", {C.POD_TPU_REQUEST: "0.5",
                                  C.POD_TPU_LIMIT: "1.0"})
    disp.step()
    src = disp.outcome(key).binding.node

    plan = disp.plan_migration(key)
    assert plan is not None
    assert plan["from"] == src and plan["node"] != src
    assert plan["node"] in plan["scores"]
    # nothing booked: planning twice is idempotent
    assert disp.plan_migration(key) == plan
    # with every other node excluded there is nowhere to go
    others = [n for n in eng.nodes if n != src]
    assert disp.plan_migration(key, exclude=others) is None
    assert disp.plan_migration("ns/ghost") is None


# -- latency-class round-trip (serving plane rides recovery verbatim) --------


def test_latency_class_survives_journal_crash_recovery(tmp_path):
    """A latency-class session (the serving plane's front-door tenants)
    restores from the journal with its class intact: the restarted
    scheduler re-registers the client as ``latency``, so priority
    admission keeps holding after a crash — not just the buffers."""
    p1 = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN),
                   journal_dir=str(tmp_path))
    p1.serve()
    c = connect(p1, "lat-crash", tpu_class="latency")
    x = np.arange(64, dtype=np.float32)
    bx = c.put(x)
    assert p1._session("lat-crash").tpu_class == "latency"
    p1.crash()

    p2 = ChipProxy(scheduler=TokenScheduler(WINDOW, BASE, MIN),
                   journal_dir=str(tmp_path))
    p2.serve()
    c.set_endpoint("127.0.0.1", p2.port)
    np.testing.assert_array_equal(c.get(bx), x)
    assert p2._session("lat-crash").tpu_class == "latency"
    assert p2.scheduler._classes["lat-crash"] == "latency"
    c.close()
    p2.close()
    p1.close()


def test_latency_class_survives_live_migration():
    """Live migration exports/imports the session manifest's ``class``
    key: the destination session and its token scheduler both see
    ``latency``, so a migrated serving tenant keeps its priority."""
    p1 = make_proxy()
    p2 = make_proxy()
    try:
        c = connect(p1, "lat-mover", tpu_class="latency")
        x = np.arange(128, dtype=np.float32)
        bx = c.put(x)
        assert p1._session("lat-mover").tpu_class == "latency"
        migrate_session(("127.0.0.1", p1.port), ("127.0.0.1", p2.port),
                        c._conn.token, drain=True)
        np.testing.assert_array_equal(c.get(bx), x)
        assert p2._session("lat-mover").tpu_class == "latency"
        assert p2.scheduler._classes["lat-mover"] == "latency"
        c.close()
    finally:
        p1.close()
        p2.close()


@pytest.mark.parametrize("how", ["journal", "migrate"])
def test_older_proxys_ncarry_restores_as_a_plain_program(tmp_path, how):
    """A journal or a migration written by a proxy that still had the
    fused-loop path records ``ncarry`` beside a program. Run once such a
    program IS one step, so it restores as a plain one: recovery never
    fails on the old field, and the next execute runs one step."""
    import json

    p1 = make_proxy(journal_dir=str(tmp_path) if how == "journal" else None)
    p2 = None
    try:
        c = connect(p1, "looper")
        w, x = c.put(np.zeros(4, np.float32)), c.put(np.ones(4, np.float32))
        exe = c.compile(lambda w, x: (w + x, w.sum()), w, x)
        token = c._conn.token
        if how == "journal":
            p1.crash()
            path = tmp_path / f"{token}.json"
            manifest = json.loads(path.read_text())
            assert manifest["programs"]
            for spec in manifest["programs"]:
                spec["ncarry"] = 1          # as the older proxy wrote it
            path.write_text(json.dumps(manifest))
            p2 = make_proxy(journal_dir=str(tmp_path))
            c.set_endpoint("127.0.0.1", p2.port)
        else:
            # the source answers as the older proxy did: ncarry in the
            # manifest's programs and in export_program's reply
            admin = p1._handle_admin

            def older(op, req, state):
                reply = admin(op, req, state)
                if op == "export_session":
                    for spec in reply["manifest"]["programs"]:
                        spec["ncarry"] = 1
                elif op == "export_program":
                    reply["ncarry"] = 1
                return reply

            p1._handle_admin = older
            p2 = make_proxy()
            res = migrate_session(("127.0.0.1", p1.port),
                                  ("127.0.0.1", p2.port), token)
            assert res["programs"] == [{"exec_id": exe._exec_id,
                                        "ncarry": 1}]
        w2, aux = exe(w, x)                 # one execute = one step
        np.testing.assert_array_equal(c.get(w2), np.ones(4, np.float32))
        assert float(c.get(aux)) == 0.0
        assert p2._session("looper").exec_count == 1
        # what this proxy writes and exports records no such field
        assert p2._manifest(p2._session("looper"))["programs"] == [
            {"exec_id": exe._exec_id}]
        c.close()
    finally:
        p1.close()
        if p2 is not None:
            p2.close()
