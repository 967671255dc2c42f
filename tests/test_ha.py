"""Control-plane HA (doc/ha.md): replicated registry, epoch-fenced
leadership, warm-standby scheduler takeover, client failover.

The invariants under test:

- **Single writer**: exactly one dispatcher publishes binds at any
  epoch; a deposed leader's fenced writes are refused 409 and it
  freezes rather than retries.
- **Bounded-lag replication**: the follower tails the leader's
  op-stream with a durable cursor; a stream change or a cursor behind
  the window rebases from snapshot; follower reads carry staleness
  marks and follower writes are refused with the leader hint.
- **Warm takeover**: a standby reconstructs engine state from the
  registry and unfreezes at the next epoch when the lease expires; the
  decision recorder and flight recorder both mark the transition.
- **HA off = byte-identical**: no fence kwargs, no extra headers, no
  extra metric families, the exact pre-HA journal.
"""

import json
import urllib.error
import urllib.request

import pytest

from kubeshare_tpu import constants as C
from kubeshare_tpu.ha import (LeadershipManager, ReplicationFollower,
                              WarmStandby)
from kubeshare_tpu.scheduler import SchedulerEngine
from kubeshare_tpu.scheduler.dispatcher import Dispatcher
from kubeshare_tpu.scheduler.service import SchedulerService
from kubeshare_tpu.telemetry import (FencedWriteError, NotLeaderError,
                                     RegistryClient, TelemetryRegistry,
                                     sync_engine_from_registry)
from kubeshare_tpu.topology.discovery import FakeTopology


class _TickClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


def _capacity(reg, node="tpu-host-0"):
    chips = [c for c in FakeTopology(hosts=1, mesh=(2, 2)).chips()
             if c.host == node]
    reg.put_capacity(node, [c.to_labels() for c in chips])
    return chips


def shared(request="0.5", limit="1.0", **extra):
    labels = {C.POD_TPU_REQUEST: request, C.POD_TPU_LIMIT: limit}
    labels.update(extra)
    return labels


# -- replication ---------------------------------------------------------------


def test_replication_incremental_apply(tmp_path):
    leader = TelemetryRegistry()
    follower = TelemetryRegistry(journal=str(tmp_path / "f.jsonl"))
    repl = ReplicationFollower(follower, leader)
    _capacity(leader)
    leader.put_lease("tpu-host-0", 3)
    assert repl.step()
    assert repl.in_sync()
    assert "tpu-host-0" in follower.capacity()
    assert follower.leases()["tpu-host-0"]["epoch"] == 3
    # a second pull with nothing new stays at head
    assert repl.step() and repl.in_sync()
    leader.put_pod("ns/p0", {"node": "tpu-host-0"})
    assert repl.step()
    assert "ns/p0" in follower.pods()


def test_replication_rebase_on_stream_change(tmp_path):
    """A leader restart begins a new stream id — the follower's cursor
    is meaningless there and the next pull must rebase from snapshot
    instead of gluing two incarnations' op-streams together."""
    j = str(tmp_path / "leader.jsonl")
    leader = TelemetryRegistry(journal=j)
    follower = TelemetryRegistry(journal=str(tmp_path / "f.jsonl"))
    repl = ReplicationFollower(follower, leader)
    _capacity(leader)
    assert repl.step() and repl.rebases == 0
    leader.close()
    leader2 = TelemetryRegistry(journal=j)         # new incarnation
    leader2.put_lease("tpu-host-0", 9)
    repl.source = leader2
    assert repl.step()
    assert repl.rebases == 1
    assert follower.leases()["tpu-host-0"]["epoch"] == 9
    assert "tpu-host-0" in follower.capacity()     # snapshot, not diff
    leader2.close()


def test_replication_cursor_durable_across_follower_restart(tmp_path):
    j = str(tmp_path / "f.jsonl")
    leader = TelemetryRegistry()
    follower = TelemetryRegistry(journal=j)
    repl = ReplicationFollower(follower, leader)
    _capacity(leader)
    assert repl.step()
    cursor, stream = repl.cursor, repl.stream
    assert cursor > 0
    follower.close()
    # the restarted follower resumes from its journaled cursor: the
    # next pull is incremental (no rebase) and only ships new ops
    follower2 = TelemetryRegistry(journal=j)
    repl2 = ReplicationFollower(follower2, leader)
    assert (repl2.cursor, repl2.stream) == (cursor, stream)
    leader.put_lease("tpu-host-0", 2)
    assert repl2.step()
    assert repl2.rebases == 0
    assert follower2.leases()["tpu-host-0"]["epoch"] == 2
    follower2.close()


def test_replication_window_overflow_rebases():
    from kubeshare_tpu.telemetry.registry import REPLICATION_WINDOW

    leader = TelemetryRegistry()
    follower = TelemetryRegistry()
    repl = ReplicationFollower(follower, leader)
    _capacity(leader)
    assert repl.step() and repl.rebases == 0
    for i in range(REPLICATION_WINDOW + 10):   # cursor falls off the log
        leader.put_lease("n-burst", i + 1)
    assert repl.step()
    assert repl.rebases == 1
    assert follower.leases()["n-burst"]["epoch"] == REPLICATION_WINDOW + 10


def test_follower_refuses_writes_and_promote_reopens(tmp_path):
    leader = TelemetryRegistry()
    follower = TelemetryRegistry(journal=str(tmp_path / "f.jsonl"))
    repl = ReplicationFollower(follower, leader, leader_hint="the-leader")
    with pytest.raises(NotLeaderError) as ei:
        follower.put_lease("n0", 1)
    assert ei.value.leader == "the-leader"
    with pytest.raises(NotLeaderError):
        _capacity(follower)
    _capacity(leader)
    assert repl.step()
    repl.promote()
    follower.put_lease("n0", 1)                # writable again
    assert follower.leases()["n0"]["epoch"] == 1
    follower.close()


def test_follower_http_307_and_staleness_marks(tmp_path):
    """Over the wire: follower reads answer with explicit staleness
    marks; follower writes answer 307 with the leader in Location. A
    leader's responses carry neither — the HA-off wire is untouched."""
    leader = TelemetryRegistry()
    leader.serve()
    follower = TelemetryRegistry(journal=str(tmp_path / "f.jsonl"))
    ReplicationFollower(follower,
                        RegistryClient("127.0.0.1", leader.port),
                        leader_hint=f"127.0.0.1:{leader.port}").step()
    follower.serve()
    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{follower.port}/capacity") as r:
            assert r.headers["X-Kubeshare-Replica"] == "follower"
            assert r.headers["X-Kubeshare-Leader"] \
                == f"127.0.0.1:{leader.port}"
        req = urllib.request.Request(
            f"http://127.0.0.1:{follower.port}/lease/n0",
            data=json.dumps({"epoch": 1}).encode(), method="PUT")
        with pytest.raises(urllib.error.HTTPError) as ei:
            urllib.request.urlopen(req)
        assert ei.value.code == 307
        assert f"127.0.0.1:{leader.port}" in ei.value.headers["Location"]
        # leader responses carry no replica headers (byte-identity gate)
        with urllib.request.urlopen(
                f"http://127.0.0.1:{leader.port}/capacity") as r:
            assert r.headers.get("X-Kubeshare-Replica") is None
            assert r.headers.get("X-Kubeshare-Staleness-S") is None
    finally:
        leader.close()
        follower.close()


# -- leadership + fencing ------------------------------------------------------


def test_leadership_acquire_renew_depose_epochs():
    clock = _TickClock(100.0)
    reg = TelemetryRegistry(clock=clock)
    a = LeadershipManager(reg, "scheduler", "a", ttl_s=5.0, clock=clock)
    b = LeadershipManager(reg, "scheduler", "b", ttl_s=5.0, clock=clock)
    assert a.step() and a.epoch == 1
    assert not b.step()                        # live leader: stand by
    clock.t += 2.0
    assert a.step() and a.epoch == 1           # renewal, same incarnation
    clock.t += 6.0                             # a's lease expires
    assert b.step() and b.epoch == 2           # takeover at the next epoch
    assert not a.step()                        # a discovers it was deposed
    assert a.epoch == 2                        # and learns the new epoch


def test_leadership_survives_registry_failover(tmp_path):
    """The scheduler leadership lease replicates like any lease, and
    journal replay resets its timestamp — so after a registry failover
    the SAME holder renews at the SAME epoch on the promoted follower
    (one-TTL restart grace instead of a spurious scheduler takeover)."""
    clock = _TickClock(100.0)
    leader = TelemetryRegistry(clock=clock)
    follower = TelemetryRegistry(journal=str(tmp_path / "f.jsonl"),
                                 clock=clock)
    repl = ReplicationFollower(follower, leader, clock=clock)
    mgr = LeadershipManager(leader, "scheduler", "sched-a", ttl_s=5.0,
                            clock=clock)
    assert mgr.step() and mgr.epoch == 1
    assert repl.step()
    repl.promote()                              # registry failover
    mgr.registry = follower
    clock.t += 2.0
    assert mgr.step()                           # renewal, not takeover
    assert mgr.epoch == 1
    assert follower.leader("scheduler")["holder"] == "sched-a"
    follower.close()


def test_fenced_pod_writes_in_process():
    reg = TelemetryRegistry()
    reg.acquire_leader("scheduler", "a", 3, ttl_s=60.0)
    reg.put_pod("ns/p", {"node": "n0"}, fence=3)       # current: accepted
    reg.put_pod("ns/p", {"node": "n0"}, fence=7)       # newer: accepted
    with pytest.raises(FencedWriteError) as ei:
        reg.put_pod("ns/p", {"node": "n1"}, fence=2)   # deposed: refused
    assert (ei.value.fence, ei.value.current) == (2, 3)
    with pytest.raises(FencedWriteError):
        reg.drop_pod("ns/p", fence=1)
    assert reg.pods()["ns/p"]["node"] == "n0"          # write never landed
    assert list(reg.fence_log) == [3, 7]               # accepted epochs only
    # no fence = the exact pre-HA path, regardless of lease state
    reg.put_pod("ns/q", {"node": "n1"})
    assert list(reg.fence_log) == [3, 7]


def test_fenced_write_409_over_http():
    reg = TelemetryRegistry()
    reg.serve()
    try:
        client = RegistryClient("127.0.0.1", reg.port)
        reg.acquire_leader("scheduler", "a", 5, ttl_s=60.0)
        client.put_pod("ns/p", {"node": "n0"}, fence=5)
        with pytest.raises(FencedWriteError) as ei:
            client.put_pod("ns/p", {"node": "n1"}, fence=4)
        assert ei.value.current == 5
        with pytest.raises(FencedWriteError):
            client.drop_pod("ns/p", fence=4)
        assert reg.pods()["ns/p"]["node"] == "n0"
    finally:
        reg.close()


# -- warm standby --------------------------------------------------------------


def _engine_with_fleet(reg):
    eng = SchedulerEngine()
    sync_engine_from_registry(eng, reg)
    return eng


def test_standby_freezes_then_takes_over():
    clock = _TickClock(100.0)
    reg = TelemetryRegistry(clock=clock)
    _capacity(reg)
    # the primary leads and binds a pod
    primary = Dispatcher(_engine_with_fleet(reg), reg, clock=clock)
    pha = WarmStandby(primary, reg, "primary", ttl_s=5.0, clock=clock)
    assert pha.step() and not primary.frozen
    primary.submit("ns", "p0", shared())
    primary.step()
    assert "ns/p0" in reg.pods()
    # the standby stays frozen and warm while the primary renews
    standby = Dispatcher(SchedulerEngine(), reg, clock=clock)
    sha = WarmStandby(standby, reg, "standby", ttl_s=5.0, clock=clock,
                      resync_period_s=1.0)
    assert not sha.step() and standby.frozen
    clock.t += 2.0
    assert pha.step() and not sha.step()
    assert standby.engine.chips_by_node          # kept warm: fleet synced
    # the primary goes silent past the TTL: the standby takes over at
    # the next epoch with the bound pod reconstructed, and unfreezes
    clock.t += 6.0
    assert sha.step()
    assert not standby.frozen
    assert sha.lead.epoch == 2
    assert "ns/p0" in standby.engine.pod_status
    assert standby.engine.pod_status["ns/p0"].node_name == "tpu-host-0"
    assert sha.takeover_count == 1
    # the silent ex-leader discovers the new epoch and freezes
    assert not pha.step()
    assert primary.frozen
    assert "deposed" in primary.frozen_reason


def test_deposed_dispatcher_fenced_write_freezes():
    """The OTHER half of split-brain handling: a deposed dispatcher
    that never ran its own election step (a partition) discovers the
    takeover through a fenced 409 at publish time — and freezes instead
    of retrying a write that can never succeed."""
    clock = _TickClock(100.0)
    reg = TelemetryRegistry(clock=clock)
    _capacity(reg)
    disp = Dispatcher(_engine_with_fleet(reg), reg, clock=clock)
    disp.attach_fencing(lambda: 1)             # believes it leads at 1
    reg.acquire_leader("scheduler", "usurper", 2, ttl_s=60.0)
    disp.submit("ns", "p0", shared())
    disp.step()
    assert disp.frozen
    assert "fenced" in disp.frozen_reason
    assert "ns/p0" not in reg.pods()           # the bind never landed
    # the pod is requeued, not lost: a thaw (re-election) can place it
    assert "ns/p0" in disp._pending or "ns/p0" in disp._retry_at


def test_takeover_marks_decisions_and_flightrecorder():
    from kubeshare_tpu.obs.decisions import DecisionRecorder
    from kubeshare_tpu.obs.flight import default_recorder

    clock = _TickClock(100.0)
    reg = TelemetryRegistry(clock=clock)
    _capacity(reg)
    disp = Dispatcher(SchedulerEngine(), reg, clock=clock)
    decisions = DecisionRecorder()
    sha = WarmStandby(disp, reg, "standby", ttl_s=5.0, clock=clock,
                      decisions=decisions)
    # the recorder is process-wide and keeps its last few dumps: count the
    # dumps that are new, not the length (full once other tests dumped)
    before = {(d["t"], d["seq"]) for d in default_recorder().state()["dumps"]}
    assert sha.step()                           # nobody led: acquires
    lead = [d for d in decisions.state()["recent"]
            if d["kind"] == "leadership"]
    assert lead and lead[-1]["epoch"] == 1
    assert lead[-1]["holder"] == "standby"
    dumps = default_recorder().state()["dumps"]
    assert [d for d in dumps if (d["t"], d["seq"]) not in before] == [
        dumps[-1]]
    assert dumps[-1]["reason"] == "leadership-transition"


# -- client failover -----------------------------------------------------------


def test_registry_client_rotates_endpoints_on_failure():
    reg = TelemetryRegistry()
    reg.serve()
    try:
        # first endpoint is a dead port: the client rotates and succeeds
        client = RegistryClient(["127.0.0.1:1", f"127.0.0.1:{reg.port}"],
                                seed=7)
        client.RETRY_BACKOFF_S = 0.001
        client.put_lease("n0", 1)
        assert reg.leases()["n0"]["epoch"] == 1
        # sticky: subsequent calls go straight to the live endpoint
        assert client._base.endswith(str(reg.port))
    finally:
        reg.close()


def test_registry_client_follows_307_to_leader(tmp_path):
    leader = TelemetryRegistry()
    leader.serve()
    follower = TelemetryRegistry(journal=str(tmp_path / "f.jsonl"))
    ReplicationFollower(follower,
                        RegistryClient("127.0.0.1", leader.port),
                        leader_hint=f"127.0.0.1:{leader.port}").step()
    follower.serve()
    try:
        # a client pointed only at the follower lands its write on the
        # leader through the 307 redirect — no reconfiguration
        client = RegistryClient("127.0.0.1", follower.port)
        client.put_lease("n0", 4)
        assert leader.leases()["n0"]["epoch"] == 4
    finally:
        leader.close()
        follower.close()


class _FakeResp:
    status = 200

    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False

    def read(self):
        return b'{"ok": true}'


def test_service_client_rotates_and_schedule_after_refused():
    from kubeshare_tpu.scheduler.bridge import ServiceClient

    calls = []

    def fake_open(req, data=None, timeout=None):
        calls.append(req.full_url)
        if "dead" in req.full_url:
            raise urllib.error.URLError(ConnectionRefusedError("refused"))
        return _FakeResp()

    client = ServiceClient("http://dead:1,http://live:2", seed=3)
    client.RETRY_BACKOFF_S = 0.0
    client._open = fake_open
    code, body = client.state()
    assert code == 200 and body == {"ok": True}
    assert calls == ["http://dead:1/state", "http://live:2/state"]
    # the failover is sticky — and connection-refused is the one
    # transport failure a schedule MAY be resent after (provably never
    # reached a server)
    calls.clear()
    code, _ = client.schedule("ns", "p", shared())
    assert code == 200
    assert calls == ["http://live:2/schedule"]


def test_service_client_ambiguous_failure_not_resent():
    """A timeout mid-request is ambiguous — the schedule may have
    landed. The client must raise instead of double-submitting."""
    from kubeshare_tpu.scheduler.bridge import ServiceClient

    calls = []

    def fake_open(req, data=None, timeout=None):
        calls.append(req.full_url)
        raise urllib.error.URLError(TimeoutError("timed out"))

    client = ServiceClient(["http://a:1", "http://b:2"], seed=1)
    client.RETRY_BACKOFF_S = 0.0
    client._open = fake_open
    with pytest.raises((urllib.error.URLError, OSError)):
        client.schedule("ns", "p", shared())
    assert len(calls) == 1                      # never re-sent
    # idempotent reads DO retry across both endpoints
    calls.clear()
    with pytest.raises((urllib.error.URLError, OSError)):
        client.state()
    assert len(calls) == client.RETRY_ATTEMPTS
    assert {c.split("/")[2] for c in calls} == {"a:1", "b:2"}


def test_clients_seeded_jitter_deterministic():
    from kubeshare_tpu.scheduler.bridge import ServiceClient

    a = RegistryClient(["h1:1", "h2:2"], seed=42)
    b = RegistryClient(["h1:1", "h2:2"], seed=42)
    assert [a._rng.random() for _ in range(4)] \
        == [b._rng.random() for _ in range(4)]
    sa = ServiceClient(["http://h1:1"], seed=42)
    sb = ServiceClient(["http://h1:1"], seed=42)
    assert [sa._rng.random() for _ in range(4)] \
        == [sb._rng.random() for _ in range(4)]


# -- service surface -----------------------------------------------------------


def test_service_ha_endpoint_and_metrics():
    reg = TelemetryRegistry()
    _capacity(reg)
    svc = SchedulerService(SchedulerEngine(), reg, replay=False)
    # detached: /ha reports so, and no HA gauge families render
    assert svc.ha_state() == {"attached": False, "frozen": False}
    assert "kubeshare_ha_leader" not in svc.render_metrics()
    svc.attach_standby("primary", ttl_s=60.0)
    assert svc.dispatcher.frozen                # frozen until elected
    assert svc.standby.step()
    st = svc.ha_state()
    assert st["attached"] and st["role"] == "leader"
    assert st["epoch"] == 1 and not st["frozen"]
    text = svc.render_metrics()
    assert "kubeshare_ha_leader 1" in text
    assert "kubeshare_ha_epoch 1" in text
    assert "kubeshare_ha_last_takeover_timestamp_seconds" in text


def test_ha_disabled_registry_wire_identical(tmp_path):
    """HA never used ⇒ the journal bytes and the HTTP surface are
    exactly the pre-HA ones: no leader: keys, no fence log, no replica
    headers, no cursor records."""
    j = str(tmp_path / "j.jsonl")
    clock = _TickClock(100.0)
    reg = TelemetryRegistry(journal=j, clock=clock)
    _capacity(reg)
    reg.put_lease("tpu-host-0", 1)
    reg.put_pod("ns/p", {"node": "tpu-host-0"})
    assert not reg.fence_log
    assert not any(k.startswith("leader:") for k in reg.leases())
    with open(j, encoding="utf-8") as fh:
        for line in fh:
            rec = json.loads(line)
            assert rec["op"] in {"put_capacity", "put_lease", "put_pod"}
            assert "holder" not in rec
    reg.close()


# -- chaos acceptance ----------------------------------------------------------


@pytest.mark.parametrize("name", ["registry-leader-kill-mid-bind-publish",
                                  "partition-with-standby-takeover"])
def test_chaos_ha_scenarios_converge(name):
    from kubeshare_tpu.chaos import run_scenario

    report = run_scenario(name, seed=11)
    assert report["converged"], report
    assert report["violations"] == [], report["violations"]
    assert report["mttr_s"] >= 0.0


# -- topcli fleet panel --------------------------------------------------------


def test_topcli_fleet_renders_ha_panel():
    import time as _time

    from kubeshare_tpu.topcli import fleet_snapshot, render_fleet

    reg = TelemetryRegistry()
    reg.serve()
    try:
        client = RegistryClient("127.0.0.1", reg.port)
        now = _time.time()
        fams = {"kubeshare_ha_leader": "gauge",
                "kubeshare_ha_epoch": "gauge",
                "kubeshare_ha_last_takeover_timestamp_seconds": "gauge"}
        client.push_metrics("sched-a:9007", "scheduler", snapshot={
            "families": fams,
            "samples": [("kubeshare_ha_leader", {}, 1.0),
                        ("kubeshare_ha_epoch", {}, 3.0),
                        ("kubeshare_ha_last_takeover_timestamp_seconds",
                         {}, now - 30.0)]}, now=now)
        client.push_metrics("sched-b:9007", "scheduler", snapshot={
            "families": fams,
            "samples": [("kubeshare_ha_leader", {}, 0.0),
                        ("kubeshare_ha_epoch", {}, 3.0)]}, now=now)
        snap = fleet_snapshot(client)
        assert set(snap["ha"]) == {"sched-a:9007", "sched-b:9007"}
        out = render_fleet(snap)
        assert "HA (epoch-fenced leadership" in out
        # scope to the HA section — the instance table upstream also
        # names the instances
        ha_lines = out.split("HA (epoch-fenced leadership", 1)[1] \
            .splitlines()
        a_line = next(line for line in ha_lines
                      if "sched-a:9007" in line)
        assert "leader" in a_line
        b_line = next(line for line in ha_lines
                      if "sched-b:9007" in line)
        assert "standby" in b_line
    finally:
        reg.close()
