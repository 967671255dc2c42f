"""Gang isolation plane tests (doc/gang.md): the carve wire format and
its round-trip back to the planned sub-mesh block, the carved-mesh
builder on virtual CPU devices, the gang-atomic token coordinator
(two-phase reserve/commit, backoff, pause/drain, uniform effective
shares), elastic gang routing, and the negotiated wire extension."""

import threading
import time

import numpy as np
import pytest

from kubeshare_tpu.autopilot.elastic import ElasticQuota
from kubeshare_tpu.gang import (CarveError, GangTokenCoordinator,
                                block_coords, carve_block, carve_env,
                                format_mesh, parse_mesh,
                                parse_visible_chips, strip_carve)
from kubeshare_tpu.isolation import protocol, tokensched
from kubeshare_tpu.isolation.tokensched import TokenScheduler

WINDOW = 1000.0
BASE = 100.0
MIN = 10.0


# --------------------------------------------------------------------------
# carve wire format: select_submesh block <-> KUBESHARE_TPU_VISIBLE_CHIPS
# --------------------------------------------------------------------------

def test_carve_env_round_trips_chips_and_coords():
    env = carve_env(["c0", "c1", "c2", "c3"],
                    [(0, 0), (0, 1), (1, 0), (1, 1)])
    assert env == "c0@0.0,c1@0.1,c2@1.0,c3@1.1"
    entries = parse_visible_chips(env)
    assert entries == [("c0", (0, 0)), ("c1", (0, 1)),
                       ("c2", (1, 0)), ("c3", (1, 1))]
    assert strip_carve(env) == "c0,c1,c2,c3"


def test_carve_env_seed_form_passthrough():
    # chips without coords render (and parse) in the seed format
    env = carve_env(["c0", "c1"], [None, ()])
    assert env == "c0,c1"
    assert parse_visible_chips(env) == [("c0", None), ("c1", None)]
    assert strip_carve(env) == env


def test_carve_env_rejects_unparseable_chip_ids():
    with pytest.raises(CarveError):
        carve_env(["a,b"], [(0, 0)])
    with pytest.raises(CarveError):
        carve_env(["a@b"], [(0, 0)])
    with pytest.raises(CarveError):
        carve_env(["a", "b"], [(0, 0)])  # length mismatch
    with pytest.raises(CarveError):
        parse_visible_chips("c0@x.y")


def test_mesh_shape_round_trip():
    assert parse_mesh(format_mesh((2, 4))) == (2, 4)
    with pytest.raises(CarveError):
        parse_mesh("2x")
    with pytest.raises(CarveError):
        parse_mesh("0x4")


def test_carve_block_recovers_planned_block():
    env = carve_env(["a", "b", "c", "d"],
                    [(1, 2), (1, 1), (0, 2), (0, 1)])
    origin, shape = carve_block(parse_visible_chips(env), mesh=(2, 4))
    assert (origin, shape) == ((0, 1), (2, 2))
    assert set(block_coords(origin, shape, (2, 4))) \
        == {(0, 1), (0, 2), (1, 1), (1, 2)}


def test_carve_block_wraps_the_torus():
    # select_block places blocks on a torus: {3, 0} on a 4-wide axis is
    # one contiguous interval with origin 3
    entries = [("a", (0, 3)), ("b", (0, 0))]
    origin, shape = carve_block(entries, mesh=(1, 4))
    assert (origin, shape) == ((0, 3), (1, 2))
    assert block_coords(origin, shape, (1, 4)) == [(0, 3), (0, 0)]
    # without the mesh shape the same coords cannot validate as a block
    with pytest.raises(CarveError):
        carve_block(entries)


def test_carve_block_rejects_scatter_holes_and_junk():
    with pytest.raises(CarveError):       # scatter (greedy-compact pick)
        carve_block([("a", (0, 0)), ("b", (1, 1))], mesh=(2, 2))
    with pytest.raises(CarveError):       # L-shape: intervals but a hole
        carve_block([("a", (0, 0)), ("b", (0, 1)), ("c", (1, 0))],
                    mesh=(2, 2))
    with pytest.raises(CarveError):       # duplicate coords
        carve_block([("a", (0, 0)), ("b", (0, 0))], mesh=(2, 2))
    with pytest.raises(CarveError):       # mixed rank
        carve_block([("a", (0, 0)), ("b", (1,))])
    with pytest.raises(CarveError):       # seed entry without coords
        carve_block([("a", None)])
    with pytest.raises(CarveError):
        carve_block([])


# --------------------------------------------------------------------------
# carved mesh: KUBESHARE_TPU_VISIBLE_CHIPS -> NamedSharding-ready Mesh
# --------------------------------------------------------------------------

def test_make_carved_mesh_builds_usable_namedsharding():
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from kubeshare_tpu.parallel.mesh import make_carved_mesh

    env = carve_env(["a", "b", "c", "d"],
                    [(0, 0), (0, 1), (1, 0), (1, 1)])
    mesh = make_carved_mesh(env, mesh_shape="2x2")
    assert mesh.shape == {"dp": 2, "tp": 2}
    x = np.arange(16, dtype=np.float32).reshape(4, 4)
    sharded = jax.device_put(x, NamedSharding(mesh, P("dp", "tp")))
    assert len(sharded.sharding.device_set) == 4
    np.testing.assert_allclose(np.asarray(sharded), x)


def test_make_carved_mesh_orders_devices_by_block_position():
    import jax

    from kubeshare_tpu.parallel.mesh import make_carved_mesh

    # wrapped 1-D carve: entry a@0.3 is block position 0, b@0.0 is 1
    mesh = make_carved_mesh("a@0.3,b@0.0", mesh_shape="1x4")
    assert mesh.shape == {"dp": 1, "tp": 2}
    assert list(mesh.devices.flat) == list(jax.devices()[:2])


def test_make_carved_mesh_rejects_non_contiguous_carve():
    from kubeshare_tpu.parallel.mesh import make_carved_mesh

    with pytest.raises(CarveError):
        make_carved_mesh("a@0.0,b@1.1", mesh_shape="2x2")
    with pytest.raises(CarveError):      # seed env carries no coords
        make_carved_mesh("a,b")


# --------------------------------------------------------------------------
# gang-atomic token coordinator
# --------------------------------------------------------------------------

def coord_with(nchips=2):
    coord = GangTokenCoordinator(reserve_window_s=0.08,
                                 backoff_base_s=0.005, backoff_max_s=0.03)
    scheds = {}
    for i in range(nchips):
        chip = f"chip-{i}"
        sched = TokenScheduler(WINDOW, BASE, MIN, chip=chip)
        coord.attach_chip(chip, sched)
        scheds[chip] = sched
    return coord, scheds


def register_members(coord, scheds, gang="g", request=0.5, limit=1.0):
    members = []
    for i, (chip, sched) in enumerate(sorted(scheds.items())):
        name = f"w{i}"
        sched.add_client(name, request, limit)
        members.append((chip, name))
    coord.register_gang(gang, members, namespace="ns")
    return members


def test_gang_acquire_grants_every_member_chip_then_releases():
    coord, scheds = coord_with(2)
    register_members(coord, scheds)
    held = coord.acquire("g", timeout=5.0)
    assert set(held) == {"chip-0", "chip-1"}
    assert all(q > 0 for q in held.values())
    snap = coord.snapshot()["gangs"]["g"]
    assert snap["state"] == "held" and snap["held"] == ["chip-0", "chip-1"]
    coord.release("g", used_ms=10.0)
    snap = coord.snapshot()["gangs"]["g"]
    assert snap["state"] == "idle" and snap["grants"] == 1
    # tokens really released: a co-tenant can acquire immediately
    scheds["chip-0"].add_client("solo", 0.3, 1.0)
    assert scheds["chip-0"].acquire("solo", timeout=1.0) > 0


def test_gang_never_commits_partial_while_cotenant_holds():
    coord, scheds = coord_with(2)
    register_members(coord, scheds)
    scheds["chip-1"].add_client("solo", 0.3, 1.0)
    scheds["chip-1"].acquire("solo", timeout=1.0)   # block one member chip

    out = {}
    t = threading.Thread(
        target=lambda: out.update(held=coord.acquire("g", timeout=10.0)))
    t.start()
    time.sleep(0.3)    # several reserve windows + backoffs
    snap = coord.snapshot()["gangs"]["g"]
    assert snap["grants"] == 0, "gang committed without every chip"
    assert snap["partial_releases"] >= 1   # reserved chip-0, gave it back
    scheds["chip-1"].release("solo", 5.0)
    t.join(timeout=10.0)
    assert not t.is_alive()
    assert set(out["held"]) == {"chip-0", "chip-1"}
    coord.release("g")


def test_gang_acquire_timeout_releases_partial_reservation():
    coord, scheds = coord_with(2)
    register_members(coord, scheds)
    scheds["chip-1"].add_client("solo", 0.3, 1.0)
    scheds["chip-1"].acquire("solo", timeout=1.0)
    with pytest.raises(TimeoutError):
        coord.acquire("g", timeout=0.25)
    snap = coord.snapshot()["gangs"]["g"]
    assert snap["state"] == "idle" and snap["held"] == []
    # chip-0's token went back: nothing holds it
    assert scheds["chip-0"].core.holder() is None


def test_colocated_fractional_members_share_one_chip_hold():
    coord, scheds = coord_with(1)
    scheds["chip-0"].add_client("a", 0.4, 1.0)
    scheds["chip-0"].add_client("b", 0.4, 1.0)
    coord.register_gang("g", [("chip-0", "a"), ("chip-0", "b")])
    assert coord.gang_members("g") == [("chip-0", "a"), ("chip-0", "b")]
    held = coord.acquire("g", timeout=5.0)
    # the chip token is exclusive: one hold through the representative
    # client covers both co-located members
    assert set(held) == {"chip-0"}
    assert scheds["chip-0"].core.holder() == "a"
    coord.release("g")
    assert scheds["chip-0"].core.holder() is None


def test_pause_drains_blocks_grants_and_resume_restores():
    coord, scheds = coord_with(2)
    register_members(coord, scheds)
    coord.acquire("g", timeout=5.0)
    assert coord.pause("g", timeout=0.05) is False    # still held
    coord.release("g")
    assert coord.pause("g", timeout=2.0) is True      # drained
    assert coord.snapshot()["gangs"]["g"]["state"] == "paused"
    with pytest.raises(TimeoutError):
        coord.acquire("g", timeout=0.1)               # no grants while paused
    coord.resume("g")
    held = coord.acquire("g", timeout=5.0)
    assert set(held) == {"chip-0", "chip-1"}
    coord.release("g")


def test_set_effective_gang_is_all_or_nothing():
    coord, scheds = coord_with(2)
    register_members(coord, scheds, request=0.4, limit=0.5)
    assert coord.set_effective_gang("g", 0.6, 0.8) is True
    assert scheds["chip-0"].effective("w0") == (0.6, 0.8)
    assert scheds["chip-1"].effective("w1") == (0.6, 0.8)
    # one member vanishes -> the broadcast must roll back, not skew
    scheds["chip-1"].remove_client("w1")
    assert coord.set_effective_gang("g", 0.7, 0.9) is False
    assert scheds["chip-0"].effective("w0") == (0.4, 0.5)


def test_detach_chip_releases_gangs_holding_it():
    coord, scheds = coord_with(2)
    register_members(coord, scheds)
    coord.acquire("g", timeout=5.0)
    coord.detach_chip("chip-1")    # eviction under a live grant
    snap = coord.snapshot()["gangs"]["g"]
    assert snap["state"] == "idle" and snap["held"] == []
    assert scheds["chip-0"].core.holder() is None


def test_register_gang_membership_change_drops_stale_holds():
    coord, scheds = coord_with(2)
    register_members(coord, scheds)
    coord.acquire("g", timeout=5.0)
    # migration rebind re-publishes different membership mid-hold
    scheds["chip-0"].add_client("w9", 0.2, 1.0)
    coord.register_gang("g", [("chip-0", "w9")])
    snap = coord.snapshot()["gangs"]["g"]
    assert snap["state"] == "idle" and snap["held"] == []
    assert scheds["chip-1"].core.holder() is None


# --------------------------------------------------------------------------
# elastic plane: gang credit is uniform across member chips
# --------------------------------------------------------------------------

def elastic_gang_setup(busy_sibling=False):
    coord = GangTokenCoordinator()
    scheds = {}
    for i in range(2):
        chip = f"chip-{i}"
        sched = TokenScheduler(WINDOW, BASE, MIN, chip=chip)
        sched.add_client(f"g{i}", 0.4, 0.5)
        coord.attach_chip(chip, sched)
        scheds[chip] = sched
    scheds["chip-0"].add_client("idle0", 0.5, 1.0)
    if busy_sibling:
        scheds["chip-1"].add_client("busy1", 0.9, 0.95)
        scheds["chip-1"].acquire("busy1", timeout=1.0)
        scheds["chip-1"].release("busy1", 900.0)
    else:
        scheds["chip-1"].add_client("idle1", 0.5, 1.0)
    coord.register_gang("ring", [("chip-0", "g0"), ("chip-1", "g1")])
    eq = ElasticQuota(schedulers=scheds, gang_coordinator=coord)
    # make the member on chip-0 measurably hot against its limit
    scheds["chip-0"].acquire("g0", timeout=1.0)
    scheds["chip-0"].release("g0", 450.0)
    return eq, coord, scheds


def test_elastic_gang_credit_raises_every_member_chip_uniformly():
    eq, _coord, scheds = elastic_gang_setup()
    eq.step()
    eff0 = scheds["chip-0"].effective("g0")
    eff1 = scheds["chip-1"].effective("g1")
    assert eff0 == eff1, "gang credit skewed across member chips"
    assert eff0[1] > 0.5, "no credit granted"
    snap = eq.snapshot()["chips"]["chip-0"]
    assert snap["g0"]["gang"] == "ring"


def test_elastic_gang_credit_refused_when_a_sibling_lacks_slack():
    eq, _coord, scheds = elastic_gang_setup(busy_sibling=True)
    revocations = eq.revocations
    eq.step()
    # chip-0 had headroom, but chip-1's co-tenant is running hot: the
    # uniform raise would oversubscribe it, so NO chip changes
    assert scheds["chip-0"].effective("g0") == (0.4, 0.5)
    assert scheds["chip-1"].effective("g1") == (0.4, 0.5)
    assert eq.revocations > revocations      # dropped as gang-refused


# --------------------------------------------------------------------------
# wire extension: gang ops are a negotiated feature
# --------------------------------------------------------------------------

def test_wire_gang_ops_with_coordinator_attached():
    sched = TokenScheduler(WINDOW, BASE, MIN, chip="chip-0")
    sched.add_client("w0", 0.5, 1.0)
    coord = GangTokenCoordinator()
    coord.attach_chip("chip-0", sched)
    server = tokensched.serve(sched, coordinator=coord)
    port = server.server_address[1]
    try:
        with protocol.Connection("127.0.0.1", port) as conn:
            conn.call({"op": "gang_register", "gang": "g",
                       "members": [["chip-0", "w0"]]})
            reply, _ = conn.call({"op": "gang_acquire", "gang": "g",
                                  "timeout": 5.0})
            assert reply["held"] == {"chip-0": BASE}
            reply, _ = conn.call({"op": "gang_state"})
            assert reply["state"]["gangs"]["g"]["state"] == "held"
            conn.call({"op": "gang_release", "gang": "g",
                       "used_ms": 10.0})
        # disconnect withdraws the connection's gangs
        deadline = time.monotonic() + 2.0
        while coord.gangs() and time.monotonic() < deadline:
            time.sleep(0.01)
        assert coord.gangs() == []
    finally:
        server.shutdown()


def test_wire_gang_ops_unknown_without_coordinator():
    # un-negotiated peers keep the seed wire: a server without a
    # coordinator answers gang ops with the standard unknown-op error
    sched = TokenScheduler(WINDOW, BASE, MIN)
    server = tokensched.serve(sched)
    try:
        with protocol.Connection("127.0.0.1",
                                 server.server_address[1]) as conn:
            with pytest.raises(RuntimeError, match="unknown op"):
                conn.call({"op": "gang_acquire", "gang": "g"})
            with pytest.raises(RuntimeError, match="unknown op"):
                conn.call({"op": "gang_state"})
    finally:
        server.shutdown()
