"""Scheduler-engine tests: the five BASELINE eval configs on fake
topology, plus label validation and the extension-point mechanics the
reference never tested (SURVEY §4: zero automated tests upstream).
"""

import pytest

from kubeshare_tpu import constants as C
from kubeshare_tpu.scheduler import (LabelError, SchedulerEngine,
                                     Unschedulable, parse_pod_labels)
from kubeshare_tpu.topology.discovery import FakeTopology

HBM = FakeTopology().memory


def shared_labels(request="0.5", limit="1.0", **extra):
    labels = {C.POD_TPU_REQUEST: request, C.POD_TPU_LIMIT: limit}
    labels.update(extra)
    return labels


def engine_with(hosts=1, mesh=(2, 2), model="TPU-v4", **kw):
    eng = SchedulerEngine(**kw)
    topo = FakeTopology(hosts=hosts, mesh=mesh, model=model)
    chips = topo.chips()
    by_host: dict = {}
    for chip in chips:
        by_host.setdefault(chip.host, []).append(chip)
    for host, host_chips in by_host.items():
        eng.add_node(host, host_chips)
    return eng


# --------------------------------------------------------------------------
# label parsing (pod.go:207-327 parity; the test/pod1-10 scenarios)
# --------------------------------------------------------------------------

def test_labels_regular_pod_without_tpu_labels():
    pod = parse_pod_labels("ns", "p", {})
    assert not pod.needs_tpu and pod.priority == 0


def test_labels_shared_pod():
    pod = parse_pod_labels("ns", "p", shared_labels("0.5", "1.0"))
    assert pod.needs_tpu and pod.request == 0.5 and pod.limit == 1.0
    assert not pod.multi_chip and pod.opportunistic


def test_labels_limit_required():
    with pytest.raises(LabelError, match="tpu_limit"):
        parse_pod_labels("ns", "p", {C.POD_TPU_REQUEST: "0.5"})


def test_labels_request_exceeds_limit():
    with pytest.raises(LabelError, match="> tpu_limit"):
        parse_pod_labels("ns", "p", shared_labels("1.0", "0.5"))


def test_labels_precision_capped_at_centichip():
    """Shares carry at most 2 decimals: arbitrary-precision fractions
    would defeat the cell bookkeeping's float-residue snap (and a
    micro-share is meaningless against the 300 ms quantum)."""
    with pytest.raises(LabelError, match="decimal places"):
        parse_pod_labels("ns", "p", shared_labels("0.1234567894", "1.0"))
    with pytest.raises(LabelError, match="decimal places"):
        parse_pod_labels("ns", "p", shared_labels("0.5", "0.505"))
    pod = parse_pod_labels("ns", "p", shared_labels("0.25", "1.0"))
    assert pod.request == 0.25
    # trailing zeros carry no precision (fixed-width float formatting)
    pod = parse_pod_labels("ns", "p", shared_labels("0.250", "1.00"))
    assert pod.request == 0.25
    # the resync path quantizes instead of rejecting: an already-RUNNING
    # pod admitted under older rules must keep its booking on replay
    pod = parse_pod_labels("ns", "p", shared_labels("0.125", "1.0"),
                           lenient=True)
    assert pod.request == pytest.approx(0.12)


def test_labels_bad_number():
    with pytest.raises(LabelError, match="not a non-negative number"):
        parse_pod_labels("ns", "p", shared_labels("half", "1.0"))
    with pytest.raises(LabelError):
        parse_pod_labels("ns", "p", shared_labels("-0.5", "1.0"))


def test_labels_multi_chip_rules():
    pod = parse_pod_labels("ns", "p", shared_labels("2", "2"))
    assert pod.multi_chip and pod.request == 2.0
    with pytest.raises(LabelError, match="tpu_limit == tpu_request"):
        parse_pod_labels("ns", "p", shared_labels("2", "3"))
    with pytest.raises(LabelError, match="integer"):
        parse_pod_labels("ns", "p", shared_labels("1.5", "1.5"))


def test_labels_zero_zero_is_regular():
    pod = parse_pod_labels("ns", "p", shared_labels("0", "0"))
    assert not pod.needs_tpu


def test_labels_priority_range():
    assert parse_pod_labels(
        "ns", "p", {C.POD_PRIORITY: "100"}).priority == 100
    with pytest.raises(LabelError, match="range"):
        parse_pod_labels("ns", "p", {C.POD_PRIORITY: "101"})
    with pytest.raises(LabelError, match="range"):
        parse_pod_labels("ns", "p", {C.POD_PRIORITY: "-2"})


def test_labels_memory_validation():
    pod = parse_pod_labels(
        "ns", "p", {C.POD_TPU_LIMIT: "1.0", C.POD_TPU_MEMORY: "1024"})
    assert pod.memory == 1024
    with pytest.raises(LabelError, match="integer byte"):
        parse_pod_labels(
            "ns", "p", {C.POD_TPU_LIMIT: "1.0", C.POD_TPU_MEMORY: "lots"})


def test_labels_group_min_available():
    labels = shared_labels()
    labels.update({C.POD_GROUP_NAME: "g", C.POD_GROUP_HEADCOUNT: "5",
                   C.POD_GROUP_THRESHOLD: "0.2"})
    pod = parse_pod_labels("ns", "p", labels)
    assert pod.min_available == 1  # floor(0.2*5 + 0.5)
    labels[C.POD_GROUP_THRESHOLD] = "0.5"
    assert parse_pod_labels("ns", "p", labels).min_available == 3  # 2.5→3


def test_labels_bad_group_degrades_to_groupless():
    labels = shared_labels()
    labels.update({C.POD_GROUP_NAME: "g", C.POD_GROUP_HEADCOUNT: "zero",
                   C.POD_GROUP_THRESHOLD: "0.2"})
    pod = parse_pod_labels("ns", "p", labels)
    assert pod.group_name == "" and pod.min_available == 0


# --------------------------------------------------------------------------
# queue sort (Less, scheduler.go:247-267)
# --------------------------------------------------------------------------

def test_queue_less_priority_then_time():
    eng = engine_with()
    hi = eng.submit("ns", "hi", shared_labels(**{C.POD_PRIORITY: "50"}))
    lo = eng.submit("ns", "lo", shared_labels(**{C.POD_PRIORITY: "1"}))
    assert eng.queue_less(hi, lo) and not eng.queue_less(lo, hi)
    a = eng.submit("ns", "a", shared_labels())
    b = eng.submit("ns", "b", shared_labels())
    assert eng.queue_less(a, b)  # same priority+time → key order


# --------------------------------------------------------------------------
# BASELINE config 1+2: single pod, then 2x0.5 co-location
# --------------------------------------------------------------------------

def test_single_shared_pod_binds_with_port_and_default_memory():
    eng = engine_with(hosts=1, mesh=(1,))
    pod = eng.submit("ns", "mnist", shared_labels("0.5", "1.0"))
    binding = eng.schedule(pod)
    assert binding.node == "tpu-host-0"
    assert binding.port == C.POD_MANAGER_PORT_START + 1  # offset 0 reserved
    assert binding.memory == HBM // 2  # defaulted: request * full HBM
    assert binding.env[C.ENV_VISIBLE_CHIPS] == binding.chip_ids[0]
    assert binding.env[C.ENV_POD_NAME] == "ns/mnist"
    leaf = eng.leaf_cells[binding.chip_ids[0]]
    assert leaf.available == 0.5


def test_two_colocated_pods_share_one_chip():
    eng = engine_with(hosts=1, mesh=(1,))
    b1 = eng.schedule(eng.submit("ns", "pod1", shared_labels("0.5", "1.0")))
    b2 = eng.schedule(eng.submit("ns", "pod2", shared_labels("0.5", "1.0")))
    assert b1.chip_ids == b2.chip_ids  # same chip
    assert b1.port != b2.port
    leaf = eng.leaf_cells[b1.chip_ids[0]]
    assert leaf.available == 0.0
    with pytest.raises(Unschedulable):
        eng.schedule(eng.submit("ns", "pod3", shared_labels("0.5", "1.0")))


def test_delete_reclaims_everything():
    eng = engine_with(hosts=1, mesh=(1,))
    binding = eng.schedule(eng.submit("ns", "p", shared_labels("0.5", "1.0")))
    leaf = eng.leaf_cells[binding.chip_ids[0]]
    eng.delete_pod("ns/p")
    assert leaf.available == 1.0 and leaf.free_memory == HBM
    assert not eng.ports[binding.node].is_masked(
        binding.port - C.POD_MANAGER_PORT_START)


# --------------------------------------------------------------------------
# BASELINE config 3: opportunistic defragmentation
# --------------------------------------------------------------------------

def test_opportunistic_packs_onto_used_chip():
    eng = engine_with(hosts=2, mesh=(1,))
    guar = eng.submit("ns", "guar",
                      shared_labels("0.5", "1.0", **{C.POD_PRIORITY: "10"}))
    b_guar = eng.schedule(guar)
    opp = eng.submit("ns", "opp", shared_labels("0.2", "1.0"))
    b_opp = eng.schedule(opp)
    assert b_opp.chip_ids == b_guar.chip_ids  # defrag: pack, don't spread


def test_guarantee_spreads_to_free_chip():
    eng = engine_with(hosts=2, mesh=(1,))
    first = eng.schedule(eng.submit(
        "ns", "g1", shared_labels("0.5", "1.0", **{C.POD_PRIORITY: "10"})))
    second = eng.schedule(eng.submit(
        "ns", "g2", shared_labels("0.5", "1.0", **{C.POD_PRIORITY: "10"})))
    assert first.chip_ids != second.chip_ids  # guarantee avoids contention


# --------------------------------------------------------------------------
# BASELINE config 4: coscheduling gang
# --------------------------------------------------------------------------

def gang_labels(name="lstm", headcount="5", threshold="0.2", prio="10"):
    labels = shared_labels("0.2", "1.0", **{C.POD_PRIORITY: prio})
    labels.update({C.POD_GROUP_NAME: name, C.POD_GROUP_HEADCOUNT: headcount,
                   C.POD_GROUP_THRESHOLD: threshold})
    return labels


def test_gang_prefilter_needs_min_available_submitted():
    eng = engine_with()
    p1 = eng.submit("ns", "w-0", gang_labels(threshold="0.6", headcount="5"))
    ok, msg = eng.pre_filter(p1)
    assert not ok and "min_available" in msg  # 3 needed, 1 submitted
    for i in range(1, 3):
        eng.submit("ns", f"w-{i}", gang_labels(threshold="0.6", headcount="5"))
    ok, _ = eng.pre_filter(p1)
    assert ok


def test_gang_permit_barrier_and_timeout():
    eng = engine_with(hosts=2, mesh=(2, 2))
    pods = [eng.submit("ns", f"w-{i}", gang_labels(threshold="1.0",
                                                   headcount="3"))
            for i in range(3)]
    eng.schedule(pods[0])
    decision, timeout = eng.permit(pods[0])
    assert decision == "wait" and timeout == pytest.approx(2.0 * 3)
    eng.schedule(pods[1])
    assert eng.permit(pods[1]) == ("wait", pytest.approx(6.0))
    eng.schedule(pods[2])
    decision, _ = eng.permit(pods[2])
    assert decision == "allow"


def test_gang_unreserve_rejects_members():
    eng = engine_with(hosts=1, mesh=(2, 2))
    pods = [eng.submit("ns", f"w-{i}", gang_labels(threshold="1.0",
                                                   headcount="2"))
            for i in range(2)]
    eng.schedule(pods[0])
    rejected = eng.unreserve(pods[0])
    assert rejected == ["ns/w-1"]
    leaf_avail = [leaf.available for leaf in eng.leaf_cells.values()]
    assert all(a == 1.0 for a in leaf_avail)  # fully reclaimed


def test_gang_locality_prefers_same_host():
    eng = engine_with(hosts=2, mesh=(2, 2))
    pods = [eng.submit("ns", f"w-{i}", gang_labels(threshold="0.5",
                                                   headcount="4"))
            for i in range(4)]
    bindings = [eng.schedule(p) for p in pods]
    hosts = {b.node for b in bindings}
    assert len(hosts) == 1  # locality keeps the gang on one host


def test_gang_binding_env_round_trips_to_planned_block():
    """The carved KUBESHARE_TPU_VISIBLE_CHIPS env (doc/gang.md) must parse back to
    exactly the contiguous sub-mesh block the scheduler planned, and the
    seed-format chip list must survive a strip."""
    from kubeshare_tpu.gang import (carve_block, parse_mesh,
                                    parse_visible_chips, strip_carve)

    eng = engine_with(hosts=1, mesh=(2, 2))
    labels = shared_labels("1", "1", **{
        C.POD_GROUP_NAME: "ring", C.POD_GROUP_HEADCOUNT: "4",
        C.POD_GROUP_THRESHOLD: "1.0"})
    pods = [eng.submit("ns", f"w-{i}", dict(labels)) for i in range(4)]
    bindings = [eng.schedule(p) for p in pods]
    coords, mesh_shapes = [], set()
    for b in bindings:
        env = b.env
        assert C.ENV_MESH_SHAPE in env, "carve annotation missing"
        mesh_shapes.add(env[C.ENV_MESH_SHAPE])
        entries = parse_visible_chips(env[C.ENV_VISIBLE_CHIPS])
        assert all(c is not None for _chip, c in entries)
        assert strip_carve(env[C.ENV_VISIBLE_CHIPS]) == ",".join(b.chip_ids)
        coords.extend(entries)
    assert len(mesh_shapes) == 1
    mesh = parse_mesh(mesh_shapes.pop())
    origin, shape = carve_block(coords, mesh=mesh)
    # the union of the members' carves IS the planned 2x2 block
    assert shape == (2, 2) and mesh == (2, 2) and origin == (0, 0)


# --------------------------------------------------------------------------
# BASELINE config 5: heterogeneous topology-aware placement
# --------------------------------------------------------------------------

def hetero_engine():
    eng = SchedulerEngine()
    v4 = FakeTopology(hosts=1, mesh=(2, 2), model="TPU-v4",
                      host_prefix="v4-host")
    v5 = FakeTopology(hosts=1, mesh=(2, 2), model="TPU-v5e",
                      host_prefix="v5-host", memory=2 * HBM)
    for topo in (v4, v5):
        by_host: dict = {}
        for chip in topo.chips():
            by_host.setdefault(chip.host, []).append(chip)
        for host, chips in by_host.items():
            eng.add_node(host, chips)
    return eng


def test_model_constraint_filters_nodes():
    eng = hetero_engine()
    pod = eng.submit("ns", "p", shared_labels(
        "0.5", "1.0", **{C.POD_TPU_MODEL: "TPU-v5e"}))
    binding = eng.schedule(pod)
    assert binding.node == "v5-host-0"
    assert binding.models == ["TPU-v5e"]
    fit, msg = eng.filter(pod, "v4-host-0")
    assert not fit and "no TPU-v5e" in msg


def test_unknown_model_unschedulable():
    eng = hetero_engine()
    pod = eng.submit("ns", "p", shared_labels(
        "0.5", "1.0", **{C.POD_TPU_MODEL: "TPU-v9"}))
    with pytest.raises(Unschedulable):
        eng.schedule(pod)


def test_multi_chip_pod_takes_whole_leaves():
    eng = engine_with(hosts=1, mesh=(2, 2))
    pod = eng.submit("ns", "big", shared_labels("2", "2"))
    binding = eng.schedule(pod)
    assert len(binding.chip_ids) == 2
    assert binding.port == 0  # whole-chip pods bypass the manager
    assert binding.memory == 2 * HBM
    for chip_id in binding.chip_ids:
        assert eng.leaf_cells[chip_id].available == 0.0


def test_multi_chip_respects_partial_usage():
    eng = engine_with(hosts=1, mesh=(2,))
    eng.schedule(eng.submit("ns", "frac", shared_labels("0.5", "1.0")))
    with pytest.raises(Unschedulable):
        eng.schedule(eng.submit("ns", "big", shared_labels("2", "2")))


# --------------------------------------------------------------------------
# health, regular pods, resync
# --------------------------------------------------------------------------

def test_unhealthy_node_excluded_but_keeps_bookings():
    eng = engine_with(hosts=2, mesh=(1,))
    b = eng.schedule(eng.submit("ns", "p", shared_labels("0.5", "1.0")))
    eng.set_node_health(b.node, False)
    leaf = eng.leaf_cells[b.chip_ids[0]]
    assert leaf.available == 0.5  # booking preserved
    pod2 = eng.submit("ns", "q", shared_labels("0.5", "1.0"))
    b2 = eng.schedule(pod2)
    assert b2.node != b.node  # steered to the healthy node


def test_regular_pod_prefers_chipless_node():
    eng = engine_with(hosts=1, mesh=(1,))
    eng.chips_by_node["cpu-node"] = {}
    eng.ports["cpu-node"] = eng.ports["tpu-host-0"]
    pod = eng.submit("ns", "web", {})
    scores = {n: eng.score(pod, n) for n in ("cpu-node", "tpu-host-0")}
    assert scores["cpu-node"] > scores["tpu-host-0"]


def test_resync_rebuilds_state_after_restart():
    eng = engine_with(hosts=1, mesh=(2,))
    labels = shared_labels("0.5", "1.0")
    binding = eng.schedule(eng.submit("ns", "p", labels))
    leaf_avail = eng.leaf_cells[binding.chip_ids[0]].available

    fresh = engine_with(hosts=1, mesh=(2,))
    fresh.resync_bound("ns", "p", labels, binding.annotations, binding.node)
    leaf = fresh.leaf_cells[binding.chip_ids[0]]
    assert leaf.available == leaf_avail
    assert leaf.free_memory == HBM - binding.memory
    assert fresh.ports[binding.node].is_masked(
        binding.port - C.POD_MANAGER_PORT_START)


def test_resync_multi_chip():
    eng = engine_with(hosts=1, mesh=(2, 2))
    labels = shared_labels("2", "2")
    binding = eng.schedule(eng.submit("ns", "big", labels))

    fresh = engine_with(hosts=1, mesh=(2, 2))
    fresh.resync_bound("ns", "big", labels, binding.annotations, binding.node)
    for chip_id in binding.chip_ids:
        assert fresh.leaf_cells[chip_id].available == 0.0


def test_defaulted_memory_cannot_overcommit():
    """Unset tpu_mem defaults to request x full HBM at reserve; selection
    must fit-check against that default, not zero."""
    eng = engine_with(hosts=1, mesh=(1,))
    eng.schedule(eng.submit("ns", "heavy", {
        C.POD_TPU_REQUEST: "0.2", C.POD_TPU_LIMIT: "1.0",
        C.POD_TPU_MEMORY: str(3 * HBM // 4)}))
    with pytest.raises(Unschedulable):
        # default would be HBM/2 > remaining HBM/4
        eng.schedule(eng.submit("ns", "default", shared_labels("0.5", "1.0")))
    leaf = next(iter(eng.leaf_cells.values()))
    assert leaf.free_memory >= 0


def test_filter_checks_defaulted_memory_like_reserve():
    """Filter must apply the same request x full-HBM default as reserve:
    a node whose leaves have compute headroom but tight free HBM must be
    rejected at filter time, and schedule() must fall back to a node that
    actually fits instead of aborting the cycle (round-2 advisor medium)."""
    eng = SchedulerEngine()
    tight = FakeTopology(hosts=1, mesh=(1,), host_prefix="tight").chips()
    roomy = FakeTopology(hosts=1, mesh=(1,), host_prefix="roomy").chips()
    eng.add_node(tight[0].host, tight)
    eng.add_node(roomy[0].host, roomy)
    # eat 3/4 of the tight node's HBM with a tiny compute fraction
    eng.schedule(eng.submit("ns", "hog", {
        C.POD_TPU_REQUEST: "0.1", C.POD_TPU_LIMIT: "1.0",
        C.POD_TPU_MEMORY: str(3 * HBM // 4)}), nodes=[tight[0].host])
    # 0.5 request with unset tpu_mem -> needs HBM/2; tight has HBM/4 free
    fit, why = eng.filter(
        eng.submit("ns", "p", shared_labels("0.5", "1.0")), tight[0].host)
    assert not fit, why
    binding = eng.schedule(
        eng.submit("ns", "p2", shared_labels("0.5", "1.0")))
    assert binding.node == roomy[0].host


def test_resubmit_new_uid_reclaims_old_incarnation():
    eng = engine_with(hosts=1, mesh=(1,))
    eng.schedule(eng.submit("ns", "p", shared_labels("0.5", "1.0"), uid="A"))
    leaf = next(iter(eng.leaf_cells.values()))
    assert leaf.available == 0.5
    eng.submit("ns", "p", shared_labels("0.5", "1.0"), uid="B")
    assert leaf.available == 1.0  # old incarnation's booking reclaimed
    assert eng.ports["tpu-host-0"].count() == 1  # only the reserved bit 0


def test_queue_less_antisymmetric_for_groupless_pods():
    eng = engine_with()
    a = eng.submit("ns", "a", shared_labels())
    b = eng.submit("ns", "b", shared_labels())
    assert eng.queue_less(a, b) != eng.queue_less(b, a)


def test_resync_ignores_out_of_pool_port():
    eng = engine_with(hosts=1, mesh=(1,))
    pod = eng.resync_bound("ns", "p", shared_labels("0.5", "1.0"),
                           {C.POD_TPU_CHIP_ID: "TPU-v4-tpu-host-0-0",
                            C.POD_TPU_MEMORY: "1024",
                            C.POD_MANAGER_PORT: "99999"},
                           "tpu-host-0")
    assert pod.port == 0  # rejected, resync completed without crashing
    assert pod.cells and pod.cells[0].available == 0.5


def test_mixed_booking_reclaim_is_exact():
    """A multi-chip pod books a leaf's *free* memory; its reclaim must
    mirror that, not the full memory (drift regression)."""
    eng = engine_with(hosts=1, mesh=(2,))
    frac = eng.submit("ns", "frac", {
        C.POD_TPU_REQUEST: "0", C.POD_TPU_LIMIT: "0.5",
        C.POD_TPU_MEMORY: str(HBM // 4)})
    eng.schedule(frac)  # request 0: leaf stays whole-free, memory booked
    big = eng.submit("ns", "big", shared_labels("2", "2"))
    eng.schedule(big)
    eng.delete_pod("ns/big")
    eng.delete_pod("ns/frac")
    for leaf in eng.leaf_cells.values():
        assert leaf.free_memory == HBM and leaf.available == 1.0


def test_multichip_never_spans_models():
    eng = SchedulerEngine()
    chips = (FakeTopology(hosts=1, mesh=(2,), model="TPU-v4").chips()
             + FakeTopology(hosts=1, mesh=(2,), model="TPU-v5e").chips())
    eng.add_node("tpu-host-0", chips)
    pod = eng.submit("ns", "big", shared_labels("4", "4"))
    with pytest.raises(Unschedulable):
        eng.schedule(pod)  # 4 chips exist, but 2+2 across generations
    pod2 = eng.submit("ns", "pair", shared_labels("2", "2"))
    binding = eng.schedule(pod2)
    assert len(set(binding.models)) == 1


def test_inventory_change_rebuilds_auto_topology():
    eng = engine_with(hosts=1, mesh=(1,))
    eng.schedule(eng.submit("ns", "p", shared_labels("0.5", "1.0")))
    grown = FakeTopology(hosts=1, mesh=(2,)).chips()
    eng.add_node("tpu-host-0", grown)
    assert len(eng.leaf_cells) == 2  # new chip became schedulable
    booked = eng.leaf_cells["TPU-v4-tpu-host-0-0"]
    assert booked.available == 0.5  # live booking replayed


def test_set_fleet_batch_build():
    eng = SchedulerEngine()
    topo = FakeTopology(hosts=3, mesh=(2,))
    fleet: dict = {}
    for chip in topo.chips():
        fleet.setdefault(chip.host, ([], True))[0].append(chip)
    eng.set_fleet(fleet)
    assert len(eng.leaf_cells) == 6
    assert len(eng.nodes) == 3


def test_set_fleet_removes_departed_nodes():
    eng = SchedulerEngine()
    topo = FakeTopology(hosts=2, mesh=(1,))
    fleet: dict = {}
    for chip in topo.chips():
        fleet.setdefault(chip.host, ([], True))[0].append(chip)
    eng.set_fleet(fleet)
    assert len(eng.nodes) == 2
    del fleet["tpu-host-1"]
    eng.set_fleet(fleet)
    assert eng.nodes == ["tpu-host-0"]
    assert all(leaf.node == "tpu-host-0" for leaf in eng.leaf_cells.values())


def test_port_exhaustion_resets_defaulted_memory():
    eng = engine_with(hosts=1, mesh=(1,))
    bitmap = eng.ports["tpu-host-0"]
    for i in range(1, C.POD_MANAGER_PORT_RANGE):
        bitmap.mask(i)  # exhaust the pool
    pod = eng.submit("ns", "p", shared_labels("0.5", "1.0"))
    with pytest.raises(Unschedulable, match="port pool"):
        eng.reserve(pod, "tpu-host-0")
    assert pod.memory == 0 and pod.cells == [] and pod.node_name == ""
    leaf = next(iter(eng.leaf_cells.values()))
    assert leaf.available == 1.0  # nothing booked


def test_port_pool_round_robin_reuse():
    eng = engine_with(hosts=1, mesh=(1,))
    b1 = eng.schedule(eng.submit("ns", "a", shared_labels("0.3", "1.0")))
    eng.delete_pod("ns/a")
    b2 = eng.schedule(eng.submit("ns", "b", shared_labels("0.3", "1.0")))
    assert b2.port == b1.port + 1  # round-robin, not immediate reuse


# --------------------------------------------------------------------------
# preemption — TPU-build extension completing the reference's priority
# semantics (opportunistic = displaceable filler, constants.go:13-15,
# README.md:41-43; the reference never actually displaces)
# --------------------------------------------------------------------------

def guarantee_labels(request="1", limit="1"):
    return shared_labels(request, limit, **{C.POD_PRIORITY: "50"})


def leaf_snapshot(eng):
    return {cid: (l.available, l.free_memory)
            for cid, l in eng.leaf_cells.items()}


def test_preemption_minimal_victims_and_exact_restore():
    eng = engine_with(hosts=1, mesh=(2,))
    for i in range(2):
        eng.schedule(eng.submit("ns", f"opp{i}", shared_labels("1", "1")))
    before = leaf_snapshot(eng)
    guar = eng.submit("ns", "guar", guarantee_labels())
    with pytest.raises(Unschedulable):
        eng.schedule(guar)
    plan = eng.find_preemption(guar)
    assert plan is not None and len(plan["victims"]) == 1
    assert leaf_snapshot(eng) == before, "simulation must restore exactly"
    eng.delete_pod(plan["victims"][0])
    assert eng.schedule(guar).node


def test_preemption_grows_victim_set_until_fit():
    eng = engine_with(hosts=1, mesh=(1,))
    eng.schedule(eng.submit("ns", "a", shared_labels("0.5", "1.0")))
    eng.schedule(eng.submit("ns", "b", shared_labels("0.5", "1.0")))
    guar = eng.submit("ns", "guar", guarantee_labels())
    plan = eng.find_preemption(guar)
    assert plan is not None
    assert set(plan["victims"]) == {"ns/a", "ns/b"}


def test_preemption_none_for_opportunistic_preemptor():
    eng = engine_with(hosts=1, mesh=(2,))
    for i in range(2):
        eng.schedule(eng.submit("ns", f"opp{i}", shared_labels("1", "1")))
    another = eng.submit("ns", "another", shared_labels("1", "1"))
    assert eng.find_preemption(another) is None


def test_preemption_never_evicts_guarantee_pods():
    eng = engine_with(hosts=1, mesh=(2,))
    for i in range(2):
        eng.schedule(eng.submit("ns", f"g{i}",
                                shared_labels("1", "1",
                                              **{C.POD_PRIORITY: "10"})))
    before = leaf_snapshot(eng)
    guar = eng.submit("ns", "guar", guarantee_labels())
    assert eng.find_preemption(guar) is None
    assert leaf_snapshot(eng) == before


def test_preemption_pulls_whole_opportunistic_gang():
    eng = engine_with(hosts=1, mesh=(2,))
    gang = {C.POD_GROUP_NAME: "g", C.POD_GROUP_HEADCOUNT: "2",
            C.POD_GROUP_THRESHOLD: "1.0"}
    members = [eng.submit("ns", f"m{i}", shared_labels("1", "1", **gang))
               for i in range(2)]
    for m in members:
        eng.schedule(m)
    guar = eng.submit("ns", "guar", guarantee_labels())
    plan = eng.find_preemption(guar)
    assert plan is not None
    assert set(plan["victims"]) == {"ns/m0", "ns/m1"}, \
        "evicting part of a gang would strand the rest"


def test_preemption_prefers_standalone_over_newer_gang():
    """A newer gang member would drag its whole gang out; the plan must
    pick the older STANDALONE filler when one victim suffices."""
    eng = engine_with(hosts=1, mesh=(3,))
    eng.schedule(eng.submit("ns", "solo", shared_labels("1", "1")))
    gang = {C.POD_GROUP_NAME: "g", C.POD_GROUP_HEADCOUNT: "2",
            C.POD_GROUP_THRESHOLD: "1.0"}
    members = [eng.submit("ns", f"m{i}", shared_labels("1", "1", **gang))
               for i in range(2)]
    for m in members:
        eng.schedule(m)
    guar = eng.submit("ns", "guar", guarantee_labels())
    plan = eng.find_preemption(guar)
    assert plan is not None
    assert plan["victims"] == ["ns/solo"], plan


def test_preemption_drops_useless_greedy_victims():
    """A newer victim reclaimed before the one that actually produced
    the fit must be dropped from the plan (re-reserve sweep): only the
    contributing victim dies."""
    eng = engine_with(hosts=1, mesh=(2,))
    gb = eng.schedule(eng.submit("ns", "g0", shared_labels(
        "0.5", "1.0", **{C.POD_PRIORITY: "10"})))
    # older whole-chip filler on the OTHER chip
    opp2 = eng.submit("ns", "opp2", shared_labels("1", "1"))
    b2 = eng.schedule(opp2)
    assert b2.chip_ids != gb.chip_ids
    # newer fractional filler co-located with the guarantee pod
    eng.schedule(eng.submit("ns", "opp1", shared_labels("0.5", "1.0")))

    guar = eng.submit("ns", "guar", guarantee_labels())
    plan = eng.find_preemption(guar)
    assert plan is not None
    assert plan["victims"] == ["ns/opp2"], \
        f"opp1 contributes nothing to a whole-chip fit: {plan}"


def test_preemption_skips_non_capacity_nodes():
    """Model-mismatched nodes must not be simulated at all — eviction
    can never produce a fit there."""
    eng = engine_with(hosts=1, mesh=(1,), model="TPU-v4")
    eng.schedule(eng.submit("ns", "opp", shared_labels("1", "1")))
    guar = eng.submit("ns", "guar", shared_labels(
        "1", "1", **{C.POD_PRIORITY: "50", C.POD_TPU_MODEL: "TPU-v5e"}))
    assert eng.find_preemption(guar) is None
