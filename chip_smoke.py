#!/usr/bin/env python
"""chip_smoke.py — the sharing path, once, on the directly attached chip.

    python chip_smoke.py            # one TPU chip: phases 1-4 below
    python chip_smoke.py --chips 4  # four chips: the gang path only

The quickest proof that the system still starts on the chip: label →
scheduler → launcher's command builders → chip proxy / pod manager →
token gate → XLA, through the entry points users start. A chip belongs
to ONE process at a time, so this parent never initializes a JAX backend
(it never even imports jax): every phase that needs the chip is one child
process at a time.

One chip (default):

1. *control plane*: discovery in a child → ``config_from_chips`` →
   ``SchedulerEngine`` places two ``tpu_request=0.5`` pods, then one
   whole-chip pod → bindings → ``default_proxy_cmd`` / ``default_pmgr_cmd``
   build the commands the later phases run. Native cores are built from
   the committed ``.cpp`` into a fresh ``_build/``.
2. *proxy attach*: the per-chip proxy process owns the chip; two
   unmodified ``python -m kubeshare_tpu.models.transformer`` processes
   (default preset) attach by environment alone at 0.5/0.5; then, the
   proxy gone, the same command runs exclusively for the loss comparison.
3. *gate attach*: a whole-chip ``python -m kubeshare_tpu.models.resnet``
   owns the chip, metered through its pod manager against a token
   scheduler, HBM cap armed from the allocator's stats.
4. *kernels*: flash attention fwd+bwd and the fused Adam, compiled
   (``tpu_custom_call`` in the lowered text), against the dense reference
   and ``optax.adam`` at float32 ``highest`` precision.

``--chips 4`` runs the gang path and what it is compared with, no other
phase: four one-chip members (env from the scheduler's gang reserve,
pinned by ``_pin_visible_devices``, joined by
``distributed_init_from_env``) train the default-preset transformer over
``gang_mesh()`` with ``KUBESHARE_TPU_MESH=dp=1,sp=2,tp=2``; then one
process drives all four chips with the same mesh, seed and steps.

Every earlier line is smoke output, not a benchmark metric. The LAST
line of stdout is the result, printed only when every phase passed::

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

Any error, timeout, non-finite loss or non-TPU device fails the run with
a non-zero exit and the phase named, and no result line.
"""

from __future__ import annotations

import argparse
import atexit
import json
import math
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

REPO = Path(__file__).resolve().parent
SHIM = REPO / "kubeshare_tpu" / "_shim"
NODE = "smoke-node"

GANG_MESH = "dp=1,sp=2,tp=2"


@dataclass
class Plan:
    """Sizes and limits of one run. The defaults ARE the smoke as the
    driver runs it; tests/test_chip_smoke.py rehearses the same phases
    on CPU with a smaller plan (a test-only hook — no program option
    selects it)."""

    platform: str = "tpu"             # what every chip owner must report
    child_env: dict = field(default_factory=dict)   # added to every child
    transformer_steps: int = 1500
    gate_model: str = "resnet"        # the whole-chip pod's workload
    gate_steps: int = 60
    gang_steps: int = 30
    #: flash attention cases (batch, seq, heads, kv_heads, head_dim): the
    #: default preset's head_dim 32 and a real-width 128, MHA and GQA,
    #: each causal with and without ``kernel_window``
    kernel_cases: tuple = ((2, 256, 8, 8, 32), (2, 256, 8, 2, 32),
                           (2, 1024, 8, 8, 128), (2, 1024, 8, 2, 128))
    kernel_window: int = 128
    #: leaves of the fused Adam: sizes (1-D) or shapes; the last is ragged
    #: in rows against the block and in lanes against the tile
    adam_sizes: tuple = (4096 * 256, 1000, (2100, 1003))
    #: in-kernel matmuls run at the MXU's default precision (bf16 passes)
    #: against a float32 ``highest`` reference: max |err| ≤ tol · max |ref|
    kernel_tol: float = 2e-2
    adam_tol: float = 1e-5
    #: each tenant's share of the proxy's device time: 0.50 ± 0.05
    split_tol: float = 0.05
    #: shared vs exclusive (and gang vs one process) final loss
    loss_rtol: float = 0.05
    loss_atol: float = 0.02
    #: the gate pod's ``tpu_mem`` grant arms the HBM cap from the
    #: allocator's stats and REFUSES to start without them — which is what
    #: a CPU backend (no stats) does, so only the rehearsal turns it off
    gate_mem_grant: bool = True
    #: added to every fixed listening port (the launcher's port map, the
    #: binding's pod-manager port): a rehearsal inside the test suite must
    #: not collide with other tests that start the real daemons
    port_shift: int = 0
    #: wipe ``_build/`` before building the native cores; a rehearsal
    #: leaves it, since other tests load the same libraries meanwhile
    fresh_native_build: bool = True
    child_timeout_s: float = 420.0
    ready_timeout_s: float = 180.0
    #: extra env of the four gang members / of the one four-chip process
    gang_member_env: dict = field(default_factory=dict)
    gang_single_env: dict = field(default_factory=dict)


class PhaseFailed(Exception):
    def __init__(self, phase: str, why: str):
        super().__init__(f"{phase}: {why}")
        self.phase = phase


def say(msg: str) -> None:
    print(f"[smoke {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# --------------------------------------------------------------------------
# children: one process group each, logs in files, all killed on the way out
# --------------------------------------------------------------------------

_LIVE: list[subprocess.Popen] = []


class Child:
    def __init__(self, phase: str, tag: str, cmd: list[str], env: dict,
                 workdir: Path):
        self.phase, self.tag = phase, tag
        self.out_path = workdir / f"{tag}.out"
        self.err_path = workdir / f"{tag}.err"
        self._out = open(self.out_path, "w")
        self._err = open(self.err_path, "w")
        self.t0 = time.monotonic()
        self.proc = subprocess.Popen(cmd, env=env, cwd=str(REPO),
                                     stdout=self._out, stderr=self._err,
                                     start_new_session=True)
        _LIVE.append(self.proc)

    @property
    def out(self) -> str:
        return self.out_path.read_text(errors="replace")

    @property
    def err(self) -> str:
        return self.err_path.read_text(errors="replace")

    def tail(self, n: int = 25) -> str:
        lines = (self.err + "\n" + self.out).strip().splitlines()
        return "\n".join(lines[-n:])

    def wait_line(self, pattern: str, timeout_s: float) -> re.Match:
        """Block until stdout shows ``pattern`` (the daemons' READY)."""
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            m = re.search(pattern, self.out)
            if m:
                return m
            if self.proc.poll() is not None:
                raise PhaseFailed(self.phase, f"{self.tag} exited rc="
                                  f"{self.proc.returncode} before "
                                  f"{pattern!r}:\n{self.tail()}")
            time.sleep(0.2)
        self.stop()
        raise PhaseFailed(self.phase, f"{self.tag} not ready after "
                          f"{timeout_s:.0f}s:\n{self.tail()}")

    def wait(self, timeout_s: float) -> float:
        """Wait for a clean exit; returns the child's wall seconds."""
        try:
            rc = self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            self.stop()
            raise PhaseFailed(self.phase, f"{self.tag} hung > "
                              f"{timeout_s:.0f}s:\n{self.tail()}") from None
        if rc != 0:
            raise PhaseFailed(self.phase,
                              f"{self.tag} exited rc={rc}:\n{self.tail()}")
        return time.monotonic() - self.t0

    def stop(self) -> None:
        _kill(self.proc)


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is not None:
        return
    for sig, grace in ((signal.SIGTERM, 10.0), (signal.SIGKILL, 5.0)):
        try:
            os.killpg(os.getpgid(proc.pid), sig)
        except (ProcessLookupError, PermissionError):
            return
        try:
            proc.wait(timeout=grace)
            return
        except subprocess.TimeoutExpired:
            continue


def _kill_all() -> None:
    for proc in _LIVE:
        _kill(proc)


atexit.register(_kill_all)


def base_env(plan: Plan, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                       if p])
    env.setdefault("TPU_LOG_DIR", "disabled")
    # cache every compile, not only those over JAX's one-second default:
    # a second run with the same cache dir then compiles nothing at all,
    # and its counts say so (written=0) instead of flickering around 1 s
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.update(plan.child_env)
    env.update({k: str(v) for k, v in extra.items()})
    return env


def shim_env(env: dict) -> dict:
    """What the node agent does to a pod: the attach shim first on
    PYTHONPATH (the LD_PRELOAD equivalent)."""
    env = dict(env)
    env["PYTHONPATH"] = os.pathsep.join([str(SHIM), env["PYTHONPATH"]])
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


_CACHE_LINE = re.compile(r"compile cache (\S+): requests=(\d+) hits=(\d+) "
                         r"written=(\d+)")
_RAN_ON = re.compile(r"ran on platform=(\S+) kind='([^']*)' count=(\d+)")
_RESULT = re.compile(r"(\d+) steps in ([\d.]+)s = ([\d.]+) steps/s, "
                     r"final loss (\S+)")


def cache_counts(*children: Child) -> dict:
    """Sum (and print) the children's compile-cache traffic:
    utils/compilecache logs one line per process at exit."""
    total = {"dir": "", "requests": 0, "hits": 0, "written": 0}
    for c in children:
        for m in _CACHE_LINE.finditer(c.err):
            total["dir"] = m.group(1)
            for key, g in zip(("requests", "hits", "written"), (2, 3, 4)):
                total[key] += int(m.group(g))
    say(f"compile cache {total['dir']}: requests={total['requests']} "
        f"hits={total['hits']} written={total['written']}")
    return total


def cli_result(child: Child) -> dict:
    """Parse a model CLI's result line + the device it reports."""
    m = _RESULT.search(child.out)
    if not m:
        raise PhaseFailed(child.phase,
                          f"{child.tag} printed no result:\n{child.tail()}")
    loss = float(m.group(4))
    if not math.isfinite(loss):
        raise PhaseFailed(child.phase, f"{child.tag} final loss {loss}")
    res = {"steps": int(m.group(1)), "seconds": float(m.group(2)),
           "steps_per_s": float(m.group(3)), "loss": loss}
    d = _RAN_ON.search(child.err)
    if d:
        res["device"] = {"platform": d.group(1), "kind": d.group(2),
                         "count": int(d.group(3))}
    return res


def need_platform(phase: str, who: str, got: str, plan: Plan) -> None:
    if got != plan.platform:
        raise PhaseFailed(phase, f"{who} runs on platform {got!r}, the "
                          f"smoke needs {plan.platform!r} (no accelerator, "
                          "or JAX held to another backend)")


def losses_agree(phase: str, what: str, a: float, b: float,
                 plan: Plan) -> None:
    if abs(a - b) > plan.loss_atol + plan.loss_rtol * abs(b):
        raise PhaseFailed(phase, f"{what}: losses {a} vs {b} differ by more "
                          f"than {plan.loss_atol} + {plan.loss_rtol}·|ref|")


# --------------------------------------------------------------------------
# phase 1: control plane on the real chip
# --------------------------------------------------------------------------

def phase_control_plane(plan: Plan, workdir: Path, want_chips: int = 1) -> dict:
    phase = "control-plane"
    from kubeshare_tpu import constants as C
    from kubeshare_tpu.isolation import native
    from kubeshare_tpu.nodeagent import launcherd
    from kubeshare_tpu.scheduler.engine import SchedulerEngine
    from kubeshare_tpu.topology.cellconfig import config_from_chips
    from kubeshare_tpu.topology.chip import ChipInfo
    from kubeshare_tpu.topology.discovery import node_inventory

    state = workdir / "state"
    t0 = time.monotonic()
    try:
        inv = node_inventory(NODE, str(state), env=base_env(plan))
    except RuntimeError as exc:
        raise PhaseFailed(phase, f"discovery: {exc}") from None
    need_platform(phase, "the discovered node", inv["platform"], plan)
    chips = [ChipInfo.from_labels(l) for l in inv["chips"]]
    if len(chips) != want_chips:
        raise PhaseFailed(phase, f"discovered {len(chips)} chip(s), this "
                          f"run needs {want_chips}")
    say(f"discovery (child, {time.monotonic() - t0:.1f}s): "
        f"device_kind={inv['device_kind']!r} x{len(chips)}, HBM "
        f"{chips[0].memory} bytes/chip (allocator bytes_limit), coords "
        f"{[c.coords for c in chips]}")

    # native cores from the committed .cpp into a FRESH _build/: a build
    # that silently became the Python twin is a failure here, not a log line
    if plan.fresh_native_build:
        shutil.rmtree(Path(native.__file__).resolve().parent / "_build",
                      ignore_errors=True)
    lib = native.build_library("tokensched")
    relay = native.build_binary("podmgr_relay")
    if not lib or not relay:
        raise PhaseFailed(phase, "native build failed (g++ and the committed "
                          ".cpp are required): tokensched="
                          f"{lib!r} podmgr_relay={relay!r}")
    say(f"native cores built: {Path(lib).name}, {Path(relay).name}")

    eng = SchedulerEngine(config_from_chips(chips))
    eng.add_node(NODE, chips)
    info = {"inventory": inv, "chips": chips, "engine": eng,
            "state": state, "relay": relay}
    if want_chips != 1:
        return info

    shared = {}
    for name in ("tenant-a", "tenant-b"):
        pod = eng.submit("smoke", name, {C.POD_TPU_REQUEST: "0.5",
                                         C.POD_TPU_LIMIT: "1.0"})
        shared[name] = eng.schedule(pod)
    if len({b.chip_ids[0] for b in shared.values()}) != 1:
        raise PhaseFailed(phase, "the two 0.5 pods did not share one chip")
    for name in shared:
        eng.delete_pod(f"smoke/{name}")
    whole = eng.schedule(eng.submit("smoke", "whole", {
        C.POD_TPU_REQUEST: "1", C.POD_TPU_LIMIT: "1"}))
    chip_ids = [c.chip_id for c in chips]
    chip = whole.chip_ids[0]
    exec_port = launcherd.exec_port_map(chip_ids)[chip] + plan.port_shift
    token_port = exec_port + launcherd.TOKEN_PORT_OFFSET
    mgr_port = whole.port + plan.port_shift
    proxy_cmd, proxy_env = launcherd.default_proxy_cmd(
        chip, chips[0].index, exec_port, token_port)
    gate_token_port = free_port()
    pmgr_cmd, pmgr_env = launcherd.default_pmgr_cmd(
        whole.pod_key, mgr_port, whole.request, whole.limit,
        gate_token_port)
    if pmgr_cmd != [relay]:
        raise PhaseFailed(phase, "the launcher chose the Python pod manager "
                          f"though the native relay was built: {pmgr_cmd}")
    say(f"bindings: tenant-a/b -> {chip} at 0.5/0.5 (tpu_mem "
        f"{shared['tenant-a'].memory} each), whole -> {chip} (tpu_mem "
        f"{whole.memory}); port map: proxy exec {exec_port}, tokens "
        f"{token_port}, pod manager {mgr_port}")
    info.update(shared=shared, whole=whole, exec_port=exec_port,
                mgr_port=mgr_port, proxy=(proxy_cmd, proxy_env),
                pmgr=(pmgr_cmd, pmgr_env), gate_token_port=gate_token_port)
    return info


# --------------------------------------------------------------------------
# phase 2: proxy attach, two tenants
# --------------------------------------------------------------------------

def phase_proxy_attach(plan: Plan, workdir: Path, cp: dict) -> dict:
    phase = "proxy-attach"
    from kubeshare_tpu import constants as C
    from kubeshare_tpu.isolation.client import ProxyClient

    t0 = time.monotonic()
    cmd, env = cp["proxy"]
    exec_port = cp["exec_port"]
    proxy = Child(phase, "proxy", cmd, base_env(plan, **env), workdir)
    proxy.wait_line(r"READY (\d+) TOKENS (\d+)", plan.ready_timeout_s)
    owns = re.search(r"chip proxy owns (.*?) platform=(\S+) kind='([^']*)'; "
                     r"token core (\w+)", proxy.err)
    if not owns:
        raise PhaseFailed(phase, "proxy never reported its chip:\n"
                          + proxy.tail())
    need_platform(phase, "the chip proxy", owns.group(2), plan)
    if owns.group(4) != "NativeTokenCore":
        raise PhaseFailed(phase, f"proxy serves tokens from {owns.group(4)} "
                          "though the native core was built")
    say(f"proxy owns {owns.group(1)} ({owns.group(3)}), token core "
        f"{owns.group(4)}, ready in {time.monotonic() - t0:.1f}s")

    # Node daemons must come up while the proxy owns the chip: they read
    # the inventory the discovery child left, and this process — standing
    # in for them — still has not imported jax.
    from kubeshare_tpu.telemetry.collector import CapacityCollector
    from kubeshare_tpu.telemetry.registry import TelemetryRegistry
    from kubeshare_tpu.topology.discovery import discover_chips
    registry = TelemetryRegistry()
    collector = CapacityCollector(registry, node=NODE, backend="jax",
                                  lease_ttl_s=0, state_dir=str(cp["state"]))
    seen = discover_chips("jax", host=NODE, state_dir=str(cp["state"]))
    if not collector.collect_once() or seen != cp["chips"]:
        raise PhaseFailed(phase, "daemon discovery failed while the proxy "
                          "owns the chip")
    say(f"daemon discovery while the proxy owns the chip: {len(seen)} "
        "chip(s) from the node inventory, jax not imported here")

    tenants = {}
    for name, binding in cp["shared"].items():
        env = shim_env(base_env(plan, **{
            **binding.env, C.ENV_CHIP_PROXY_PORT: exec_port}))
        tenants[binding.pod_key] = Child(
            phase, name, [sys.executable, "-m",
                          "kubeshare_tpu.models.transformer",
                          "--steps", str(plan.transformer_steps)],
            env, workdir)

    # an idle observer session: it never asks for a token, so the two
    # tenants' grants are what they would be without it
    with ProxyClient("127.0.0.1", exec_port, "smoke/observer", 0.01,
                     0.01) as observer:
        split: dict[str, float] = {}
        deadline = time.monotonic() + plan.child_timeout_s
        while (any(t.proc.poll() is None for t in tenants.values())
               and time.monotonic() < deadline):
            chip = observer.usage()["chip"]
            live = {k: v["exec_ms_total"]
                    for k, v in chip["sessions"].items() if k in tenants}
            if len(live) == 2 and all(v > 0 for v in live.values()):
                split = live
            time.sleep(0.25)
        walls = {k: t.wait(max(1.0, deadline - time.monotonic()))
                 for k, t in tenants.items()}
        chip = observer.usage()["chip"]
    proxy.stop()
    need_platform(phase, "the chip proxy (usage report)", chip["platform"],
                  plan)
    if chip["total_execs"] <= 0:
        raise PhaseFailed(phase, "the proxy executed nothing")
    if not split:
        raise PhaseFailed(phase, "never saw both tenants' sessions live "
                          "with device time")
    share = max(split.values()) / sum(split.values())
    results = {k: cli_result(t) for k, t in tenants.items()}
    for key, res in results.items():
        say(f"{key}: {res['steps']} steps, {res['steps_per_s']:.1f} steps/s "
            f"(smoke output), final loss {res['loss']:.4f}, wall "
            f"{walls[key]:.1f}s")
    say(f"device-time split {{{', '.join(f'{k}: {v:.0f} ms' for k, v in split.items())}}}"
        f" -> max share {share:.3f}; proxy lifetime executions "
        f"{chip['total_execs']}, platform {chip['platform']}")
    if share > 0.5 + plan.split_tol:
        raise PhaseFailed(phase, f"device-time split {split}: max share "
                          f"{share:.3f} > {0.5 + plan.split_tol}")

    # the same command and seed on the bare chip, now that the proxy
    # has given it back
    excl = Child(phase, "exclusive", [sys.executable, "-m",
                                      "kubeshare_tpu.models.transformer",
                                      "--steps", str(plan.transformer_steps)],
                 base_env(plan), workdir)
    excl.wait(plan.child_timeout_s)
    ref = cli_result(excl)
    need_platform(phase, "the exclusive run", ref["device"]["platform"], plan)
    say(f"exclusive: {ref['steps_per_s']:.1f} steps/s (smoke output), "
        f"final loss {ref['loss']:.4f}")
    for key, res in results.items():
        losses_agree(phase, f"{key} shared vs exclusive", res["loss"],
                     ref["loss"], plan)
    return {"cache": cache_counts(proxy, excl, *tenants.values()),
            "device": ref["device"],
            "seconds": time.monotonic() - t0}


# --------------------------------------------------------------------------
# phase 3: gate attach, whole chip
# --------------------------------------------------------------------------

def phase_gate_attach(plan: Plan, workdir: Path, cp: dict) -> dict:
    phase = "gate-attach"
    from kubeshare_tpu import constants as C
    from kubeshare_tpu.isolation import protocol
    from kubeshare_tpu.isolation.tokensched import (NativeTokenCore,
                                                    TokenScheduler, serve)

    t0 = time.monotonic()
    whole = cp["whole"]
    sched = TokenScheduler(native=True)
    if not isinstance(sched.core, NativeTokenCore):
        raise PhaseFailed(phase, "token scheduler fell to the Python core")
    sched_srv = serve(sched, port=cp["gate_token_port"])
    pmgr = None
    try:
        cmd, env = cp["pmgr"]
        pmgr = Child(phase, "podmgr", cmd, base_env(plan, **env), workdir)
        conn = None
        deadline = time.monotonic() + 30.0
        while conn is None:     # the relay registers upstream, then binds
            try:
                conn = protocol.Connection("127.0.0.1", cp["mgr_port"])
            except OSError:
                if pmgr.proc.poll() is not None or time.monotonic() > deadline:
                    raise PhaseFailed(phase, "pod manager never bound:\n"
                                      + pmgr.tail()) from None
                time.sleep(0.2)
        mem = whole.memory if plan.gate_mem_grant else 0
        env = shim_env(base_env(plan, **{
            **whole.env, C.ENV_ATTACH_MODE: "gate", C.ENV_TPU_MEMORY: mem,
            C.ENV_POD_MANAGER_PORT: cp["mgr_port"]}))
        pod = Child(phase, "gate-pod",
                    [sys.executable, "-m",
                     f"kubeshare_tpu.models.{plan.gate_model}",
                     "--steps", str(plan.gate_steps)], env, workdir)
        used = 0.0
        with conn:
            conn.call({"op": "register"})
            # charges land on the sliding window at renew/release time:
            # sample during the run and once after exit
            deadline = time.monotonic() + plan.child_timeout_s
            while pod.proc.poll() is None and time.monotonic() < deadline:
                reply, _ = conn.call({"op": "usage"})
                used = max(used, reply.get("used_ms", 0.0))
                time.sleep(0.25)
            pod.wait(max(1.0, deadline - time.monotonic()))
            reply, _ = conn.call({"op": "usage"})
            used = max(used, reply.get("used_ms", 0.0))
        # the pod restarts as an eager-only workload (no jax.jit of its
        # own): every eager op must pass the same gate
        eager, eager_report = run_self_child(phase, "eager", plan,
                                             workdir, env, {"ops": 50})
    finally:
        if pmgr is not None:
            pmgr.stop()
        sched_srv.shutdown()
        sched_srv.server_close()
        sched.close()
    res = cli_result(pod)
    need_platform(phase, "the gate pod", res["device"]["platform"], plan)
    if used <= 0:
        raise PhaseFailed(phase, "the gate pod was never charged device time")
    armed = "HBM cap armed" in pod.err
    if mem > 0 and not armed:
        raise PhaseFailed(phase, f"tpu_mem={mem} granted but the "
                          "HBM cap never armed:\n" + pod.tail())
    say(f"gate pod: {res['steps']} steps, {res['steps_per_s']:.1f} steps/s "
        f"(smoke output), final loss {res['loss']:.4f}; charged "
        f"{used:.1f} ms device time through the native pod manager; HBM "
        f"cap {'armed at ' + str(mem) + ' bytes' if armed else 'not granted'}")
    need_platform(phase, "the eager pod", eager_report["device"]["platform"],
                  plan)
    say(f"eager pod: {eager_report['gate_passes']} gate passes for "
        f"{eager_report['ops']} eager jnp ops")
    if eager_report["gate_passes"] < eager_report["ops"]:
        raise PhaseFailed(phase, "eager ops escaped the meter: "
                          f"{eager_report}")
    return {"cache": cache_counts(pod, eager),
            "seconds": time.monotonic() - t0}


# --------------------------------------------------------------------------
# phase 4: kernels on the chip (and the children that are this file)
# --------------------------------------------------------------------------

def run_self_child(phase: str, name: str, plan: Plan, workdir: Path,
                   env: dict, payload: dict) -> tuple[Child, dict]:
    """Run ``python chip_smoke.py --child <name>`` to its end; its last
    stdout line is its JSON report."""
    child = Child(phase, name, [sys.executable, str(REPO / "chip_smoke.py"),
                                "--child", name, json.dumps(payload)],
                  env, workdir)
    child.wait(plan.child_timeout_s)
    try:
        report = json.loads(child.out.strip().splitlines()[-1])
    except (IndexError, ValueError):
        raise PhaseFailed(phase, f"{name} printed no report:\n"
                          + child.tail()) from None
    return child, report


def phase_kernels(plan: Plan, workdir: Path) -> dict:
    phase = "kernels"
    t0 = time.monotonic()
    child, report = run_self_child(phase, "kernels", plan, workdir,
                                   base_env(plan), {
        "platform": plan.platform, "cases": plan.kernel_cases,
        "window": plan.kernel_window, "adam_sizes": plan.adam_sizes,
        "kernel_tol": plan.kernel_tol, "adam_tol": plan.adam_tol})
    for line in child.out.strip().splitlines()[:-1]:
        say(line)
    need_platform(phase, "the kernel child", report["device"]["platform"],
                  plan)
    if report["failures"]:
        raise PhaseFailed(phase, "; ".join(report["failures"]))
    return {"cache": cache_counts(child), "device": report["device"],
            "seconds": time.monotonic() - t0}


def _device_report() -> dict:
    import jax
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def _child_kernels(p: dict) -> None:
    import importlib

    import jax
    import jax.numpy as jnp
    import numpy as np
    import optax

    from kubeshare_tpu.ops.attention import dot_product_attention
    from kubeshare_tpu.utils.compilecache import enable_compile_cache
    enable_compile_cache()
    fa = importlib.import_module("kubeshare_tpu.ops.flash_attention")
    fad = importlib.import_module("kubeshare_tpu.ops.fused_adam")

    device = _device_report()
    on_tpu = p["platform"] == "tpu"
    failures: list[str] = []

    def compiled(fn, *args) -> bool:
        return "tpu_custom_call" in jax.jit(fn).lower(*args).as_text()

    for b, s, h, hk, d in p["cases"]:
        for window in (None, p["window"]):
            ks = jax.random.split(jax.random.PRNGKey(0), 4)
            q = jax.random.normal(ks[0], (b, s, h, d))
            k = jax.random.normal(ks[1], (b, s, hk, d))
            v = jax.random.normal(ks[2], (b, s, hk, d))
            g = jax.random.normal(ks[3], (b, s, h, d))

            def flash(q, k, v, g):
                out, vjp = jax.vjp(lambda q, k, v: fa.flash_attention(
                    q, k, v, causal=True, window=window), q, k, v)
                return (out, *vjp(g))

            def dense(q, k, v, g):
                out, vjp = jax.vjp(lambda q, k, v: dot_product_attention(
                    q, k, v, causal=True, window=window), q, k, v)
                return (out, *vjp(g))

            tag = f"flash b{b} s{s} h{h}/{hk} d{d} window={window}"
            is_compiled = compiled(flash, q, k, v, g)
            got = jax.jit(flash)(q, k, v, g)
            with jax.default_matmul_precision("highest"):
                want = jax.jit(dense)(q, k, v, g)
            rel = [float(jnp.max(jnp.abs(a - w)) / jnp.max(jnp.abs(w)))
                   for a, w in zip(got, want)]
            print(f"{tag}: compiled={is_compiled} max|err|/max|ref| "
                  f"o/dq/dk/dv = {[f'{r:.1e}' for r in rel]}", flush=True)
            if on_tpu and not is_compiled:
                failures.append(f"{tag}: no tpu_custom_call (interpreted)")
            if not all(np.isfinite(r) and r <= p["kernel_tol"] for r in rel):
                failures.append(f"{tag}: error {rel} > {p['kernel_tol']}")

    for n in p["adam_sizes"]:
        shape = tuple(np.atleast_1d(n))
        rng = np.random.default_rng(0)
        params = jnp.asarray(rng.normal(size=shape).astype(np.float32))
        m, v = jnp.zeros_like(params), jnp.zeros_like(params)
        opt = optax.adam(1e-3)
        state, ref = opt.init(params), params
        is_compiled = compiled(
            lambda p_, g_, m_, v_: fad.adam_update(p_, g_, m_, v_, step=1),
            params, params, m, v)
        with jax.default_matmul_precision("highest"):
            for t in range(1, 4):
                grad = jnp.asarray(rng.normal(size=shape).astype(np.float32))
                upd, state = opt.update(grad, state, ref)
                ref = optax.apply_updates(ref, upd)
                params, m, v = fad.adam_update(params, grad, m, v, step=t)
        err = float(jnp.max(jnp.abs(params - ref)))
        print(f"fused adam n={n}: compiled={is_compiled} max|err| vs "
              f"optax.adam = {err:.1e}", flush=True)
        if on_tpu and not is_compiled:
            failures.append(f"fused adam n={n}: no tpu_custom_call")
        if not (np.isfinite(err) and err <= p["adam_tol"]):
            failures.append(f"fused adam n={n}: error {err}")

    print(json.dumps({"device": device, "failures": failures}), flush=True)


# --------------------------------------------------------------------------
# --chips 4: the gang path and what it is compared with
# --------------------------------------------------------------------------

def phase_gang(plan: Plan, workdir: Path, cp: dict) -> dict:
    phase = "gang"
    from kubeshare_tpu import constants as C

    t0 = time.monotonic()
    eng = cp["engine"]
    labels = {C.POD_TPU_REQUEST: "1", C.POD_TPU_LIMIT: "1",
              C.POD_GROUP_NAME: "smoke-gang", C.POD_GROUP_HEADCOUNT: "4",
              C.POD_GROUP_THRESHOLD: "1"}
    pods = [eng.submit("smoke", f"member-{i}", labels) for i in range(4)]
    bindings = [eng.schedule(pod) for pod in pods]
    coord = f"localhost:{free_port()}"
    members = []
    for b in bindings:
        env = dict(b.env)
        # whole-chip members run unmetered (attach's "distributed" path):
        # no pod manager, so none of its variables
        for key in (C.ENV_POD_MANAGER_PORT, C.ENV_TPU_REQUEST,
                    C.ENV_TPU_LIMIT, C.ENV_TPU_MEMORY):
            env.pop(key, None)
        env.update({C.ENV_COORDINATOR: coord, "KUBESHARE_TPU_MESH": GANG_MESH,
                    C.ENV_RENDEZVOUS_TIMEOUT_S: "120"})
        say(f"{b.pod_key}: rank {b.group_rank}/{b.group_size}, grant "
            f"{env[C.ENV_VISIBLE_CHIPS]} on node mesh "
            f"{env.get(C.ENV_MESH_SHAPE)}")
        members.append(Child(
            phase, f"member-{b.group_rank}",
            [sys.executable, "-m", "kubeshare_tpu.models.transformer",
             "--steps", str(plan.gang_steps)],
            shim_env(base_env(plan, **{**env, **plan.gang_member_env})),
            workdir))
    deadline = time.monotonic() + plan.child_timeout_s
    try:
        for mchild in members:
            mchild.wait(max(1.0, deadline - time.monotonic()))
    except PhaseFailed:
        for mchild in members:      # one member's failure strands the rest
            mchild.stop()
            say(f"--- {mchild.tag} ---\n{mchild.tail(12)}")
        raise
    results = [cli_result(mchild) for mchild in members]
    for mchild, res in zip(members, results):
        need_platform(phase, mchild.tag, res["device"]["platform"], plan)
        if res["device"]["count"] != 4:
            raise PhaseFailed(phase, f"{mchild.tag} saw "
                              f"{res['device']['count']} device(s): four "
                              "isolated runtimes, not one mesh")
        losses_agree(phase, f"{mchild.tag} vs member-0", res["loss"],
                     results[0]["loss"], plan)
    say(f"four processes x one chip: {plan.gang_steps} steps each, "
        f"{results[0]['steps_per_s']:.1f} steps/s (smoke output), final "
        f"losses {[r['loss'] for r in results]}")

    child, report = run_self_child(
        phase, "single", plan, workdir,
        base_env(plan, **{"KUBESHARE_TPU_MESH": GANG_MESH,
                          **plan.gang_single_env}),
        {"steps": plan.gang_steps})
    need_platform(phase, "the one-process run", report["device"]["platform"],
                  plan)
    say(f"one process x four chips: final loss {report['loss']}, params on "
        f"{report['param_devices']} devices, fc.w shard "
        f"{report['fc_shard']} of {report['fc_shape']}, tokens shard "
        f"{report['token_shard']}, collectives {report['collectives']}")
    if report["failures"]:
        raise PhaseFailed(phase, "; ".join(report["failures"]))
    losses_agree(phase, "gang vs one process", results[0]["loss"],
                 report["loss"], plan)
    return {"cache": cache_counts(child, *members),
            "device": report["device"], "seconds": time.monotonic() - t0}


def _child_single(p: dict) -> None:
    """One process, every chip: train through ``run_training`` (the CLI's
    own loop) over ``gang_mesh()``, then prove the layout on the same
    building blocks: shard shapes, distinct devices, collectives."""
    import jax
    import optax

    from kubeshare_tpu.models import transformer
    from kubeshare_tpu.models.common import run_training
    from kubeshare_tpu.parallel.mesh import (make_sharded_train_step,
                                             param_sharding, token_sharding)
    from kubeshare_tpu.parallel.runner import gang_mesh
    from kubeshare_tpu.utils.compilecache import enable_compile_cache
    enable_compile_cache()

    device = _device_report()
    failures: list[str] = []
    mesh = gang_mesh()
    result = run_training(transformer.init, transformer.loss_fn,
                          transformer.batch_fn, p["steps"], mesh=mesh,
                          mesh_hooks=transformer.MESH_HOOKS)
    if not math.isfinite(result.final_loss):
        failures.append(f"final loss {result.final_loss}")

    pkey, bkey = jax.random.split(jax.random.PRNGKey(0))
    params = transformer.init(pkey)
    params = jax.device_put(params, param_sharding(mesh, params))
    batch = jax.device_put(transformer.batch_fn(bkey), token_sharding(mesh))
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step = make_sharded_train_step(
        transformer.MESH_HOOKS["loss"](mesh), optimizer, mesh,
        batch_sharding=token_sharding(mesh))
    text = step.lower(params, opt_state, batch).compile().as_text()
    collectives = sorted(c for c in ("all-reduce", "all-gather",
                                     "collective-permute", "reduce-scatter",
                                     "all-to-all") if c in text)
    tp, sp = mesh.shape["tp"], mesh.shape["sp"]
    fc = params["blocks"][0]["fc"]["w"]
    fc_shard = fc.sharding.shard_shape(fc.shape)
    tok_shard = batch[0].sharding.shard_shape(batch[0].shape)
    if len(fc.sharding.device_set) != 4:
        failures.append(f"params on {len(fc.sharding.device_set)} devices")
    if fc_shard[-1] != fc.shape[-1] // tp:
        failures.append(f"fc.w not column-split over tp: {fc_shard}")
    if tok_shard != (batch[0].shape[0], batch[0].shape[1] // sp):
        failures.append(f"tokens not split over sp: {tok_shard}")
    if "collective-permute" not in collectives or not (
            {"all-reduce", "reduce-scatter"} & set(collectives)):
        failures.append(f"compiled step lacks the ring/tp collectives: "
                        f"{collectives}")
    print(json.dumps({
        "device": device, "failures": failures, "loss": result.final_loss,
        "param_devices": len(fc.sharding.device_set),
        "fc_shape": list(fc.shape), "fc_shard": list(fc_shard),
        "token_shard": list(tok_shard), "collectives": collectives}),
        flush=True)


def _child_eager(p: dict) -> None:
    """A gate-attached pod (the shim attached this process before this
    line ran) that computes eagerly only: count its passes of the gate."""
    import jax.numpy as jnp

    from kubeshare_tpu import attach
    from kubeshare_tpu.isolation.client import ExecutionGate

    if attach.active_mode() != "gate":
        raise SystemExit(f"not gate-attached: {attach.active_mode()!r}")
    passes: list[int] = []
    real_call = ExecutionGate.__call__
    ExecutionGate.__call__ = lambda self: (passes.append(1),
                                           real_call(self))[1]
    x = jnp.ones((256, 256))
    passes.clear()
    for _ in range(p["ops"]):
        x = jnp.tanh(x @ x)          # two eager ops, never a jit of ours
    float(x[0, 0])
    print(json.dumps({"device": _device_report(), "ops": 2 * p["ops"],
                      "gate_passes": len(passes)}), flush=True)


_CHILDREN = {"kernels": _child_kernels, "single": _child_single,
             "eager": _child_eager}


# --------------------------------------------------------------------------

def run(plan: Plan, chips: int = 1) -> dict:
    """All phases of one run; returns the device the chip's owners
    reported. Raises :class:`PhaseFailed`."""
    t0 = time.monotonic()
    workdir = Path(tempfile.mkdtemp(prefix="chip_smoke-"))
    try:
        cp = phase_control_plane(plan, workdir, want_chips=chips)
        if chips == 4:
            phases = {"gang": phase_gang(plan, workdir, cp)}
        else:
            phases = {"proxy-attach": phase_proxy_attach(plan, workdir, cp),
                      "gate-attach": phase_gate_attach(plan, workdir, cp),
                      "kernels": phase_kernels(plan, workdir)}
        if "jax" in sys.modules:
            raise PhaseFailed("parent", "this process imported jax: a "
                              "parent that touches JAX holds the chip")
    finally:
        _kill_all()
        shutil.rmtree(workdir, ignore_errors=True)
    device = list(phases.values())[-1]["device"]
    total = {k: sum(ph["cache"][k] for ph in phases.values())
             for k in ("requests", "hits", "written")}
    say("phase seconds (smoke output): "
        + ", ".join(f"{k} {v['seconds']:.1f}" for k, v in phases.items())
        + f"; total {time.monotonic() - t0:.1f}")
    say(f"compile cache, whole run: requests={total['requests']} "
        f"hits={total['hits']} written={total['written']}")
    if device["count"] != chips:
        raise PhaseFailed("result", f"{device['count']} device(s) reported, "
                          f"{chips} wanted")
    return device


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="chip_smoke.py")
    parser.add_argument("--chips", type=int, choices=(1, 4), default=1,
                        help="4 = the gang path only (needs four chips)")
    parser.add_argument("--child", nargs=2, metavar=("NAME", "JSON"),
                        help=argparse.SUPPRESS)
    args = parser.parse_args(argv)
    if args.child:
        _CHILDREN[args.child[0]](json.loads(args.child[1]))
        return 0
    try:
        device = run(Plan(), chips=args.chips)
    except PhaseFailed as exc:
        print(f"chip_smoke FAILED in phase {exc}", flush=True)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
