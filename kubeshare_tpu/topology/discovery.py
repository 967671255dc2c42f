"""Chip discovery.

The reference discovers devices through NVML (``pkg/collector/gpu.go:26-107``,
including the MIG sub-device branch). The TPU equivalent enumerates chips
through the live PJRT client (JAX), which exposes device kind, HBM size and
ICI mesh coordinates — so, unlike the reference, the full topology is
discoverable and the hand-written cluster config file becomes an optional
override (the reference's own TODO at ``pkg/scheduler/config.go:18``).

Two backends:

- ``jax``:  enumerate ``jax.local_devices()`` on the machine that owns
  the chips — in a SHORT-LIVED CHILD, never in the caller. A chip belongs
  to one process at a time: a node daemon that enumerated in-process
  would hold the device its own children (the chip proxy, a whole-chip
  pod) need. The child gives the chip back when it exits, and its
  answer is kept as the node's chip inventory (``<state_dir>/chips.json``)
  so that a daemon starting while a proxy owns the chip still learns
  what the node has.
- ``fake``: a synthetic mesh for tests and simulation — the analog of the
  reference's *missing* fake-NVML (it had none; SURVEY §4).
"""

from __future__ import annotations

import fcntl
import json
import os
import subprocess
import sys
from dataclasses import dataclass
from pathlib import Path

from ..utils import default_node_name
from .chip import ChipInfo, make_chip_id, normalize_model

DEFAULT_FAKE_HBM = 16 * 1024**3
#: backend start-up in the probe child; past it the chip is held or broken
PROBE_TIMEOUT_S = 120.0
INVENTORY_FILE = "chips.json"


@dataclass
class FakeTopology:
    """Synthetic TPU fleet: ``hosts`` machines × a ``mesh`` of chips each.

    ``mesh`` is the per-host chip grid (e.g. ``(2, 2)`` for a v4 host's 4
    chips); global coords place hosts side by side along the first axis.
    """

    hosts: int = 1
    mesh: tuple[int, ...] = (2, 2)
    model: str = "TPU-v4"
    memory: int = DEFAULT_FAKE_HBM
    host_prefix: str = "tpu-host"
    #: hosts per ICI slice: 0 = single-host fleets with no slice identity
    #: (standalone machines); N > 0 stamps ``slice_id`` so N-host groups
    #: form ONE multi-host slice cell and separate groups stay SEPARATE
    #: cells in ``config_from_chips`` — what live discovery reports via
    #: ``d.slice_index`` (discovery.py:86)
    hosts_per_slice: int = 0

    def chips(self) -> list[ChipInfo]:
        chips: list[ChipInfo] = []
        per_host = 1
        for d in self.mesh:
            per_host *= d
        for h in range(self.hosts):
            host = f"{self.host_prefix}-{h}"
            slice_id = ("" if not self.hosts_per_slice
                        else str(h // self.hosts_per_slice))
            for i in range(per_host):
                coords = []
                rem = i
                for dim in reversed(self.mesh):
                    coords.append(rem % dim)
                    rem //= dim
                coords.reverse()
                coords[0] += h * self.mesh[0]  # hosts tile along axis 0
                chips.append(ChipInfo(
                    chip_id=make_chip_id(self.model, host, i),
                    index=i,
                    host=host,
                    model=self.model,
                    memory=self.memory,
                    coords=tuple(coords),
                    slice_id=slice_id,
                ))
        return chips


def _jax_chips(host: str | None = None) -> list[ChipInfo]:
    """Enumerate in THIS process — which thereby takes the chips. Only
    the probe child (:func:`probe_node`) and a process that owns them
    anyway should call it."""
    import jax

    host = host or default_node_name()
    chips: list[ChipInfo] = []
    for d in jax.local_devices():
        model = normalize_model(d.device_kind)
        stats = d.memory_stats()
        if stats and "bytes_limit" in stats:
            memory = int(stats["bytes_limit"])
        elif d.platform == "cpu":
            memory = DEFAULT_FAKE_HBM  # virtual test devices have no HBM
        else:
            # No guessed capacity for a real device: every tpu_mem grant
            # and HBM cap downstream would be divided out of a number
            # nobody measured.
            raise RuntimeError(
                f"{d} ({d.platform}) reports no allocator stats "
                f"(memory_stats() = {stats!r}); cannot size its HBM")
        coords = tuple(getattr(d, "coords", ()) or ())
        # Per-host index (NVML-index parity): local_hardware_id restarts at 0
        # on every host, unlike the global d.id.
        index = getattr(d, "local_hardware_id", None)
        if index is None:
            index = d.id
        slice_index = getattr(d, "slice_index", None)
        slice_id = "" if slice_index is None else str(slice_index)
        chips.append(ChipInfo(
            chip_id=make_chip_id(model, host, index),
            index=index,
            host=host,
            model=model,
            memory=memory,
            coords=coords,
            slice_id=slice_id,
        ))
    return chips


def _probe_main() -> None:
    """Body of the probe child: one JSON line — the platform and device
    kind as JAX reports them, and the chips' labels."""
    import jax

    host = sys.argv[1] if len(sys.argv) > 1 else None
    chips = _jax_chips(host or None)
    dev = jax.local_devices()[0]
    print(json.dumps({"platform": dev.platform,
                      "device_kind": dev.device_kind,
                      "chips": [c.to_labels() for c in chips]}), flush=True)


def probe_node(host: str | None = None,
               timeout_s: float = PROBE_TIMEOUT_S,
               env: dict | None = None) -> dict:
    """Enumerate the node's chips in a short-lived child process that
    gives them back on exit; the caller never initializes a JAX backend.
    Returns ``{"platform", "device_kind", "chips": [labels...]}``.
    Raises when the child cannot reach the chips (held by another
    process, runtime broken) — there is no substitute answer."""
    env = dict(os.environ if env is None else env)
    pkg_parent = str(Path(__file__).resolve().parents[2])
    env["PYTHONPATH"] = os.pathsep.join(
        p for p in (pkg_parent, env.get("PYTHONPATH", "")) if p)
    code = ("from kubeshare_tpu.topology.discovery import _probe_main; "
            "_probe_main()")
    try:
        proc = subprocess.run([sys.executable, "-c", code, host or ""],
                              env=env, capture_output=True, text=True,
                              timeout=timeout_s)
    except subprocess.TimeoutExpired:
        raise RuntimeError(
            f"chip discovery child hung > {timeout_s:.0f}s — is the chip "
            "held by another process?") from None
    lines = proc.stdout.strip().splitlines()
    if proc.returncode != 0 or not lines:
        tail = (proc.stderr or proc.stdout).strip().splitlines()
        raise RuntimeError("chip discovery child failed (rc="
                           f"{proc.returncode}): "
                           + (tail[-1] if tail else "no output"))
    # the runtime may print banners before the child's own (last) line
    return json.loads(lines[-1])


def _boot_id() -> str:
    try:
        with open("/proc/sys/kernel/random/boot_id") as f:
            return f.read().strip()
    except OSError:
        return ""


def node_inventory(host: str | None = None, state_dir: str | None = None,
                   timeout_s: float = PROBE_TIMEOUT_S,
                   env: dict | None = None) -> dict:
    """The node's chips (:func:`probe_node`'s record plus ``host`` and
    ``boot_id``): from the inventory ``<state_dir>/chips.json`` when a
    probe of this boot already wrote it, else from a fresh probe whose
    answer becomes the inventory. Serialized by a file lock, so daemons
    starting together run ONE probe (two children would fight over the
    chip). ``state_dir=None`` keeps no inventory: every call probes."""
    host = host or default_node_name()

    def probe() -> dict:
        return dict(probe_node(host, timeout_s, env), host=host,
                    boot_id=_boot_id())

    if state_dir is None:
        return probe()
    os.makedirs(state_dir, exist_ok=True)
    path = os.path.join(state_dir, INVENTORY_FILE)
    with open(path + ".lock", "w") as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        try:
            with open(path) as f:
                inv = json.load(f)
        except FileNotFoundError:
            inv = None
        if (inv is not None and inv.get("host") == host
                and inv.get("boot_id") == _boot_id()):
            return inv
        inv = probe()
        tmp = path + ".tmp"
        with open(tmp, "w") as f:
            json.dump(inv, f)
        os.replace(tmp, path)
        return inv


def discover_chips(backend: str = "auto", host: str | None = None,
                   fake: FakeTopology | None = None,
                   state_dir: str | None = None) -> list[ChipInfo]:
    """Enumerate local chips without taking them.

    ``backend``: ``"jax"`` (live PJRT, through :func:`node_inventory` — a
    child process and the node inventory under ``state_dir``), ``"fake"``
    (synthetic), or ``"auto"`` (``fake`` iff
    ``$KUBESHARE_TPU_FAKE_TOPOLOGY`` is set, e.g. ``"2:2x2"`` = 2 hosts
    of a 2×2 mesh).
    """
    if backend == "auto":
        backend = "fake" if os.environ.get("KUBESHARE_TPU_FAKE_TOPOLOGY") else "jax"
    if backend == "jax":
        return [ChipInfo.from_labels(labels) for labels in
                node_inventory(host, state_dir)["chips"]]
    if backend == "fake":
        if fake is None:
            fake = parse_fake_spec(os.environ.get("KUBESHARE_TPU_FAKE_TOPOLOGY", "1:2x2"))
        chips = fake.chips()
        if host is not None:
            # A per-node collector must report only its own chips — a
            # host outside the fake fleet's namespace reports none (a
            # whole-fleet fallback would make every collector publish
            # every chip as its own).
            return [c for c in chips if c.host == host]
        return chips
    raise ValueError(f"unknown discovery backend: {backend}")


def parse_fake_spec(spec: str) -> FakeTopology:
    """``"<hosts>:<d0>x<d1>[x<d2>][@<model>]"`` → :class:`FakeTopology`."""
    model = "TPU-v4"
    if "@" in spec:
        spec, model = spec.split("@", 1)
    hosts_str, _, mesh_str = spec.partition(":")
    if not mesh_str:
        hosts_str, mesh_str = "1", hosts_str
    mesh = tuple(int(d) for d in mesh_str.split("x"))
    return FakeTopology(hosts=int(hosts_str), mesh=mesh, model=model)
