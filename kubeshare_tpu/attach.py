"""Transparent attach: route an UNMODIFIED JAX workload through the
isolation runtime, driven purely by environment variables.

The reference achieves zero-touch attach by injecting
``LD_PRELOAD=libgemhook.so.1`` + ``POD_MANAGER_PORT`` into the pod spec
(``pkg/scheduler/pod.go:445-457``); the hook intercepts the CUDA driver
API and the workload never knows. The Python/JAX equivalent is a
``sitecustomize`` shim (``kubeshare_tpu/_shim/sitecustomize.py``) that the
node agent puts on the container's ``PYTHONPATH``; it calls
:func:`attach_if_env` before the workload's first ``import jax``.

Two modes, chosen from the injected env:

- **proxy** (``KUBESHARE_TPU_CHIP_PROXY_PORT`` set): the workload must
  NOT own the chip (single-tenant per process). The client process is
  forced onto the CPU backend and ``jax.jit`` is replaced by a wrapper
  that traces the function abstractly, compiles it on the
  :class:`~.isolation.proxy.ChipProxy`, and executes it there. Arrays
  returned from jitted calls are :class:`RemoteArray` handles — they stay
  device-resident on the proxy and flow back into later jitted calls as
  handles, so a training loop ships its parameters once. Reading one
  (``float(loss)``, ``np.asarray``) fetches it.
- **gate** (only ``KUBESHARE_TPU_POD_MANAGER_PORT`` set): Gemini-parity
  metering without execution forwarding — every jitted call first passes
  an :class:`~.isolation.client.ExecutionGate` token round-trip (the
  hook ⇄ gem-pmgr ⇄ gem-schd loop). This is the fallback for a shared
  pod whose node agent did not inject a chip-proxy port (the process
  dispatches to the device itself, sharing only via tokens — exactly the
  reference's model on multi-process-capable devices). Whole-chip pods
  (port 0) attach nothing, matching the reference's multi-GPU path
  (pod.go:348-400: no LD_PRELOAD, no port).

Neither mode requires a single source change in the workload:
``python -m kubeshare_tpu.models.mnist`` (or any JAX script) attaches
through env vars alone.
"""

from __future__ import annotations

import atexit
import os
import threading

import numpy as np

from . import constants as C
from .utils.logger import get_logger
from .utils.realjit import keep as _keep_real_jit
from .utils.realjit import real_jit  # noqa: F401  (attach.real_jit, public)

log = get_logger("attach")

_state_lock = threading.Lock()
_active: "_AttachState | None" = None


class _AttachState:
    def __init__(self, mode: str, shim=None, gate=None,
                 originals: dict | None = None):
        self.mode = mode
        self.shim = shim
        self.gate = gate
        #: other jax attributes replaced at attach time, for detach
        self.originals = originals or {}


class RemoteArray:
    """A device-resident array on the chip proxy, posing as the result of
    a jitted call. Cheap to thread back into further jitted calls (it
    travels as a handle); materializing it (``np.asarray``, ``float``)
    fetches the bytes."""

    def __init__(self, shim: "_ProxyShim", buf):
        self._shim = shim
        self.buf = buf

    @property
    def shape(self):
        return self.buf.shape

    @property
    def dtype(self):
        return np.dtype(self.buf.dtype)

    @property
    def ndim(self):
        return len(self.buf.shape)

    @property
    def size(self):
        n = 1
        for d in self.buf.shape:
            n *= d
        return n

    @property
    def nbytes(self):
        return self.buf.nbytes

    def block_until_ready(self):
        return self  # the proxy blocks on device completion per dispatch

    def fetch(self) -> np.ndarray:
        return self._shim.fetch(self.buf)

    def __array__(self, dtype=None, copy=None):
        arr = self.fetch()
        return arr.astype(dtype) if dtype is not None else arr

    def __float__(self):
        return float(self.fetch())

    def __int__(self):
        return int(self.fetch())

    def __bool__(self):
        return bool(self.fetch())

    def __index__(self):
        return int(self.fetch())

    def __format__(self, spec):
        if self.ndim == 0:
            return format(self.fetch()[()], spec)
        return format(repr(self), spec)

    def __repr__(self):
        return f"RemoteArray(shape={tuple(self.shape)}, dtype={self.dtype})"

    def __del__(self):
        # No I/O here: __del__ can fire on any thread mid-protocol-call.
        # Queue the handle: it rides on the client's next request.
        try:
            self._shim.client.free_later(self.buf)
        except Exception:
            pass


class _ProxyShim:
    """Owns the ProxyClient connection + the jax.jit replacement."""

    def __init__(self, host: str, port: int, name: str, request: float,
                 limit: float, memory: int):
        from .isolation.client import ProxyClient

        self.client = ProxyClient(host, port, name, request, limit,
                                  memory=memory)

    def fetch(self, buf) -> np.ndarray:
        with self.client.shim_clock:    # the shim's own cost, in CPU time
            return self.client.get(buf)

    # -- the jax.jit replacement ------------------------------------------

    def jit(self, fn=None, **jit_kwargs):
        if fn is None:  # decorator-with-arguments form
            return lambda f: self.jit(f, **jit_kwargs)
        return _RemoteJitFunction(self, fn, jit_kwargs)

    def close(self) -> None:
        try:
            self.client.close()
        except Exception:
            pass


class _RemoteJitFunction:
    """Stand-in for a ``jax.jit``-wrapped function: traces remotely on
    first call per (structure, shapes, statics) and executes on the proxy
    thereafter."""

    def __init__(self, shim: _ProxyShim, fn, jit_kwargs: dict):
        self._shim = shim
        self._fn = fn
        self._static_argnums = _as_tuple(jit_kwargs.get("static_argnums"))
        self._static_argnames = _as_tuple(jit_kwargs.get("static_argnames"))
        # donate_argnums is accepted but not forwarded: a donated
        # argument is still referenced by the caller's frame when the
        # execute goes out, so the proxy cannot take it as dead; its
        # buffer is freed when its RemoteArray is collected, and the free
        # rides on the next execute, where the proxy writes that call's
        # outputs into it (output recycling). Forwarding it (so a step
        # holds one copy of its state, not two) is ROADMAP S4.
        self._cache: dict = {}
        self.__wrapped__ = fn

    def __call__(self, *args, **kwargs):
        # from here to the result's handles is the shim's own cost: the
        # thread's CPU time, so no wait for a reply is in it
        with self._shim.client.shim_clock:
            if _contains_tracers(args, kwargs):
                # We're INSIDE a trace (a library helper jitted at call
                # time, e.g. optax.tree.bias_correction, invoked from a
                # function being remoted): inline into the enclosing
                # program, exactly what a nested jit does.
                return self._fn(*args, **kwargs)
            return self._call_remote(args, kwargs)

    def _call_remote(self, args, kwargs):
        import jax

        shim = self._shim

        static_items = []
        dyn_args = list(args)
        for i in sorted(self._static_argnums, reverse=True):
            if i < len(dyn_args):
                static_items.append((f"#{i}", dyn_args.pop(i)))
        dyn_kwargs = dict(kwargs)
        for name in self._static_argnames:
            if name in dyn_kwargs:
                static_items.append((name, dyn_kwargs.pop(name)))
        static_items.sort()

        tree = (tuple(dyn_args), dyn_kwargs)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        bufs = [x.buf if isinstance(x, RemoteArray) else x for x in leaves]
        specs = tuple(_leaf_spec(b) for b in bufs)
        key = (treedef, specs, tuple(static_items))

        exe = self._cache.get(key)
        if exe is None:
            exe = self._compile(treedef, specs, static_items)
            self._cache[key] = exe
        out = exe(jax.tree_util.tree_unflatten(treedef, bufs))
        from .isolation.client import RemoteBuffer

        return jax.tree_util.tree_map(
            lambda b: RemoteArray(shim, b) if isinstance(b, RemoteBuffer)
            else b, out)

    def _compile(self, treedef, specs, static_items):
        import jax

        fn = self._fn
        statics = dict(static_items)

        def wrapped(tree):
            args, kwargs = tree
            args = list(args)
            # re-insert static positionals in ascending index order — the
            # lexicographic dict order would place '#10' before '#2' and
            # bind values to the wrong parameters
            for k in sorted((k for k in statics if k.startswith("#")),
                            key=lambda k: int(k[1:])):
                args.insert(int(k[1:]), statics[k])
            for k, v in statics.items():
                if not k.startswith("#"):
                    kwargs = dict(kwargs, **{k: v})
            return fn(*args, **kwargs)

        example_leaves = [jax.ShapeDtypeStruct(shape, np.dtype(dtype))
                          for shape, dtype in specs]
        example = jax.tree_util.tree_unflatten(treedef, example_leaves)
        return self._shim.client.compile(wrapped, example)


def _contains_tracers(args, kwargs) -> bool:
    """True when a call is happening under an enclosing jax trace."""
    import jax

    return any(isinstance(x, jax.core.Tracer)
               for x in jax.tree_util.tree_leaves((args, kwargs)))


def _as_tuple(v):
    if v is None:
        return ()
    if isinstance(v, (int, str)):
        return (v,)
    return tuple(v)


def _leaf_spec(leaf):
    from .isolation.client import RemoteBuffer

    if isinstance(leaf, RemoteBuffer):
        return (tuple(leaf.shape), str(leaf.dtype))
    arr = np.asarray(leaf)
    return (tuple(arr.shape), str(arr.dtype))


# --------------------------------------------------------------------------
# activation
# --------------------------------------------------------------------------

_PROXY_SURFACE_MSG = (
    "kubeshare-tpu: jax.{api} is not supported under proxy attach — this "
    "process runs on its CPU backend and the chip is owned by the node's "
    "chip proxy. Route device work through jax.jit (forwarded to the chip "
    "transparently); see README 'Supported JAX surface under proxy "
    "attach'. The reference's hook covers the whole CUDA driver API; the "
    "TPU proxy covers the jit path, and everything else fails loudly "
    "rather than silently computing on the client CPU.")

_ACCEL_PLATFORMS = ("tpu",)


def _is_accel_device(dev) -> bool:
    plat = getattr(dev, "platform", None)
    if isinstance(plat, str) and plat.lower() in _ACCEL_PLATFORMS:
        return True
    dev_set = getattr(dev, "device_set", None)  # Sharding
    if dev_set:
        return any(getattr(d, "platform", "").lower() in _ACCEL_PLATFORMS
                   for d in dev_set)
    return False


def _guard_proxy_surface(jax) -> dict:
    """Replace the JAX APIs the proxy shim does NOT forward with loud
    failures (VERDICT r3 missing-3): a ``pmap``/accelerator-``devices``/
    accelerator-``device_put`` workload must error with an actionable
    message, not silently train on the client's CPU backend. Returns the
    originals for :func:`detach`."""
    originals = {"pmap": jax.pmap, "devices": jax.devices,
                 "local_devices": jax.local_devices,
                 "device_put": jax.device_put}

    def pmap_fail(*a, **k):
        raise RuntimeError(_PROXY_SURFACE_MSG.format(api="pmap") +
                           " For multi-chip SPMD, run as a gang of "
                           "whole-chip pods (parallel.runner).")

    def devices_guard(backend=None):
        if backend is not None and str(backend).lower() in _ACCEL_PLATFORMS:
            raise RuntimeError(_PROXY_SURFACE_MSG.format(
                api=f'devices("{backend}")'))
        return originals["devices"](backend)

    def local_devices_guard(process_index=None, backend=None, host_id=None):
        if backend is not None and str(backend).lower() in _ACCEL_PLATFORMS:
            raise RuntimeError(_PROXY_SURFACE_MSG.format(
                api=f'local_devices(backend="{backend}")'))
        kw = {}
        if process_index is not None:
            kw["process_index"] = process_index
        if backend is not None:
            kw["backend"] = backend
        if host_id is not None:
            kw["host_id"] = host_id
        return originals["local_devices"](**kw)

    warned = []

    def device_put_guard(x, device=None, *, src=None, donate=False,
                         may_alias=None):
        if device is not None and _is_accel_device(device):
            raise RuntimeError(_PROXY_SURFACE_MSG.format(
                api="device_put(..., <accelerator device>)"))
        if device is None and not warned:
            warned.append(True)
            log.warning("jax.device_put under proxy attach places on the "
                        "client CPU backend; chip residency comes from "
                        "jitted calls (arrays returned by jit stay on the "
                        "chip as handles)")
        kw = {}
        if src is not None:
            kw["src"] = src
        if donate:
            kw["donate"] = donate
        if may_alias is not None:
            kw["may_alias"] = may_alias
        return originals["device_put"](x, device, **kw)

    jax.pmap = pmap_fail
    jax.devices = devices_guard
    jax.local_devices = local_devices_guard
    jax.device_put = device_put_guard
    return originals


def attach_proxy(host: str, port: int, name: str, request: float,
                 limit: float, memory: int = 0) -> None:
    """Force the CPU backend and replace ``jax.jit`` with the remote
    shim. Must run before the workload's first backend use."""
    global _active
    with _state_lock:
        if _active is not None:
            raise RuntimeError(f"already attached ({_active.mode})")
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
        import jax

        try:
            jax.config.update("jax_platforms", "cpu")
        except RuntimeError:
            pass
        shim = _ProxyShim(host, port, name, request, limit, memory)
        _keep_real_jit(jax.jit)
        jax.jit = shim.jit
        originals = _guard_proxy_surface(jax)
        _active = _AttachState("proxy", shim=shim,
                               originals=originals)
        # A zero-touch workload never calls detach(); unregister at
        # interpreter exit so the proxy drops the session immediately
        # instead of parking it (resume-capable sessions survive a dead
        # connection for the detach grace — right for a crash, wrong for
        # a clean exit). detach() is idempotent.
        atexit.register(detach)
        log.info("attached (proxy mode) to %s:%d as %s "
                 "(request=%.2f limit=%.2f)", host, port, name, request, limit)


def _meter_eager_ops(jax, gate, hbm, in_gated_jit) -> dict:
    """Close the eager-compute metering hole: a gate-mode pod owns its
    chip, so eager ``jnp`` ops and manual ``device_put`` dispatch compute
    with no gated ``jax.jit`` in the path. Every program the process runs
    — a jitted function, an eager ``jnp`` call (each is jit-wrapped
    inside JAX) or a bare primitive — is executed by ONE Python method,
    ``pxla.ExecuteReplicated.__call__``, unless ``jit``'s C++ fast path
    has cached the call and skips Python altogether. So the meter sits
    on that method, and the fast path is withheld (``_get_fastpath_data``
    answers None) from every call that is not already inside a gated
    ``jax.jit`` — the workload's own jitted steps passed the gate in
    ``gated_jit`` and keep their fast path. An eager op is then gated
    exactly like a jitted step: elapsed wall time is charged and the
    token renews (blocking, enforcing the share) when quota runs out.
    (A primitive-level hook, ``core.EvalTrace.process_primitive``, is not
    enough: an eager ``jnp`` call binds no primitive, so only bare
    ``lax`` ops would pass the meter.)
    ``device_put`` additionally pre-charges the transfer size against
    the HBM cap BEFORE the bytes land. The reference meters the whole
    CUDA driver API (hook Dockerfile:10-14); this is the JAX equivalent
    of "no device work escapes the meter". Returns restore info for
    :func:`detach`."""
    from jax._src import pjit as _pjit
    from jax._src.interpreters import pxla as _pxla

    real_call = _pxla.ExecuteReplicated.__call__
    real_fastpath = _pjit._get_fastpath_data
    in_meter = threading.local()

    def metered_call(self, *args):
        # inside a gated jit the gate was passed already; the reentrancy
        # guard keeps the gate's own completion barrier / renew from
        # recursing into the meter
        if (getattr(in_gated_jit, "on", False)
                or getattr(in_meter, "on", False)):
            return real_call(self, *args)
        in_meter.on = True
        try:
            gate()            # charge elapsed; acquire/renew (may block)
            if hbm is not None:
                hbm.maybe_check()
        finally:
            in_meter.on = False
        return real_call(self, *args)

    def fastpath_for_gated_only(*args, **kwargs):
        if getattr(in_gated_jit, "on", False):
            return real_fastpath(*args, **kwargs)
        return None           # keep this call on the metered Python path

    _pxla.ExecuteReplicated.__call__ = metered_call
    _pjit._get_fastpath_data = fastpath_for_gated_only

    real_device_put = jax.device_put

    def _leaf_on_accel(leaf) -> bool:
        try:
            return isinstance(leaf, jax.Array) and any(
                getattr(d, "platform", "").lower() in _ACCEL_PLATFORMS
                for d in leaf.devices())
        except Exception:
            return False

    def device_put_metered(x, device=None, **kw):
        # Pre-charge only what will actually LAND on the accelerator:
        # an explicit host/CPU target consumes no HBM, and leaves already
        # resident on the accel device are counted in bytes_in_use (a
        # second charge would double-count them).
        if hbm is not None and (device is None or _is_accel_device(device)):
            nbytes = sum(int(getattr(leaf, "nbytes", 0) or 0)
                         for leaf in jax.tree_util.tree_leaves(x)
                         if not _leaf_on_accel(leaf))
            if nbytes:
                hbm.check(extra_bytes=nbytes)
        return real_device_put(x, device, **kw)

    jax.device_put = device_put_metered
    return {"device_put": real_device_put,
            "_execute": (_pxla.ExecuteReplicated, "__call__", real_call),
            "_fastpath": (_pjit, "_get_fastpath_data", real_fastpath)}


def attach_gate(host: str, port: int, name: str, request: float,
                limit: float, memory: int = 0) -> None:
    """Token-gate every jitted call AND every eager op; the
    workload keeps chip ownership (whole-chip pods). ``memory`` > 0 arms
    the HBM cap: the owned device's allocator is polled at gated calls
    (and, rate-limited, at eager ops), transfers are pre-charged, and a
    breach kills the pod with an attributable error (the hook's
    allocation-time ``gpu_mem`` cap, ``pkg/scheduler/pod.go:419-424``).
    A backend with no allocator stats REFUSES to start with a mem grant
    (fail closed)."""
    global _active
    with _state_lock:
        if _active is not None:
            raise RuntimeError(f"already attached ({_active.mode})")
        from .isolation.client import ExecutionGate, HbmCap

        gate = ExecutionGate.connect(host, port, name, request, limit)
        hbm = HbmCap(memory) if memory > 0 else None
        import jax

        if hbm is not None and not os.environ.get(C.ENV_NUM_PROCESSES):
            # Startup probe: initializes the owned backend (the workload
            # would moments later anyway) and dies CLEANLY here when the
            # runtime exposes no allocator stats, instead of running
            # with tpu_mem silently unenforced.
            # GANG members skip it — jax.distributed.initialize() has not
            # run yet (attach_if_env joins the gang AFTER attach_gate),
            # and touching the backend first would wreck the rendezvous;
            # their first metered op fail-closes identically.
            hbm.check()
            log.info("HBM cap armed from the allocator's stats: "
                     "tpu_mem=%d bytes", memory)

        genuine_jit = jax.jit
        in_gated_jit = threading.local()   # the eager meter stands down

        def gated_jit(fn=None, **kw):
            if fn is None:
                return lambda f: gated_jit(f, **kw)
            jitted = genuine_jit(fn, **kw)

            def run(*args, **kwargs):
                if (_contains_tracers(args, kwargs)
                        or getattr(in_gated_jit, "on", False)):
                    # nested trace, or a gated step calling another: the
                    # outer call already passed the gate
                    return jitted(*args, **kwargs)
                gate()  # barriers the previous dispatch, charges, renews
                if hbm is not None:
                    hbm.check()  # deny the next step after a breach
                in_gated_jit.on = True
                try:
                    out = jitted(*args, **kwargs)
                finally:
                    in_gated_jit.on = False
                gate.note_dispatch(out)  # charged through completion next
                return out

            run.__wrapped__ = jitted
            return run

        _keep_real_jit(genuine_jit)
        jax.jit = gated_jit
        originals = _meter_eager_ops(jax, gate, hbm, in_gated_jit)
        _active = _AttachState("gate", gate=gate,
                               originals=originals)
        atexit.register(detach)   # release the token on clean exit
        log.info("attached (gate mode) to %s:%d as %s", host, port, name)


def _pin_visible_devices() -> bool:
    """Translate the scheduler's chip grant (``KUBESHARE_TPU_VISIBLE_CHIPS``:
    global chip ids, trailing per-host index — topology/chip.make_chip_id)
    into the variables the TPU runtime reads itself: the
    NVIDIA_VISIBLE_DEVICES equivalent, applied before jax initializes.
    Runs for EVERY attach mode — a gate-mode pod on a multi-chip host
    must not initialize chips granted to other pods. The grant never
    travels in the runtime's own TPU_VISIBLE_CHIPS: libtpu parses that
    as local indices and, given a chip id, finds no device at all."""
    chips = os.environ.get(C.ENV_VISIBLE_CHIPS, "")
    if not chips or os.environ.get("TPU_VISIBLE_DEVICES"):
        return False
    try:
        # a carved grant suffixes each chip with its mesh coord
        # ("chip@x.y", gang/carve.py) — the local index lives on the
        # chip id proper, so strip the suffix before parsing; seed-form
        # grants pass through byte-identically
        indices = [str(int(c.partition("@")[0].rsplit("-", 1)[1]))
                   for c in chips.split(",") if c]
    except (IndexError, ValueError):
        # Fail CLOSED (like _join_gang_or_die): the grant env is present
        # but unparsable, so we cannot know which chips are ours.  Falling
        # through would leave TPU_VISIBLE_DEVICES unset and initialize
        # EVERY chip on the host — including ones granted to other pods —
        # which is exactly the breach the pin exists to prevent.  Crash
        # loudly so a scheduler config bug shows up as a crash-looping pod.
        raise SystemExit(
            f"kubeshare-tpu: cannot parse local chip indices from "
            f"{C.ENV_VISIBLE_CHIPS}={chips!r}; refusing to start without "
            f"a device pin (would expose co-tenants' chips)")
    if not indices:
        raise SystemExit(
            f"kubeshare-tpu: {C.ENV_VISIBLE_CHIPS}={chips!r} parses to an "
            f"empty chip set; refusing to start without a device pin")
    # the runtime reads both names (its current one and the older one)
    # as local chip indices; give it the same answer under each
    os.environ["TPU_VISIBLE_DEVICES"] = ",".join(indices)
    os.environ["TPU_VISIBLE_CHIPS"] = ",".join(indices)
    os.environ.update(_gang_process_grid(chips))
    return True


def _gang_process_grid(chips: str) -> dict[str, str]:
    """The runtime's process-grid variables for a gang of ONE-CHIP
    processes that together hold one node's whole mesh (four members on
    a 2x2 host). The device pin alone gives each member an isolated
    one-chip runtime: ``jax.distributed`` then joins four processes that
    each see a single device, and no mesh spans them. libtpu forms one
    slice across processes only when told the grid — per-process chip
    bounds, process bounds, this process's place in it, and where its
    peers listen. Everything needed is in the binding's env: the carved
    grant carries this chip's mesh coordinate, ``KUBESHARE_TPU_NODE_MESH``
    the grid, and the peers listen on the coordinator's host at the
    ports after the coordinator's own. A gang member that holds its
    host's every chip needs none of it (the runtime sees them all), and
    gets {}; a gang carved smaller than the node is refused — its grid
    is not the node's, and a wrong grid hangs every member at start-up."""
    nproc = int(os.environ.get(C.ENV_NUM_PROCESSES, "0") or 0)
    mesh_text = os.environ.get(C.ENV_MESH_SHAPE, "")
    coord = os.environ.get(C.ENV_COORDINATOR, "")
    if nproc <= 1 or not mesh_text or not coord:
        return {}
    from .gang.carve import parse_mesh, parse_visible_chips

    entries = parse_visible_chips(chips)
    mesh = parse_mesh(mesh_text)
    node_chips = 1
    for d in mesh:
        node_chips *= d
    if len(entries) >= node_chips:
        return {}                       # whole-host member
    if len(entries) != 1 or nproc != node_chips or entries[0][1] is None:
        raise SystemExit(
            f"kubeshare-tpu: gang of {nproc} holding {len(entries)} "
            f"chip(s) each on a {mesh_text} node: only one-chip members "
            f"that together hold the whole node mesh can be joined into "
            f"one slice; refusing to start isolated one-chip runtimes")
    coords = tuple(entries[0][1]) + (0,) * (3 - len(entries[0][1]))
    bounds = tuple(mesh) + (1,) * (3 - len(mesh))
    task = coords[0] + bounds[0] * (coords[1] + bounds[1] * coords[2])
    host, _, port = coord.rpartition(":")
    base = int(port) + 1
    return {
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": ",".join(str(d) for d in bounds),
        "TPU_PROCESS_ADDRESSES": ",".join(
            f"{host}:{base + i}" for i in range(nproc)),
        "TPU_PROCESS_PORT": str(base + task),
        "CLOUD_TPU_TASK_ID": str(task),
    }


def attach_if_env() -> str:
    """Entry point for the sitecustomize shim: attach according to the
    injected env (no-op without it). Returns the mode activated
    ("proxy" | "gate" | "visible" | "") — "visible" meaning no metering
    attached, but the granted chips were pinned via TPU_VISIBLE_DEVICES
    (the whole-chip path)."""
    mode = os.environ.get(C.ENV_ATTACH_MODE, "").lower()
    if mode == "off" or _active is not None:
        return ""
    pinned = _pin_visible_devices()
    proxy_port = int(os.environ.get(C.ENV_CHIP_PROXY_PORT, "0") or 0)
    mgr_port = int(os.environ.get(C.ENV_POD_MANAGER_PORT, "0") or 0)
    if mode == "proxy" and not proxy_port:
        log.warning("attach mode 'proxy' requested but %s unset",
                    C.ENV_CHIP_PROXY_PORT)
        return ""
    if mode == "gate" and not mgr_port:
        log.warning("attach mode 'gate' requested but %s unset",
                    C.ENV_POD_MANAGER_PORT)
        return ""
    # Both endpoints are NODE-LOCAL (launcherd spawns the chip proxy and
    # the pod manager on the workload's own node, hostNetwork) — never
    # dial the cluster scheduler's IP here.
    host = os.environ.get("KUBESHARE_TPU_ATTACH_HOST", "") or "127.0.0.1"
    name = os.environ.get(C.ENV_POD_NAME, "") or f"pid-{os.getpid()}"
    request = float(os.environ.get(C.ENV_TPU_REQUEST, "0") or 0)
    limit = float(os.environ.get(C.ENV_TPU_LIMIT, "0") or 0) or max(
        request, 1.0)
    request = request or limit
    memory = int(os.environ.get(C.ENV_TPU_MEMORY, "0") or 0)
    if proxy_port and mode in ("", "proxy"):
        attach_proxy(host, proxy_port, name, request, limit, memory)
        return "proxy"
    if mgr_port and mode in ("", "gate"):
        attach_gate(host, mgr_port, name, request, limit, memory)
        # Gate-mode pods own their device, so a fractional full gang can
        # still train one SPMD model across hosts (metered by tokens).
        _join_gang_or_die()
        return "gate"
    # Whole-chip pod (no manager port — the reference's multi-GPU path,
    # pod.go:348-400): no metering to attach; the pin above confines the
    # process, and a gang member additionally joins its jax.distributed
    # runtime — zero-touch multi-host, driven by the scheduler's rank +
    # the manifest's coordinator address (parallel/runner). Proxy mode
    # deliberately does NOT join: its executions are forwarded to the
    # chip proxy, which owns the device — there is no local mesh to rank.
    if _join_gang_or_die():
        return "distributed"
    return "visible" if pinned else ""


def _join_gang_or_die() -> bool:
    """Join jax.distributed when the gang env is present. A member whose
    rendezvous FAILS must terminate rather than silently train solo — the
    rest of the gang is blocked waiting for its rank, and only a restart
    retries the rendezvous. SystemExit passes through the shim's
    never-break-the-interpreter Exception guard by design."""
    from .parallel.runner import distributed_init_from_env
    try:
        return distributed_init_from_env()
    except Exception as exc:
        log.error("gang member failed jax.distributed rendezvous: %s — "
                  "exiting so the restart can retry", exc)
        raise SystemExit(1) from exc


def detach() -> None:
    """Undo the attach (tests / graceful shutdown)."""
    global _active
    with _state_lock:
        if _active is None:
            return
        import jax

        jax.jit = real_jit()
        _keep_real_jit(None)
        for api, fn in _active.originals.items():
            if isinstance(fn, tuple):     # (owner, attr, value) restore
                owner, attr, value = fn
                setattr(owner, attr, value)
            else:
                setattr(jax, api, fn)
        if _active.shim is not None:
            _active.shim.close()
        if _active.gate is not None:
            _active.gate.close()
        _active = None


def active_mode() -> str:
    return _active.mode if _active is not None else ""


