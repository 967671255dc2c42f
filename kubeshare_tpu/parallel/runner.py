"""Multi-host gang runner: binding env → ``jax.distributed`` → mesh.

Closes the placement → multi-host execution loop. The scheduler injects
each gang member's identity (``KUBESHARE_TPU_NUM_PROCESSES`` /
``KUBESHARE_TPU_PROCESS_ID`` — unique dense ranks assigned at Reserve,
``engine.reserve``); the manifest wires ``KUBESHARE_TPU_COORDINATOR`` to
rank 0 (e.g. a headless service). This module turns those into an
initialized JAX distributed runtime and a gang-wide mesh — the TPU-native
equivalent of the reference's torchelastic WORLD_SIZE/RANK + etcd
rendezvous (``test/distribute/default/2gpu/resnet50_1.yaml``), with XLA
collectives over ICI/DCN instead of NCCL.

Typical gang workload::

    from kubeshare_tpu.parallel import runner
    runner.distributed_init_from_env()     # no-op off-gang
    mesh = runner.gang_mesh()              # all chips of the gang
    ...

Works on CPU too (gloo backend) — the tests run real multi-process
rendezvous with virtual devices.
"""

from __future__ import annotations

import contextlib
import math
import os
import time

from .. import constants as C
from ..obs import metrics as obs_metrics
from ..obs.trace import get_tracer
from ..utils.logger import get_logger

log = get_logger("runner")

_initialized = False

_STEP_LAT = obs_metrics.default_registry().histogram(
    "kubeshare_runner_step_seconds",
    "Wall time of one training/eval step in the gang runner.",
    labels=("phase",))


@contextlib.contextmanager
def step_timer(phase: str = "train", trace_id: str = "", step: int = -1):
    """Time one step's wall clock into ``kubeshare_runner_step_seconds``.

    ``phase`` labels the histogram series (train/eval/compile/...);
    kept to a handful of static values — never interpolate step numbers
    into it. With a ``trace_id`` (e.g. ``KUBESHARE_TPU_TRACE_ID`` injected
    at bind) each step also lands as a ``step`` span on the pod's
    timeline, so per-step stalls line up against token grant-waits.
    """
    t0 = time.perf_counter()
    ts0 = get_tracer().now_ms()
    try:
        yield
    finally:
        dt = time.perf_counter() - t0
        _STEP_LAT.observe(phase, value=dt)
        if trace_id:
            tracer = get_tracer()
            attrs = {"phase": phase}
            if step >= 0:
                attrs["step"] = step
            tracer.record("step", trace_id, ts0, tracer.now_ms(), **attrs)


def timed_range(n: int, phase: str = "train", trace_id: str = ""):
    """``range(n)`` that times each iteration as one step.

    Drop-in for a training loop's ``for step in range(n)`` — every
    iteration's wall time is observed under ``phase``::

        for step in runner.timed_range(num_steps):
            state = train_step(state, batch)
    """
    for i in range(n):
        with step_timer(phase, trace_id=trace_id, step=i):
            yield i


def distributed_init_from_env(env: dict | None = None) -> bool:
    """Initialize ``jax.distributed`` from the injected gang env.

    Returns True when running as a gang member (env present and
    initialization happened / already done); False for solo processes —
    callers need no branching, ``gang_mesh`` works either way.
    """
    global _initialized
    env = os.environ if env is None else env
    coord = env.get(C.ENV_COORDINATOR, "")
    nproc = env.get(C.ENV_NUM_PROCESSES, "")
    rank = env.get(C.ENV_PROCESS_ID, "")
    if not (coord and nproc and rank):
        return False
    if _initialized:
        return True
    import jax

    from ..utils.compilecache import enable_compile_cache
    enable_compile_cache()   # every member compiles the same gang step
    kwargs = {}
    timeout_s = env.get(C.ENV_RENDEZVOUS_TIMEOUT_S, "")
    if timeout_s:
        # Bound the wait for a missing coordinator; on expiry initialize
        # raises and the attach shim exits the member so a restart
        # retries (instead of blocking jax's multi-minute default). A
        # malformed value is a config typo, not a rendezvous failure —
        # warn and use the default rather than crash-loop the pod.
        try:
            kwargs["initialization_timeout"] = int(float(timeout_s))
        except ValueError:
            log.warning("ignoring malformed %s=%r",
                        C.ENV_RENDEZVOUS_TIMEOUT_S, timeout_s)
    jax.distributed.initialize(coordinator_address=coord,
                               num_processes=int(nproc),
                               process_id=int(rank), **kwargs)
    _initialized = True
    log.info("joined gang %s as process %s/%s via %s",
             env.get(C.ENV_GROUP_NAME, "?"), rank, nproc, coord)
    return True


def gang_mesh(dp: int | None = None, tp: int | None = None,
              hybrid: bool | None = None):
    """Mesh over every device the gang sees (global across processes).

    ``KUBESHARE_TPU_MESH`` (e.g. ``"dp=2,sp=2,tp=2"``) overrides
    everything: the manifest names the axes and sizes, the runner builds
    exactly that mesh — the hook long-context workloads use to get an
    ``sp`` axis for ring attention without touching code.

    Otherwise ``hybrid=None`` auto-selects: a two-tier ``(dcn, dp, tp)``
    mesh when the gang spans multiple ICI slices (distinct device
    ``slice_index``), else a flat ``(dp, tp)`` mesh — a single slice's
    ICI spans hosts, so multi-process alone does not warrant a DCN tier.
    ``hybrid=True`` forces the two-tier layout, grouping by slice when
    slices differ and by process otherwise (hosts linked only by plain
    network — the CPU-simulation case, and clusters without inter-host
    ICI).
    """
    import jax

    from .mesh import make_hybrid_mesh, make_mesh

    devices = jax.devices()

    spec = os.environ.get("KUBESHARE_TPU_MESH", "")
    if spec:
        import numpy as np
        from jax.sharding import Mesh
        if dp is not None or tp is not None or hybrid is not None:
            raise ValueError(
                "gang_mesh received explicit dp/tp/hybrid arguments but "
                f"KUBESHARE_TPU_MESH={spec!r} is set — remove one; the "
                "env override would silently win otherwise")
        axes = []
        for part in spec.split(","):
            name, _, size = part.partition("=")
            try:
                axes.append((name.strip(), int(size)))
            except ValueError:
                raise ValueError(f"bad KUBESHARE_TPU_MESH entry {part!r} "
                                 "(want name=int)") from None
        names = [n for n, _ in axes]
        # The sharding helpers (param_sharding/data_sharding/
        # make_sharded_train_step) require dp and tp axes; reject here
        # with a clear message instead of a KeyError deep inside the
        # jitted step. Axes you don't want simply get size 1.
        for required in ("dp", "tp"):
            if required not in names:
                raise ValueError(
                    f"KUBESHARE_TPU_MESH {spec!r} must name a {required!r} "
                    f"axis (use {required}=1 to disable it)")
        total = math.prod(s for _, s in axes)
        if total != len(devices):
            raise ValueError(
                f"KUBESHARE_TPU_MESH {spec!r} wants {total} devices, gang "
                f"has {len(devices)}")
        return Mesh(np.array(devices).reshape([s for _, s in axes]),
                    tuple(names))

    by_slice: dict = {}
    for d in devices:
        by_slice.setdefault(getattr(d, "slice_index", 0), []).append(d)
    if hybrid is None:
        hybrid = len(by_slice) > 1
    if not hybrid:
        return make_mesh(devices, dp=dp, tp=tp)
    if dp is not None:
        raise ValueError(
            "dp is derived per slice on hybrid meshes (slice_size // tp); "
            "pass tp instead")
    groups = by_slice
    if len(groups) <= 1:
        groups = {}
        for d in devices:
            groups.setdefault(d.process_index, []).append(d)
    if len(groups) <= 1:
        return make_mesh(devices, dp=dp, tp=tp)
    return make_hybrid_mesh([groups[k] for k in sorted(groups)], tp=tp)
