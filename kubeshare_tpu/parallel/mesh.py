"""Device-mesh and sharding helpers for multi-chip workloads.

The reference delegates multi-device execution to the workload (torch DDP +
NCCL env in ``test/distribute/default/2gpu/resnet50_1.yaml:30-35``); the
TPU-native equivalent is SPMD over a ``jax.sharding.Mesh``: annotate
shardings, let XLA insert the collectives over ICI/DCN. These helpers build
the mesh from the chips a gang was *placed on* by the scheduler, closing
the placement → execution loop.
"""

from __future__ import annotations

import math
from typing import Callable

import jax
import numpy as np
import optax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def _default_tp(n: int) -> int:
    """Largest power-of-two ≤ √n that divides n — a square-ish split that
    keeps tensor-parallel collectives on near-neighbor ICI links."""
    tp = 1 << (int(math.isqrt(n)).bit_length() - 1) if n > 1 else 1
    while n % tp:
        tp //= 2
    return tp


def make_mesh(devices=None, dp: int | None = None, tp: int | None = None) -> Mesh:
    """Build a 2D ``(dp, tp)`` mesh over *devices* (default: all).

    With neither axis given, tp gets the largest power-of-two ≤ √n and dp
    the rest — a square-ish default that keeps tensor-parallel collectives
    on near-neighbor ICI links.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    for name, axis in (("dp", dp), ("tp", tp)):
        if axis is not None and axis <= 0:
            raise ValueError(f"{name} must be positive, got {axis}")
    if dp is None and tp is None:
        tp = _default_tp(n)
        dp = n // tp
    elif dp is None:
        dp = n // tp
    elif tp is None:
        tp = n // dp
    if dp * tp != n:
        raise ValueError(f"dp*tp = {dp}*{tp} != device count {n}")
    return Mesh(np.array(devices).reshape(dp, tp), ("dp", "tp"))


def data_sharding(mesh: Mesh) -> NamedSharding:
    """Batch arrays: split along the leading axis over every data axis the
    mesh has (``dcn`` and/or ``dp``), replicated over tp."""
    if "dcn" in mesh.axis_names:
        return NamedSharding(mesh, P(("dcn", "dp")))
    return NamedSharding(mesh, P("dp"))


def token_sharding(mesh: Mesh) -> NamedSharding:
    """Token batches (batch, seq): batch over every data axis (dcn and/or
    dp), sequence over sp when the mesh has a sequence axis — the
    long-context layout ring attention consumes
    (``parallel.ringattention``)."""
    batch_axes = (("dcn", "dp") if "dcn" in mesh.axis_names else "dp")
    if "sp" in mesh.axis_names:
        return NamedSharding(mesh, P(batch_axes, "sp"))
    return NamedSharding(mesh, P(batch_axes))


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def param_sharding(mesh: Mesh, params):
    """Tensor-parallel parameter layout: a pytree of :class:`NamedSharding`
    mirroring ``params``. Matrices (ndim ≥ 2) are split on their last axis
    over tp when divisible (dense/conv output channels — the MXU-friendly
    Megatron-style column split); everything else is replicated."""
    tp = mesh.shape["tp"]

    def shard_leaf(x):
        if getattr(x, "ndim", 0) >= 2 and x.shape[-1] % tp == 0 and x.shape[-1] >= tp:
            spec = [None] * (x.ndim - 1) + ["tp"]
            return NamedSharding(mesh, P(*spec))
        return NamedSharding(mesh, P())

    return jax.tree_util.tree_map(shard_leaf, params)


def make_sharded_train_step(loss_fn: Callable, optimizer, mesh: Mesh,
                            batch_sharding: NamedSharding | None = None):
    """Jit a train step that *enforces* the mesh layout: the batch is
    constrained to ``batch_sharding`` (default :func:`data_sharding`;
    pass :func:`token_sharding`'s result for sequence-split token
    batches) and params to :func:`param_sharding` on the way in and out,
    so the layout holds even for host-resident inputs. XLA inserts the
    psum for dp gradient reduction and the tp collectives from the
    shardings. One step body with the single-chip path
    (``models.common.make_train_step``)."""
    from ..models.common import make_train_step

    if batch_sharding is None:
        batch_sharding = data_sharding(mesh)

    def constrain_params(params):
        return jax.lax.with_sharding_constraint(params, param_sharding(mesh, params))

    def constrain_batch(batch):
        return jax.lax.with_sharding_constraint(batch, batch_sharding)

    return make_train_step(loss_fn, optimizer,
                           constrain_params=constrain_params,
                           constrain_batch=constrain_batch)


def shard_init(init_fn: Callable, key, mesh: Mesh):
    """Initialize params already laid out per :func:`param_sharding`
    (device_put after host init — fine at these model sizes; big models
    would jit the init with out_shardings)."""
    params = init_fn(key)
    shardings = param_sharding(mesh, params)
    return jax.device_put(params, shardings)


def make_carved_mesh(carve: str, devices=None,
                     mesh_shape: str | tuple[int, ...] | None = None) -> Mesh:
    """Build the gang's 2D ``(dp, tp)`` mesh from a carved
    ``KUBESHARE_TPU_VISIBLE_CHIPS`` value (``"chip@x.y,..."``, doc/gang.md).

    The carve is validated against the planned sub-mesh block first —
    ``mesh_shape`` is the node mesh (``constants.ENV_MESH_SHAPE``, e.g.
    ``"2x4"``) so wrap-around blocks validate; a non-contiguous carve
    (the greedy-compact fallback's scatter picks, or a corrupted env)
    raises :class:`~kubeshare_tpu.gang.carve.CarveError` rather than
    silently building a mesh whose collectives hop off ICI.

    ``devices`` defaults to ``jax.devices()`` and is laid onto the block
    in row-major coordinate order, one device per carved chip, so
    position in the mesh mirrors position on the torus. 1-D carves get
    a ``(1, n)`` mesh; 2-D carves map block rows → dp, columns → tp.
    The result feeds :class:`~jax.sharding.NamedSharding` exactly like
    :func:`make_mesh` output.
    """
    from ..gang.carve import CarveError, carve_block, parse_mesh, parse_visible_chips

    entries = parse_visible_chips(carve)
    mesh = None
    if mesh_shape:
        mesh = parse_mesh(mesh_shape) if isinstance(mesh_shape, str) \
            else tuple(mesh_shape)
    origin, shape = carve_block(entries, mesh=mesh)
    n = len(entries)
    devices = list(devices if devices is not None else jax.devices())
    if len(devices) < n:
        raise CarveError(
            f"carve names {n} chips but only {len(devices)} devices "
            f"are visible")
    devices = devices[:n]
    if len(shape) == 1:
        dp, tp = 1, shape[0]
    else:
        dp = shape[0]
        tp = n // shape[0]
    # order devices by the carve's block position (devices[i] is the
    # runtime device behind entries[i] — TPU_VISIBLE_DEVICES preserves
    # the carve's entry order) so mesh neighbors are torus neighbors
    def block_pos(c):
        pos = []
        for axis, (v, o) in enumerate(zip(c, origin)):
            d = v - o
            if mesh is not None:
                d %= mesh[axis]
            pos.append(d)
        return tuple(pos)

    order = sorted(range(n), key=lambda i: block_pos(entries[i][1]))
    devices = [devices[i] for i in order]
    return Mesh(np.array(devices).reshape(dp, tp), ("dp", "tp"))


def make_hybrid_mesh(device_slices, tp: int | None = None) -> Mesh:
    """Mesh spanning MULTIPLE slices: axes ``(dcn, dp, tp)``.

    ``device_slices``: list of per-slice device lists (e.g. grouped by the
    ``slice_id`` discovery reports). The ``dcn`` axis crosses slice
    boundaries — only data-parallel gradient reductions ride it — while
    ``dp``/``tp`` stay inside a slice, so tensor-parallel collectives
    (all-gather/reduce-scatter per layer) never leave ICI. This is the
    standard two-tier layout for multi-host scale-out: DCN is orders of
    magnitude slower than ICI, so the mesh puts the once-per-step psum
    there and nothing else.

    All slices must be the same size (the gang scheduler's contiguous
    whole-slice allocation guarantees this for placed workloads).
    """
    sizes = {len(d) for d in device_slices}
    if len(sizes) != 1:
        raise ValueError(f"slices must be equal-sized, got {sorted(sizes)}")
    per = sizes.pop()
    if per == 0:
        raise ValueError("empty slices")
    if tp is None:
        tp = _default_tp(per)
    elif tp <= 0:
        raise ValueError(f"tp must be positive, got {tp}")
    if per % tp:
        raise ValueError(f"tp={tp} does not divide slice size {per}")
    dp = per // tp
    arr = np.array([list(d) for d in device_slices], dtype=object)
    return Mesh(arr.reshape(len(device_slices), dp, tp),
                ("dcn", "dp", "tp"))


