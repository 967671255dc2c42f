"""Re-shard live training state onto a resized mesh (doc/elastic.md).

The flip half of an elastic resize is pure control plane — bookings and
the ``KUBESHARE_TPU_VISIBLE_CHIPS`` layout. This module is the data plane: while
the gang is drain-paused, every param/optimizer leaf moves from the old
:class:`~jax.sharding.NamedSharding` to the layout
:func:`~..parallel.mesh.param_sharding` assigns on the NEW mesh, by the
cheapest path that is correct for that leaf:

  * **donate** — old and new device sets identical (a pure re-layout,
    e.g. ``(dp=4, tp=1) → (dp=2, tp=2)``): a jitted identity with
    ``out_shardings`` + ``donate_argnums=0`` re-lays the shards
    device-side and frees the old buffers eagerly (SNIPPETS [1], the
    pjit donation machinery);
  * **reshard** — device sets overlap or differ (grow/shrink):
    ``jax.device_put`` onto the target sharding, letting the runtime
    move only the non-resident slices;
  * **stream** — a leaf the runtime refuses to reshard directly falls
    back to an explicit host round-trip (``np.asarray`` →
    ``device_put``), and :func:`restate_via_checkpoint` is the
    last-resort serialization path through ``models/checkpoint.py``.

Optimizer slots (momentum/adam moments) mirror param shapes, so the
same per-leaf rule shards them; scalar counts and empty optax states
replicate. Nothing here touches the step counter or the batch
schedule — zero lost steps is the caller's invariant to keep, this
module only guarantees the state that comes out equals the state that
went in, re-laid.
"""

from __future__ import annotations

import jax
import numpy as np

from ..parallel.mesh import param_sharding

__all__ = ["restate_tree", "restate_state", "restate_via_checkpoint"]


def _new_stats() -> dict:
    return {"donated": 0, "resharded": 0, "streamed": 0,
            "bytes_donated": 0, "bytes_resharded": 0,
            "bytes_streamed": 0}


def _leaf_devices(x) -> frozenset:
    sharding = getattr(x, "sharding", None)
    devs = getattr(sharding, "device_set", None)
    return frozenset(devs) if devs else frozenset()


def _relay_leaf(x, sharding, mesh_devices: frozenset, stats: dict):
    nbytes = int(getattr(x, "nbytes", 0) or 0)
    old = _leaf_devices(x)
    if old and old == mesh_devices:
        # pure re-layout: same chips, new partitioning — donate so the
        # old shards free as the new ones materialize (no 2x HBM spike)
        relay = jax.jit(lambda a: a, out_shardings=sharding,
                        donate_argnums=0)
        out = relay(x)
        stats["donated"] += 1
        stats["bytes_donated"] += nbytes
        return out
    try:
        out = jax.device_put(x, sharding)
        stats["resharded"] += 1
        stats["bytes_resharded"] += nbytes
        return out
    except (ValueError, TypeError):
        host = np.asarray(x)
        stats["streamed"] += 1
        stats["bytes_streamed"] += int(host.nbytes)
        return jax.device_put(host, sharding)


def restate_tree(tree, new_mesh, stats: dict | None = None):
    """Re-lay one pytree onto *new_mesh* per the
    :func:`~..parallel.mesh.param_sharding` rule. Returns
    ``(tree, stats)``; empty trees (optax ``EmptyState``) pass through
    untouched."""
    stats = _new_stats() if stats is None else stats
    shardings = param_sharding(new_mesh, tree)
    mesh_devices = frozenset(new_mesh.devices.flat)
    out = jax.tree_util.tree_map(
        lambda x, s: _relay_leaf(x, s, mesh_devices, stats),
        tree, shardings)
    return out, stats


def restate_state(params, opt_state, new_mesh):
    """Re-shard a full training state — ``(params, opt_state,
    stats)`` — onto *new_mesh*. The two trees share one stats dict so
    the caller journals a single donated/resharded/streamed tally."""
    stats = _new_stats()
    params, _ = restate_tree(params, new_mesh, stats)
    opt_state, _ = restate_tree(opt_state, new_mesh, stats)
    return params, opt_state, stats


def restate_via_checkpoint(path: str, params, opt_state, new_mesh,
                           step: int = 0):
    """Fallback serialization path: round-trip the state through
    ``models/checkpoint.py`` and re-lay the loaded host copies onto
    *new_mesh*. Slow (full host round-trip + disk) but shape-agnostic —
    the escape hatch when the runtime cannot reshard in place. Returns
    ``(params, opt_state, step)`` already on the new mesh."""
    from ..models.checkpoint import load_checkpoint, save_checkpoint

    save_checkpoint(path, params, opt_state, step)
    params, opt_state, step = load_checkpoint(path, params, opt_state)
    params = jax.device_put(params, param_sharding(new_mesh, params))
    opt_state = jax.device_put(opt_state,
                               param_sharding(new_mesh, opt_state))
    return params, opt_state, step
