"""Sub-mesh carving: the scheduler's ``select_submesh`` block rendered
into the ``KUBESHARE_TPU_VISIBLE_CHIPS`` env contract and parsed back.

Wire format (backward compatible): each comma-separated entry is either
the seed form ``chip_id`` or the carved form ``chip_id@x.y`` where the
``@``-suffix is the cell's mesh coordinate, dot-joined, normalised to
the node's mesh origin (``meshselect.node_mesh_shape``). Consumers that
predate carving (the attach shim's local-index parse) strip the suffix
and see the seed string; carve-aware consumers recover the exact planned
block and can rebuild the gang's device mesh from it.

Because ``select_block`` places blocks on a *torus*, a carve may wrap an
axis (coords ``{0, 3}`` on a 4-wide ring are adjacent). Validating that
a carve is the contiguous block the scheduler planned therefore needs
the node mesh shape, carried separately in ``KUBESHARE_TPU_NODE_MESH``
(``constants.ENV_MESH_SHAPE``, e.g. ``"2x4"``) — overloading the chip
list itself would break the seed parser's fail-closed contract.
"""

from __future__ import annotations

from math import prod

__all__ = [
    "CarveError", "carve_env", "parse_visible_chips", "strip_carve",
    "carve_block", "block_coords", "format_mesh", "parse_mesh",
]


class CarveError(ValueError):
    """The carve string is malformed or not a contiguous sub-mesh block."""


def format_mesh(shape) -> str:
    """``(2, 4)`` → ``"2x4"`` (the ENV_MESH_SHAPE payload)."""
    return "x".join(str(int(d)) for d in shape)


def parse_mesh(text: str) -> tuple[int, ...]:
    try:
        shape = tuple(int(d) for d in text.strip().split("x"))
    except ValueError:
        raise CarveError(f"bad mesh shape {text!r}") from None
    if not shape or any(d <= 0 for d in shape):
        raise CarveError(f"bad mesh shape {text!r}")
    return shape


def carve_env(chip_ids, coords_list) -> str:
    """Render chip ids + their mesh coords into the KUBESHARE_TPU_VISIBLE_CHIPS
    value. ``coords_list`` entries may be ``None``/empty (chips without
    topology coords fall back to the seed form)."""
    if len(chip_ids) != len(coords_list):
        raise CarveError("chip_ids and coords_list length mismatch")
    parts = []
    for chip, coords in zip(chip_ids, coords_list):
        if "," in chip or "@" in chip:
            raise CarveError(f"chip id {chip!r} not carvable")
        if coords:
            parts.append(chip + "@" + ".".join(str(int(c)) for c in coords))
        else:
            parts.append(chip)
    return ",".join(parts)


def parse_visible_chips(env: str) -> list[tuple[str, tuple[int, ...] | None]]:
    """Parse a KUBESHARE_TPU_VISIBLE_CHIPS value into ``[(chip_id, coords|None)]``.
    Seed-form entries parse with ``coords=None``."""
    out: list[tuple[str, tuple[int, ...] | None]] = []
    for entry in env.split(","):
        entry = entry.strip()
        if not entry:
            continue
        chip, sep, suffix = entry.partition("@")
        if not chip:
            raise CarveError(f"bad carve entry {entry!r}")
        if not sep:
            out.append((chip, None))
            continue
        try:
            coords = tuple(int(c) for c in suffix.split("."))
        except ValueError:
            raise CarveError(f"bad carve entry {entry!r}") from None
        out.append((chip, coords))
    return out


def strip_carve(env: str) -> str:
    """Drop any ``@x.y`` carve suffixes, returning the seed-format chip
    list (what carve-unaware consumers should see)."""
    return ",".join(e.partition("@")[0] for e in env.split(",") if e)


def _axis_interval(vals: list[int], extent_limit: int | None) -> tuple[int, int]:
    # vals sorted unique; returns (origin, extent) of the axis interval,
    # cyclic when extent_limit (the torus axis size) is given.
    k = len(vals)
    if extent_limit is None:
        if vals[-1] - vals[0] + 1 != k:
            raise CarveError(f"axis values {vals} not contiguous")
        return vals[0], k
    if vals[0] < 0 or vals[-1] >= extent_limit:
        raise CarveError(f"axis values {vals} outside mesh axis "
                         f"of size {extent_limit}")
    if k == extent_limit:
        return 0, k
    if vals[-1] - vals[0] + 1 == k:          # plain interval, no wrap
        return vals[0], k
    # wrapped interval iff the complement is one contiguous run
    present = set(vals)
    gaps = [v for v in range(extent_limit) if v not in present]
    if gaps[-1] - gaps[0] + 1 != len(gaps):
        raise CarveError(f"axis values {vals} not a cyclic interval "
                         f"on axis of size {extent_limit}")
    return (gaps[-1] + 1) % extent_limit, k


def carve_block(entries, mesh: tuple[int, ...] | None = None
                ) -> tuple[tuple[int, ...], tuple[int, ...]]:
    """Validate that carved ``entries`` (``parse_visible_chips`` output,
    or ``(chip, coords)`` pairs) form exactly one axis-aligned block and
    return ``(origin, shape)`` — the same convention as
    ``meshselect.node_mesh_shape``. With ``mesh`` given the block may
    wrap the torus (``select_block`` places wrapped blocks); without it
    only plain intervals validate. Raises :class:`CarveError` on
    anything else — notably the greedy-compact fallback's scatter picks.
    """
    coords = []
    for chip, c in entries:
        if c is None:
            raise CarveError(f"chip {chip!r} carries no carve coords")
        coords.append(tuple(c))
    if not coords:
        raise CarveError("empty carve")
    ndim = len(coords[0])
    if any(len(c) != ndim for c in coords):
        raise CarveError("mixed coord dimensionality")
    if mesh is not None and len(mesh) != ndim:
        raise CarveError(f"mesh rank {len(mesh)} != coord rank {ndim}")
    if len(set(coords)) != len(coords):
        raise CarveError("duplicate coords in carve")
    origin, shape = [], []
    for axis in range(ndim):
        vals = sorted({c[axis] for c in coords})
        o, e = _axis_interval(vals, mesh[axis] if mesh else None)
        origin.append(o)
        shape.append(e)
    # per-axis intervals + distinct coords + count == volume ⇒ the coord
    # set IS the block (every coord lies inside it and it has no holes)
    if len(coords) != prod(shape):
        raise CarveError(f"{len(coords)} chips do not fill a "
                         f"{'x'.join(map(str, shape))} block")
    return tuple(origin), tuple(shape)


def block_coords(origin: tuple[int, ...], shape: tuple[int, ...],
                 mesh: tuple[int, ...] | None = None) -> list[tuple[int, ...]]:
    """Enumerate the block's coords in row-major order (torus wrap when
    ``mesh`` is given) — the order ``make_carved_mesh`` lays devices in."""
    coords = [()]
    for axis, extent in enumerate(shape):
        nxt = []
        for prefix in coords:
            for step in range(extent):
                v = origin[axis] + step
                if mesh is not None:
                    v %= mesh[axis]
                nxt.append(prefix + (v,))
        coords = nxt
    return coords
