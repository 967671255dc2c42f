"""Mixture-of-experts FFN with expert parallelism over an ``ep`` mesh axis.

The reference has no model math at all (its workloads are external torch
images); the TPU build carries expert parallelism as a first-class
sharding kind. Design is the dense capacity-based dispatch (Mesh-
TensorFlow / Switch style), TPU-first throughout:

- Routing, dispatch and combine are EINSUMS over one-hot tensors — no
  gather/scatter, no ragged shapes; everything lands on the MXU and jits
  with static shapes.
- The expert stacks carry a leading ``E`` axis; sharding that axis over
  ``ep`` (:func:`expert_sharding`) makes XLA insert the all-to-all pair
  around the per-expert matmuls — the canonical EP communication pattern,
  expressed as a layout instead of hand-written collectives.
- Over-capacity tokens are dropped (their FFN output is zero); with the
  residual connection in a transformer block they pass through unchanged
  — the standard Switch trade for static shapes.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .kernelcall import kernel_call


def moe_init(key, dim: int, hidden: int, n_experts: int) -> dict:
    kr, kf, kp = jax.random.split(key, 3)
    scale_in = math.sqrt(1.0 / dim)
    scale_hid = math.sqrt(1.0 / hidden)
    return {
        "router": jax.random.uniform(kr, (dim, n_experts), jnp.float32,
                                     -scale_in, scale_in),
        "fc": jax.random.uniform(kf, (n_experts, dim, hidden), jnp.float32,
                                 -scale_in, scale_in),
        "proj": jax.random.uniform(kp, (n_experts, hidden, dim), jnp.float32,
                                   -scale_hid, scale_hid),
    }


def moe_apply(params: dict, x: jax.Array, capacity_factor: float = 1.25,
              group_size: int = 2048, dtype=None
              ) -> tuple[jax.Array, jax.Array]:
    """Top-1 routed MoE FFN. ``x``: (batch, seq, dim) → (same shape,
    aux_loss).

    Tokens are routed within GROUPS of ≤ ``group_size`` with per-group
    capacity (Mesh-TF style): the dense dispatch tensor is
    (g, m, E, C) with m·C ≈ capacity_factor·m²/E per group — linear in
    total tokens instead of the quadratic (n, E, cf·n/E) a single global
    group costs (1.3 GB per layer at 16k tokens).

    ``aux_loss`` is the Switch load-balancing loss (mean PRE-drop token
    fraction × mean router probability per expert, scaled by E): computed
    before the capacity drop, so a collapsed router scores ~E and keeps
    its gradient pressure even when experts overflow.
    """
    b, s, d = x.shape
    n = b * s
    e = params["router"].shape[1]
    # Largest divisor of n with quotient ≤ group_size: groups must tile
    # the token stream exactly (static shapes, no padding).
    g = next(g for g in range(max(1, -(-n // group_size)), n + 1)
             if n % g == 0)
    m = n // g
    cap = max(1, int(capacity_factor * m / e))
    router, fc, proj = params["router"], params["fc"], params["proj"]
    if dtype is not None:
        x, fc, proj = x.astype(dtype), fc.astype(dtype), proj.astype(dtype)

    tokens = x.reshape(g, m, d)
    # Router in fp32: tiny matmul, and softmax/argmax in bf16 misroutes.
    logits = jnp.einsum("gmd,de->gme", tokens.astype(jnp.float32), router)
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)                     # (g, m)
    gate = jnp.take_along_axis(probs, expert[..., None], axis=-1)[..., 0]

    assigned = jax.nn.one_hot(expert, e, dtype=jnp.float32)  # (g, m, E)
    # Position of each token within its expert's per-group buffer, via
    # cumsum — static shapes, no sort (Switch-style).
    pos = (jnp.cumsum(assigned, axis=1) - 1.0) * assigned    # (g, m, E)
    keep = pos < cap
    onehot = assigned * keep                                 # drop overflow
    posoh = jax.nn.one_hot(
        pos.sum(axis=-1).astype(jnp.int32), cap, dtype=jnp.float32)
    # dispatch[g, m, e, c] = 1 iff group-g token m sits in slot c of
    # expert e's buffer for that group
    dispatch = onehot[..., None] * posoh[:, :, None, :]      # (g, m, E, C)

    expert_in = jnp.einsum("gmec,gmd->gecd",
                           dispatch.astype(tokens.dtype), tokens)
    h = jax.nn.gelu(jnp.einsum("gecd,edh->gech", expert_in, fc))
    expert_out = jnp.einsum("gech,ehd->gecd", h, proj)       # (g, E, C, d)
    combine = dispatch * gate[..., None, None].astype(jnp.float32)
    out = jnp.einsum("gmec,gecd->gmd", combine.astype(expert_out.dtype),
                     expert_out)

    # Switch aux loss from the PRE-drop assignment. fp32 accumulation.
    frac_tokens = assigned.mean(axis=(0, 1))                 # (E,)
    frac_probs = probs.mean(axis=(0, 1))                     # (E,)
    aux = (frac_tokens * frac_probs).sum() * e

    return out.reshape(b, s, d), aux


def expert_sharding(mesh: Mesh, params: dict) -> dict:
    """Shard the expert stacks' leading E axis over ``ep`` (router
    replicated). Applying this layout (device_put at init +
    with_sharding_constraint in the step) is ALL the expert parallelism
    there is — XLA derives the all-to-all around the expert matmuls."""
    if "ep" not in mesh.axis_names:
        raise ValueError(f"mesh {mesh.axis_names} has no 'ep' axis")
    return {
        "router": NamedSharding(mesh, P()),
        "fc": NamedSharding(mesh, P("ep", None, None)),
        "proj": NamedSharding(mesh, P("ep", None, None)),
    }


# --- top-k routed experts, dropless, for a stated share of the experts -------
#
# The layer today's sparse decoders have (sigmoid scores, a selection
# bias, top-k of all published experts, weights normalised over the k),
# told WHICH experts it holds: under expert parallelism a chip holds
# ``held`` consecutive experts from ``first_held`` and computes their part
# of the result for the tokens routed to them; what the absent experts
# would add is left out (their chips add it). Nothing is dropped: the
# (token, expert) pairs are sorted by expert into a buffer of static size
# ``tokens x top_k`` (every pair fits, whatever the skew) with the pairs
# of experts not held here sorted last, and the expert products are
# GROUPED matrix products over that buffer (megablox's Pallas kernels,
# whose grid follows the rows present: the tail of pairs not held costs
# no tile and is never written), and so are the row moves into and out of
# it (the ``moe_rows`` kernels below). So the tail of every buffer between
# :func:`dispatch` and :func:`combine` holds whatever the memory held:
# nothing reads it but elementwise passes whose tail nobody reads, and the
# two ends select by ``here``, never multiply by a zero weight.
# Differentiable: the products through :func:`grouped_matmul`'s custom VJP
# (two grouped products back), the two row permutations through custom
# VJPs whose way back is the other kind of move, a gather as the way
# there, never a scatter of rows.

#: Rows of the sorted buffer, and columns of the contraction and of the
#: output, that one grid step of a grouped product aims to work on
#: (bfloat16 operands, float32 accumulator: ~10 MiB of VMEM double
#: buffered, inside what Mosaic grants unasked on a v5e).
GMM_TILE_ROWS, GMM_TILE_COLS = 512, 1024


def topk_moe_init(key, dim: int, hidden: int, n_experts: int,
                  held: int | None = None) -> dict:
    """The router over ALL ``n_experts``, the selection bias (zero; no
    gradient reaches it, the published recipe's update of it is not part
    of a model's config), and the ``held`` experts' gated MLPs stacked on
    a leading axis."""
    held = n_experts if held is None else held
    kr, k1, k3, k2 = jax.random.split(key, 4)
    s_in, s_hid = math.sqrt(1.0 / dim), math.sqrt(1.0 / hidden)

    def u(k, shape, s):
        return jax.random.uniform(k, shape, jnp.float32, -s, s)

    return {"router": u(kr, (dim, n_experts), s_in),
            "expert_bias": jnp.zeros((n_experts,)),
            "w1": u(k1, (held, dim, hidden), s_in),
            "w3": u(k3, (held, dim, hidden), s_in),
            "w2": u(k2, (held, hidden, dim), s_hid)}


class Routing(NamedTuple):
    """Where each (token, choice) pair sits in the sorted buffer."""

    order: jax.Array        # (tokens x top_k,) row -> flat pair index
    position: jax.Array     # (tokens, top_k) pair -> row
    here: jax.Array         # (tokens, top_k) bool: the expert is held
    weights: jax.Array      # (tokens, top_k) float32, 0 where not here
    group_sizes: jax.Array  # (held,) int32: rows of each held expert


def topk_route(params: dict, x: jax.Array, top_k: int, first_held: int = 0,
               scaling: float = 1.0) -> Routing:
    """``x``: (tokens, dim). Scores and selection in float32 over all
    experts: ``s = sigmoid(x W_r)``, ``sel = top_k(s + bias)`` (the bias
    selects and never weights), ``w = s[sel] / (sum s[sel] + 1e-6)``.
    The flat pair ``token * top_k + choice`` of held expert 0 come first
    in ``order``, then held expert 1's, ..., last the pairs of experts
    not held; ``group_sizes`` counts the held ones only (their sum is the
    number of rows that anything reads)."""
    held = params["w1"].shape[0]
    scores = jax.nn.sigmoid(jnp.einsum(
        "nd,de->ne", x.astype(jnp.float32), params["router"],
        precision=jax.lax.Precision.HIGHEST))
    _, sel = jax.lax.top_k(
        scores + jax.lax.stop_gradient(params["expert_bias"]), top_k)
    # the picked scores by a one-hot product, not a gather: its transpose
    # is elementwise where a gather's is a scatter of tokens x top_k scalars
    chosen = sel[..., None] == jnp.arange(scores.shape[-1])
    picked = jnp.where(chosen, scores[:, None, :], 0.0).sum(-1)
    weights = picked / (picked.sum(-1, keepdims=True) + 1e-6) * scaling
    local = sel - first_held
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(-1).astype(jnp.int32)
    order = jnp.argsort(group, stable=True).astype(jnp.int32)
    position = jnp.argsort(order).astype(jnp.int32).reshape(sel.shape)
    group_sizes = (group[:, None] == jnp.arange(held)).sum(0,
                                                           dtype=jnp.int32)
    return Routing(order, position, here, jnp.where(here, weights, 0.0),
                   group_sizes)


# --- the row moves: ``moe_rows``, two Pallas kernels -------------------------
#
# A DMA moves whole (8, 128) tiles of a 2-D array (16 rows of a bfloat16
# one), never one row of it, and a row of a 2-D block in VMEM sits one
# sublane deep in as many registers as it has lane blocks. So the kernels
# hold rows as (lane blocks, 128) tiles on a leading axis of their own
# (``_tiles``: a row is whole registers and is read or written at any
# index) and turn a 2-D block into that form, or back, in VMEM. Tokens come
# in multiples of ``BAND``, past 1,024 of 1,024 (:func:`topk_moe_apply`
# pads), so every DMA below moves whole tiles, and what a block needs of
# the row and pair indices reaches scalar memory 1,024 entries at a time.
#
# - Sorted side (:func:`_sorted_rows`: ``dispatch``, ``combine``'s way
#   back): the source has a row per TOKEN. Grid steps first bring the
#   chunks of tokens that hold a pair kept here into VMEM (a chunk with
#   none is not read), then walk the buffer in blocks up to
#   ``sum(group_sizes)`` (the grid ends there) and copy each of those
#   rows from its token. Where the tokens' rows outgrow ``RESIDENT_BYTES``
#   (or, on the way back, their pairs ``SMEM_PAIRS``), XLA's row gathers
#   do the move instead, reading every pair.
# - Token side (:func:`_token_rows`: ``combine``, ``dispatch``'s way
#   back): the source is the sorted buffer. A block of tokens' rows of
#   held expert ``g`` are one contiguous run of it (each expert's rows are
#   in token order), so a grid step DMAs the tiles that cover each run of
#   one block, packed one run after another, while it sums the block
#   before, each token's rows in the order of its choices: a pair not held
#   here is never read, and a token with none gets exact zeros.

#: VMEM the sorted side keeps every token's row in, at most
RESIDENT_BYTES = 64 << 20
#: Tokens the sorted side brings into VMEM a grid step, at most
CHUNK_TOKENS = 512
#: Pairs whose weights and dots ``combine``'s way back keeps in scalar
#: memory (1 MiB on a v5e), at most
SMEM_PAIRS = 1 << 16
#: Rows of the sorted buffer a token-side DMA moves: a bfloat16 tile's
#: height, and what the tokens are padded to a multiple of
BAND = 16


def _row_shape(d: int) -> tuple[int, int]:
    return (d // 128, 128) if d % 128 == 0 else (1, d)


def _tiles(v: jax.Array) -> jax.Array:
    """(rows, d) -> (rows, *_row_shape(d)): a relayout in VMEM."""
    return v.reshape(v.shape[0], *_row_shape(v.shape[-1]))


def _vmem_limit(nbytes: int) -> int:
    return int(min(nbytes * 1.25 + (8 << 20), 120 << 20))


def _smem_blocks(n: int, size: int, index):
    """A 1-D operand of ``n`` entries in scalar memory, of which grid step
    ``s`` reads ``[index(s) * size, + size)``: its spec, and where in the
    block that step's entries start. XLA tiles such an array by 1,024
    entries, so a block is the 1,024 around them (or the whole array)."""
    whole = 1024 if n % 1024 == 0 and 1024 % size == 0 else (
        size if size % 1024 == 0 else n)
    return (pl.BlockSpec((whole,), lambda s, *_: (index(s) * size // whole,),
                         memory_space=pltpu.SMEM),
            lambda s: index(s) * size % whole)


def _sorted_rows(src, r: Routing, back=None, block: int = 256):
    """The sorted buffer (tokens x top_k, d) from ``src`` (tokens, d): row
    ``i < sum(r.group_sizes)`` is the row of the token of pair ``p =
    r.order[i]``; the rows beyond hold whatever the memory held. ``back =
    (weights, ys)`` makes it ``combine``'s way back: each row times
    ``weights.flat[p]`` in float32, cast to ``ys``'s dtype, and besides it
    ``dots[p]``, the row's dot with ``ys``'s row in float32, for each pair
    ``p`` held (a (tokens x top_k,) array, in pair order; the others hold
    whatever the memory held)."""
    t, d = src.shape
    rows, top_k = r.order.shape[0], r.here.shape[1]
    if t * d * src.dtype.itemsize > RESIDENT_BYTES or (
            back is not None and rows > SMEM_PAIRS):
        return _sorted_rows_by_take(src, r, back)
    block, chunk = _tile(rows, block, 16), _tile(t, CHUNK_TOKENS, 16)
    chunks = t // chunk
    count = r.group_sizes.sum(keepdims=True)
    dtype = jnp.dtype(src.dtype if back is None else back[1].dtype)
    tile = _row_shape(d)
    # the chunk each step of the first phase fetches: the last wanted one
    # so far (the pipeline fetches a block only when its index changes),
    # the first wanted one before any
    hit = r.here.any(-1).reshape(chunks, chunk).any(-1)
    last = jax.lax.cummax(jnp.where(hit, jnp.arange(chunks), -1))
    fetch = jnp.where(last >= 0, last, jnp.argmax(hit)).astype(jnp.int32)
    steps = chunks + (count[0] + block - 1) // block

    def blk(s):
        return jnp.maximum(s - chunks, 0)

    def buf_map(s, *_):
        return blk(s), 0

    def src_map(s, count_ref, fetch_ref):
        return fetch_ref[jnp.minimum(s, chunks - 1)], 0

    names = ["src", "pair"] + ["weight", "ys"] * (back is not None) + [
        "out"] + ["dots"] * (back is not None) + ["resident", "picked"] + [
        "theirs", "part", "row_v", "row_s", "sem"] * (back is not None)
    pair_spec, pair_at = _smem_blocks(rows, block, blk)
    in_specs = [pl.BlockSpec((chunk, d), src_map), pair_spec]
    operands = [src, r.order]
    scratch = [pltpu.VMEM((t, *tile), src.dtype),
               pltpu.VMEM((block, *tile), dtype)]
    out_specs = [pl.BlockSpec((block, d), buf_map)]
    out_shape = [jax.ShapeDtypeStruct((rows, d), dtype)]
    nbytes = (t + 2 * chunk) * d * src.dtype.itemsize + \
        3 * block * d * dtype.itemsize
    if back is not None:
        # the weights and dots in pair order, whole: a row's pair is
        # anywhere
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM),
                     pl.BlockSpec((block, d), buf_map)]
        operands += [back[0].reshape(-1).astype(jnp.float32), back[1]]
        out_specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        out_shape.append(jax.ShapeDtypeStruct((rows,), jnp.float32))
        scratch += [pltpu.VMEM((block, *tile), dtype),
                    pltpu.VMEM((block, tile[1]), jnp.float32),
                    pltpu.VMEM((block,), jnp.float32),
                    pltpu.SMEM((block,), jnp.float32),
                    pltpu.SemaphoreType.DMA(())]
        nbytes += block * d * 3 * dtype.itemsize + block * 1024

    def kernel(count_ref, fetch_ref, *refs):
        ref = dict(zip(names, refs))
        picked, s = ref["picked"], pl.program_id(0)

        @pl.when((s < chunks) & (fetch_ref[jnp.minimum(s, chunks - 1)] == s))
        def _():
            ref["resident"][pl.ds(pl.multiple_of(s * chunk, chunk),
                                  chunk)] = _tiles(ref["src"][...])

        @pl.when(s >= chunks)
        def _():
            if back is not None:
                ref["theirs"][...] = _tiles(ref["ys"][...])
            first = pair_at(s)

            def row(i, c):
                pair = ref["pair"][first + i]
                v = ref["resident"][pair // top_k]
                if back is not None:
                    v = v.astype(jnp.float32)
                    ref["part"][pl.ds(i, 1), :] = jnp.sum(
                        ref["theirs"][i].astype(jnp.float32) * v, axis=0,
                        keepdims=True)
                    v = v * ref["weight"][pair]
                picked[i] = v.astype(dtype)
                return c

            present = jnp.minimum(count_ref[0] - (s - chunks) * block, block)
            jax.lax.fori_loop(0, present, row, 0)
            ref["out"][...] = picked[...].reshape(block, d)
            if back is not None:
                # the block's dots as one row, through scalar memory to
                # their pairs' places (a vector's sum read one scalar at a
                # time costs ~100 ns a row)
                col = jnp.sum(ref["part"][...], axis=1, keepdims=True)
                ref["row_v"][...] = jnp.transpose(
                    jnp.broadcast_to(col, (block, 128)))[0]
                copy = pltpu.make_async_copy(ref["row_v"], ref["row_s"],
                                             ref["sem"])
                copy.start()
                copy.wait()

                def place(i, c):
                    ref["dots"][ref["pair"][first + i]] = ref["row_s"][i]
                    return c

                jax.lax.fori_loop(0, present, place, 0)

    def make(interpret):
        return pl.pallas_call(
            kernel, out_shape=out_shape, name="moe_rows",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=2, grid=(steps,), in_specs=in_specs,
                out_specs=out_specs, scratch_shapes=scratch),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_limit(nbytes)),
            interpret=interpret)

    out = kernel_call(make, count, fetch, *operands)
    return out[0] if back is None else tuple(out)


def _sorted_rows_by_take(src, r: Routing, back=None):
    """:func:`_sorted_rows` by XLA's row gathers, which read every pair:
    where the tokens' rows outgrow what one kernel call keeps in VMEM, or
    their pairs' weights and dots the scalar memory."""
    top_k = r.here.shape[1]
    rows = jnp.take(src, r.order // top_k, axis=0)
    if back is None:
        return rows
    weights, ys = back
    by_row = jnp.take(weights.reshape(-1).astype(jnp.float32), r.order)
    dots = jnp.stack([(jnp.take(ys, r.position[:, j], axis=0)
                       .astype(jnp.float32) * src).sum(-1)
                      for j in range(top_k)], 1)
    return (rows * by_row[:, None]).astype(ys.dtype), dots.reshape(-1)


def _token_rows(src, r: Routing, weights=None, dtype=jnp.float32,
                block: int = 256):
    """(tokens, d): each token's rows of ``src`` (the sorted buffer) summed
    over its held choices in their order, in float32, each by its weight
    where ``weights`` is given, cast to ``dtype``; exact zeros for a token
    none of whose choices is held. Rows of pairs not held are not read."""
    rows, d = src.shape
    tokens, top_k = r.here.shape
    held = r.group_sizes.shape[0]
    block = _tile(tokens, block, 8)
    blocks = tokens // block
    tile = _row_shape(d)
    # each held pair's expert, from the row it sits at; a block's rows of
    # expert g are the run [lo, lo + n) of the buffer, staged from the band
    # at or before lo, after the block's runs of the experts before g (a
    # token's choices are distinct experts: at most a block's rows a run)
    ends = jnp.cumsum(r.group_sizes)
    expert = (r.position[..., None] >= ends).sum(-1)
    mine = (expert[..., None] == jnp.arange(held)) & r.here[..., None]
    n = mine.sum(1, dtype=jnp.int32).reshape(blocks, block, held).sum(1)
    lo = ends - r.group_sizes + jnp.cumsum(n, 0) - n
    first = lo // BAND * BAND
    bands = jnp.where(n > 0, (lo + n - first + BAND - 1) // BAND, 0)
    to = jnp.repeat((jnp.cumsum(bands, 1) - bands) * BAND - first, block, 0)
    where = jnp.where(r.here, jnp.where(mine, to[:, None, :], 0).sum(-1)
                      + r.position, -1)
    # a run is staged in at most its rows and two bands less two
    staged = BAND * -(-(block * top_k + min(held, block * top_k)
                        * (2 * BAND - 2)) // BAND)
    plan = jnp.stack([first, bands], -1).reshape(-1).astype(jnp.int32)
    narrow = jnp.dtype(dtype) != jnp.float32
    prior = lambda s: jnp.maximum(s - 1, 0)        # noqa: E731
    names = ["src", "where"] + ["weight"] * (weights is not None) + [
        "present", "out", "staged", "picked", "sem"] + ["total"] * narrow
    operands = [where.reshape(-1).astype(jnp.int32)]
    if weights is not None:
        operands.append(weights.reshape(-1).astype(jnp.float32))
    operands.append(r.here.sum(-1, dtype=jnp.int32))

    pair_spec, pair_at = _smem_blocks(tokens * top_k, block * top_k, prior)
    token_spec, token_at = _smem_blocks(tokens, block, prior)

    def kernel(plan_ref, *refs):
        ref = dict(zip(names, refs))
        staged_v, picked, sem = ref["staged"], ref["picked"], ref["sem"]
        # a float32 result is summed where it is written
        total = ref["total"] if narrow else ref["out"]
        # step s stages block s and sums block s - 1, which step s - 1
        # staged into the other half of ``staged``
        s = pl.program_id(0)

        def runs(blk, each):
            """``each(buffer rows, staged rows)`` for each band of block
            ``blk``'s runs, in order."""
            def expert(g, to):
                at = 2 * (blk * held + g)

                def one(q, c):
                    each(pl.ds(pl.multiple_of(plan_ref[at] + q * BAND, BAND),
                               BAND),
                         pl.ds(pl.multiple_of(to + q * BAND, BAND), BAND))
                    return c

                jax.lax.fori_loop(0, plan_ref[at + 1], one, 0)
                return to + plan_ref[at + 1] * BAND

            jax.lax.fori_loop(0, held, expert, 0)

        def copy(slot, at, to):
            return pltpu.make_async_copy(ref["src"].at[at],
                                         staged_v.at[slot, to], sem.at[slot])

        @pl.when(s < blocks)
        def _():
            runs(s, lambda at, to: copy(s % 2, at, to).start())

        @pl.when(s > 0)
        def _():
            slot = (s - 1) % 2

            # every band's DMA has landed before any is read: the slot's
            # semaphore counts bytes, not which copy they came from
            runs(s - 1, lambda at, to: copy(slot, at, to).wait())

            def relayout(at, to):
                picked[to] = _tiles(staged_v[slot, to])

            runs(s - 1, relayout)
            total[...] = jnp.zeros_like(total)

            pairs, tokens_at = pair_at(s), token_at(s)

            def token(i, c):
                @pl.when(ref["present"][tokens_at + i] > 0)
                def _():
                    acc = jnp.zeros(tile, jnp.float32)
                    for j in range(top_k):
                        at = pairs + i * top_k + j
                        k = ref["where"][at]
                        row = picked[jnp.maximum(k, 0)].astype(jnp.float32)
                        if weights is not None:
                            row = ref["weight"][at] * row
                        acc = jnp.where(k >= 0, acc + row, acc)
                    total[pl.ds(i, 1), :] = acc.reshape(1, d)

                return c

            jax.lax.fori_loop(0, block, token, 0)
            if narrow:
                ref["out"][...] = total[...].astype(dtype)

    nbytes = (3 * staged * src.dtype.itemsize + 4 * block * narrow
              + 2 * block * jnp.dtype(dtype).itemsize) * d

    def make(interpret):
        return pl.pallas_call(
            kernel, out_shape=jax.ShapeDtypeStruct((tokens, d), dtype),
            name="moe_rows",
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1, grid=(blocks + 1,),
                in_specs=[pl.BlockSpec(memory_space=pl.ANY)]
                + [pair_spec] * (len(operands) - 1) + [token_spec],
                out_specs=pl.BlockSpec((block, d),
                                       lambda s, *_: (prior(s), 0)),
                scratch_shapes=[
                    pltpu.VMEM((2, staged, d), src.dtype),
                    pltpu.VMEM((staged, *tile), src.dtype),
                    pltpu.SemaphoreType.DMA((2,))]
                + [pltpu.VMEM((block, d), jnp.float32)] * narrow),
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_vmem_limit(nbytes)),
            interpret=interpret)

    return kernel_call(make, plan, src, *operands)


@jax.custom_vjp
def dispatch(x: jax.Array, r: Routing) -> jax.Array:
    """(tokens, dim) -> the sorted buffer (tokens x top_k, dim): row ``i <
    sum(r.group_sizes)`` is the token of pair ``r.order[i]``; the rows
    beyond hold whatever the memory held. Back: each token sums the rows
    of its held pairs. Tokens come as :func:`_padded` leaves them."""
    return _sorted_rows(x, r)


def _dispatch_fwd(x, r):
    return dispatch(x, r), r


def _dispatch_bwd(r, g):
    return _token_rows(g, r, dtype=g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(ys: jax.Array, weights: jax.Array, r: Routing) -> jax.Array:
    """The sorted buffer's results back in token order, each token's held
    rows summed by ``weights`` (float32): (tokens, dim). ``weights`` is
    ``r.weights``, passed apart because the router's gradient comes back
    through it."""
    return _token_rows(ys, r, weights)


def _combine_fwd(ys, weights, r):
    return combine(ys, weights, r), (ys, weights, r)


def _combine_bwd(res, g):
    ys, weights, r = res
    # a held row's cotangent is its token's, by its pair's weight; the
    # weight's, its row against its token's cotangent
    d_ys, dots = _sorted_rows(g, r, (weights, ys))
    d_w = jnp.where(r.here, dots.reshape(r.here.shape), 0.0)
    return d_ys, d_w, None


combine.defvjp(_combine_fwd, _combine_bwd)


def _padded(x: jax.Array, r: Routing) -> tuple[jax.Array, Routing]:
    """``x`` and ``r`` with tokens added, to a multiple of ``BAND`` (of
    1,024 past 1,024 tokens): each added pair is held nowhere and sorts
    last, so it costs no row and no read."""
    tokens, top_k = r.here.shape
    pad = -tokens % (BAND if tokens <= 1024 else 1024)
    if not pad:
        return x, r
    added = tokens * top_k + jnp.arange(pad * top_k, dtype=jnp.int32)
    more = ((0, pad), (0, 0))
    return jnp.pad(x, more), Routing(
        jnp.concatenate([r.order, added]),
        jnp.concatenate([r.position, added.reshape(pad, top_k)]),
        jnp.pad(r.here, more), jnp.pad(r.weights, more), r.group_sizes)


def _tile(n: int, target: int, step: int) -> int:
    """``n`` whole when it is short enough (always a legal block), else
    its largest divisor up to ``target`` that is a multiple of ``step``."""
    if n <= target:
        return n
    for tile in range(target - target % step, 0, -step):
        if n % tile == 0:
            return tile
    raise ValueError(f"no tile of {n} that is a multiple of {step}")


def _tiling(m: int, k: int, n: int) -> tuple[int, int, int]:
    return (_tile(m, GMM_TILE_ROWS, 8), _tile(k, GMM_TILE_COLS, 128),
            _tile(n, GMM_TILE_COLS, 128))


def _gmm(lhs, rhs, group_sizes, transpose_rhs: bool):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    n = rhs.shape[1] if transpose_rhs else rhs.shape[2]
    return kernel_call(lambda interp: functools.partial(
        gmm, preferred_element_type=lhs.dtype,
        tiling=_tiling(lhs.shape[0], lhs.shape[1], n),
        transpose_rhs=transpose_rhs, interpret=interp),
        lhs, rhs, group_sizes)


def _tgmm(lhs, grad, group_sizes, dtype):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import tgmm
    return kernel_call(lambda interp: functools.partial(
        tgmm, preferred_element_type=dtype,
        tiling=_tiling(lhs.shape[0], lhs.shape[1], grad.shape[1]),
        interpret=interp), lhs.swapaxes(0, 1), grad, group_sizes)


@jax.custom_vjp
def grouped_matmul(lhs, rhs, group_sizes):
    """``out[rows of group g] = lhs[rows of group g] @ rhs[g]``, the
    operands in ``lhs``'s dtype (``rhs`` is cast to it here, so that its
    gradient comes back in ITS dtype, float32 from the accumulator).
    ``lhs``: (rows, k) sorted by group; ``rhs``: (groups, k, n);
    ``group_sizes``: (groups,) int32, whose sum may stay under ``rows``:
    the rows beyond it cost no tile, are not read, and come back
    UNWRITTEN, here and in ``lhs``'s gradient."""
    return _gmm(lhs, rhs.astype(lhs.dtype), group_sizes, False)


def _grouped_fwd(lhs, rhs, group_sizes):
    return grouped_matmul(lhs, rhs, group_sizes), (lhs, rhs, group_sizes)


def _grouped_bwd(res, g):
    lhs, rhs, group_sizes = res
    g = g.astype(lhs.dtype)
    return (_gmm(g, rhs.astype(lhs.dtype), group_sizes, True),
            _tgmm(lhs, g, group_sizes, rhs.dtype), None)


grouped_matmul.defvjp(_grouped_fwd, _grouped_bwd)


def expert_ffn(params: dict, xs: jax.Array, group_sizes: jax.Array,
               dtype=None) -> jax.Array:
    """``(silu(x W1_e) * (x W3_e)) W2_e`` over the sorted buffer, three
    grouped products in ``dtype``; the gate is float32 between them."""
    if dtype is not None:
        xs = xs.astype(dtype)
    gate = jax.nn.silu(grouped_matmul(xs, params["w1"], group_sizes)
                       .astype(jnp.float32))
    carry = grouped_matmul(xs, params["w3"], group_sizes).astype(jnp.float32)
    return grouped_matmul((gate * carry).astype(xs.dtype), params["w2"],
                          group_sizes)


def topk_moe_apply(params: dict, x: jax.Array, top_k: int,
                   first_held: int = 0, scaling: float = 1.0, dtype=None,
                   scopes: tuple[str, str] = ("moe_route", "moe_experts")
                   ) -> jax.Array:
    """``x``: (batch, seq, dim) -> this share's part of the expert layer's
    result, same shape (zero for a token none of whose choices is held
    here). ``scopes`` name the two halves for a trace: routing (scores,
    top-k, sort, gather, combine) and the grouped products."""
    b, s, d = x.shape
    with jax.named_scope(scopes[0]):
        flat, r = _padded(x.reshape(b * s, d), topk_route(
            params, x.reshape(b * s, d), top_k, first_held, scaling))
        xs = dispatch(flat, r)
    with jax.named_scope(scopes[1]):
        ys = expert_ffn(params, xs, r.group_sizes, dtype=dtype)
    with jax.named_scope(scopes[0]):
        out = combine(ys, r.weights, r)[:b * s]
    return out.astype(x.dtype).reshape(b, s, d)
