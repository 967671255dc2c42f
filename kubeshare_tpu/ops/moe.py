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
# no tile and is never written). So the tail of every buffer between
# :func:`dispatch` and :func:`combine` holds whatever the memory held:
# nothing reads it but elementwise passes whose tail nobody reads, and the
# two ends select by ``here``, never multiply by a zero weight.
# Differentiable: the products through :func:`grouped_matmul`'s custom VJP
# (two grouped products back), the two row permutations through custom
# VJPs whose way back is a gather as the way there, never a scatter.

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


def _rows_of(buffer, r: Routing, choice: int):
    """Each token's row of its ``choice``-th pair, zero where the pair's
    expert is not held (its row is in the tail nobody wrote)."""
    rows = jnp.take(buffer, r.position[:, choice], axis=0)
    return jnp.where(r.here[:, choice, None], rows.astype(jnp.float32), 0.0)


@jax.custom_vjp
def dispatch(x: jax.Array, r: Routing) -> jax.Array:
    """(tokens, dim) -> the sorted buffer (tokens x top_k, dim): row ``i``
    is the token of pair ``r.order[i]``. Back: each token sums the rows
    of its held pairs (a gather a choice)."""
    return jnp.take(x, r.order // r.position.shape[1], axis=0)


def _dispatch_fwd(x, r):
    return dispatch(x, r), r


def _dispatch_bwd(r, g):
    back = sum(_rows_of(g, r, j) for j in range(r.position.shape[1]))
    return back.astype(g.dtype), None


dispatch.defvjp(_dispatch_fwd, _dispatch_bwd)


@jax.custom_vjp
def combine(ys: jax.Array, weights: jax.Array, r: Routing) -> jax.Array:
    """The sorted buffer's results back in token order, each token's held
    rows summed by ``weights`` (float32): (tokens, dim). ``weights`` is
    ``r.weights``, passed apart because the router's gradient comes back
    through it."""
    return sum(weights[:, j, None] * _rows_of(ys, r, j)
               for j in range(weights.shape[1]))


def _combine_fwd(ys, weights, r):
    return combine(ys, weights, r), (ys, weights, r)


def _combine_bwd(res, g):
    ys, weights, r = res
    top_k = weights.shape[1]
    # a row's cotangent is its token's, by its pair's weight: zero in the
    # tail, where the weight is zero and ``g`` is real data
    by_row = jnp.take(weights.reshape(-1), r.order)
    d_ys = (jnp.take(g, r.order // top_k, axis=0)
            * by_row[:, None]).astype(ys.dtype)
    d_w = jnp.stack([(_rows_of(ys, r, j) * g).sum(-1)
                     for j in range(top_k)], axis=1)
    return d_ys, d_w, None


combine.defvjp(_combine_fwd, _combine_bwd)


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
    flat = x.reshape(b * s, d)
    with jax.named_scope(scopes[0]):
        r = topk_route(params, flat, top_k, first_held, scaling)
        xs = dispatch(flat, r)
    with jax.named_scope(scopes[1]):
        ys = expert_ffn(params, xs, r.group_sizes, dtype=dtype)
    with jax.named_scope(scopes[0]):
        out = combine(ys, r.weights, r)
    return out.astype(x.dtype).reshape(b, s, d)
