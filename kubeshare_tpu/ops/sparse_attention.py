"""Block-sparse causal attention with a learned-free, score-driven choice of
key blocks (the InfLLM-V2 scheme of the MiniCPM4 family), in two stages.

**Selection** (:func:`select_blocks`, an XLA program). Keys are pooled into
overlapping windows (``kc_j`` = mean of ``k[j*stride : j*stride +
kernel_size]``); query ``t`` sees window ``j`` only once the window has
ENDED (``j*stride + kernel_size - 1 <= t``), so nothing after ``t`` leaks
into its choice. Per query head ``p_h = softmax_j(q . kc_j * scale)`` over
the visible windows, summed over the heads of a kv group; a block of
``block_size`` keys scores the max over the windows that overlap it. The
first ``init_blocks`` blocks and the ``window_blocks`` blocks ending at the
query's own are always taken; the ``topk`` blocks of highest score, those
counted among them, are the query's keys. One choice a (query, kv group),
shared by the group's heads. Queries are worked in blocks so that the
per-head scores (seq x heads x windows, float32) never exist whole. The
choice leaves as a bit table, 32 blocks an int32 word.

**Attention over the chosen blocks** (:func:`chosen_blocks_attention`, a
Pallas TPU kernel). Grid (batch x kv groups, q tiles, k tiles), the k tiles
innermost; a step is handed the q tile of ALL heads of one group as a lane
block of the model's own (batch, seq, heads x head_dim) array, and the
group's one k / v head. The (q tile x k tile) mask (causal, and the bit of
the key's block in the query's word) is made ONCE a step and serves every
head of the group; each head then runs the online-softmax update against
it. Tiles above the diagonal are predicated off and fetch nothing. The
kernel visits every tile at or below the diagonal: what it saves over dense
attention is nothing yet (a tile is skipped only where causality empties
it), what it computes is exactly softmax over the chosen keys. A kernel
that gathers a query's blocks is open work (PERF.md section 7).

Softmax, masks and accumulators float32; q, k, v meet the MXU in their own
dtype. Forward only.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.realjit import real_jit
from .attention import MASK_VALUE, kv_groups
from .kernelcall import kernel_call

_LANES = 128
_WORD = 32
_NT = (((1,), (1,)), ((), ()))
_NN = (((1,), (0,)), ((), ()))

#: q rows and keys of a grid step of :func:`chosen_blocks_attention`. Swept
#: on a v5e at 32 query heads on 2 kv heads of 128, bfloat16 (PERF.md, PR
#: 34), 16,384 / 32,768 tokens: 128 x 512 34.2 / 130.7 ms, 256 x 512 29.4 /
#: 112.0, 512 x 512 30.6 / 116.4, 256 x 1024 21.2 / 78.8.
TILE_Q, TILE_K = 256, 1024
#: queries a step of the selection scores at once.
SELECT_ROWS = 1024


def pooled_keys(k: jax.Array, kernel_size: int, stride: int) -> jax.Array:
    """(batch, seq, kv_heads, d) -> (batch, windows, kv_heads, d) float32:
    the mean of every ``kernel_size`` keys, ``stride`` apart."""
    b, s, hk, d = k.shape
    if kernel_size % stride or s % stride or s < kernel_size:
        raise ValueError(f"kernel_size {kernel_size} and seq {s} must be "
                         f"multiples of stride {stride}, seq >= kernel_size")
    parts = k.astype(jnp.float32).reshape(b, s // stride, stride, hk, d).sum(2)
    per = kernel_size // stride
    n = s // stride - per + 1
    return sum(parts[:, i:i + n] for i in range(per)) / kernel_size


def _choose(q, kc, t0, *, n_blocks, kernel_size, stride, block_size,
            init_blocks, window_blocks, topk, scale):
    """The chosen blocks of one block of queries, (batch, kv_heads, rows,
    n_blocks) bool. ``q``: (batch, rows, kv_heads, group, d) at positions
    ``t0 + arange(rows)``; ``kc``: (batch, windows, kv_heads, d)."""
    rows, n_c = q.shape[1], kc.shape[1]
    t = t0 + jnp.arange(rows)
    sc = jnp.einsum("bqkgd,bjkd->bkgqj", q, kc.astype(q.dtype),
                    preferred_element_type=jnp.float32) * scale
    seen = (jnp.arange(n_c) * stride + kernel_size - 1)[None, :] <= t[:, None]
    sc = jnp.where(seen, sc, MASK_VALUE)
    p = jnp.exp(sc - sc.max(-1, keepdims=True))
    p = jnp.where(seen, p, 0.0)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    group = p.sum(2)                                  # (b, hk, rows, n_c)
    # a block's score: the max over the windows that overlap it
    per, ratio = kernel_size // stride, block_size // stride
    left = per - 1
    right = ratio * n_blocks - n_c
    padded = jnp.pad(group, ((0, 0), (0, 0), (0, 0), (left, right)))
    score = jax.lax.reduce_window(
        padded, -jnp.inf, jax.lax.max, (1, 1, 1, ratio + per - 1),
        (1, 1, 1, ratio), "VALID")                    # (b, hk, rows, blocks)
    blk = jnp.arange(n_blocks)[None, :]
    own = (t // block_size)[:, None]
    forced = (blk < init_blocks) | ((blk > own - window_blocks)
                                    & (blk <= own))
    score = jnp.where(forced & (blk <= own), jnp.inf,
                      jnp.where(blk <= own, score, -1.0))
    top, idx = jax.lax.top_k(score, min(topk, n_blocks))
    hit = (idx[..., None] == jnp.arange(n_blocks)) & (top[..., None] >= 0.0)
    return hit.any(-2)


def _pack(chosen: jax.Array) -> jax.Array:
    """(..., n_blocks) bool -> (..., words) int32, bit ``b % 32`` of word
    ``b // 32``."""
    n = chosen.shape[-1]
    pad = -n % _WORD
    if pad:
        chosen = jnp.pad(chosen, [(0, 0)] * (chosen.ndim - 1) + [(0, pad)])
    bits = chosen.reshape(*chosen.shape[:-1], -1, _WORD).astype(jnp.uint32)
    words = (bits << jnp.arange(_WORD, dtype=jnp.uint32)).sum(
        -1, dtype=jnp.uint32)
    return jax.lax.bitcast_convert_type(words, jnp.int32)


def unpack(words: jax.Array, n_blocks: int) -> jax.Array:
    """The bit table back as (..., n_blocks) bool (tests, the reference's
    side of a comparison)."""
    w = jax.lax.bitcast_convert_type(words, jnp.uint32)
    bits = (w[..., None] >> jnp.arange(_WORD, dtype=jnp.uint32)) & 1
    return bits.reshape(*w.shape[:-1], -1)[..., :n_blocks].astype(bool)


def select_blocks(q: jax.Array, k: jax.Array, *, kernel_size: int,
                  stride: int, block_size: int, init_blocks: int,
                  window_blocks: int, topk: int,
                  rows: int | None = None) -> jax.Array:
    """``q``: (batch, seq, heads, d), ``k``: (batch, seq, kv_heads, d) ->
    the chosen blocks as bits, (batch, kv_heads, seq, words) int32
    (:func:`unpack` reads them). ``rows``: queries scored at once (default
    :data:`SELECT_ROWS`; the sequence where that does not divide it)."""
    b, s, h, d = q.shape
    hk = k.shape[2]
    g = kv_groups(h, hk)
    if s % block_size or block_size % stride:
        raise ValueError(f"seq {s} must be whole blocks of {block_size}, "
                         f"a block whole strides of {stride}")
    rows = SELECT_ROWS if rows is None else rows
    if s % rows:
        rows = s
    kc = pooled_keys(k, kernel_size, stride)
    choose = functools.partial(
        _choose, n_blocks=s // block_size, kernel_size=kernel_size,
        stride=stride, block_size=block_size, init_blocks=init_blocks,
        window_blocks=window_blocks, topk=topk, scale=1.0 / math.sqrt(d))
    qb = q.reshape(b, s // rows, rows, hk, g, d).transpose(1, 0, 2, 3, 4, 5)
    starts = jnp.arange(s // rows) * rows
    words = jax.lax.map(lambda a: _pack(choose(a[0], kc, a[1])),
                        (qb, starts))                 # (n, b, hk, rows, w)
    return words.transpose(1, 2, 0, 3, 4).reshape(b, hk, s, -1)


# -- attention over the chosen blocks -----------------------------------------

def _kernel(bits_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
            tq, tk, group, d, block_size, scale, n_k):
    j, kk = pl.program_id(1), pl.program_id(2)
    last = jnp.minimum(((j + 1) * tq - 1) // tk, n_k - 1)

    @pl.when(kk == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    @pl.when(kk <= last)
    def _tile():
        qpos = j * tq + jax.lax.broadcasted_iota(jnp.int32, (tq, 1), 0)
        kpos = kk * tk + jax.lax.broadcasted_iota(jnp.int32, (1, tk), 1)
        # the key's block in the query's bit table: the word by a one-hot
        # sum over the row's words (a tile's blocks lie in one word), the
        # bit by a shift that varies along the keys
        words = bits_ref[0]
        widx = jax.lax.broadcasted_iota(jnp.int32, words.shape, 1)
        word = jnp.sum(jnp.where(widx == (kk * tk // block_size) // _WORD,
                                 words, 0), axis=1, keepdims=True)
        bit = (kpos // block_size) % _WORD
        live = ((jax.lax.shift_right_logical(
            jnp.broadcast_to(word, (tq, tk)),
            jnp.broadcast_to(bit, (tq, tk))) & 1) == 1) & (kpos <= qpos)
        kb, vb = k_ref[0], v_ref[0]
        for h in range(group):
            sc = jax.lax.dot_general(q_ref[0, :, h * d:(h + 1) * d], kb, _NT,
                                     preferred_element_type=jnp.float32)
            sc = jnp.where(live, sc * scale, MASK_VALUE)
            m_old = m_ref[h]
            m = jnp.maximum(m_old, sc.max(axis=-1, keepdims=True))
            alpha = jnp.exp(m_old - m)
            p = jnp.where(live, jnp.exp(sc - m), 0.0)
            m_ref[h] = m
            l_ref[h] = l_ref[h] * alpha + p.sum(axis=-1, keepdims=True)
            pv = jax.lax.dot_general(p.astype(vb.dtype), vb, _NN,
                                     preferred_element_type=jnp.float32)
            lanes = slice(h * d, (h + 1) * d)
            acc_ref[:, lanes] = acc_ref[:, lanes] * alpha + pv

    @pl.when(kk == n_k - 1)
    def _finish():
        for h in range(group):
            lanes = slice(h * d, (h + 1) * d)
            o_ref[0, :, lanes] = (acc_ref[:, lanes] / l_ref[h]).astype(
                o_ref.dtype)


@functools.partial(real_jit(), static_argnames=(
    "block_size", "scale", "tile_q", "tile_k", "interpret"))
def _attend(q, k, v, bits, block_size, scale, tile_q, tile_k, interpret):
    b, s, h, d = q.shape
    hk = k.shape[2]
    g = h // hk
    tq, tk = tile_q, tile_k
    n_q, n_k = s // tq, s // tk
    words = bits.shape[-1]

    def kv_at(i, j, kk):
        # a tile above the diagonal is not worked: keep the last block that
        # is, and nothing is fetched for it
        return i // hk, jnp.minimum(kk, ((j + 1) * tq - 1) // tk), i % hk

    qspec = pl.BlockSpec((1, tq, g * d), lambda i, j, kk: (i // hk, j,
                                                           i % hk))
    kspec = pl.BlockSpec((1, tk, d), kv_at)
    # blocks two deep (q and the float32 o, k, v, the words), the
    # accumulator, the lane-padded running max and sum, a few score tiles
    need = (2 * (tq * g * d * (q.dtype.itemsize + 4) + 2 * tk * d
                 * k.dtype.itemsize + tq * _LANES * 4)
            + tq * g * d * 4 + 2 * g * tq * _LANES * 4
            + 8 * tq * tk * 4)
    out = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_kernel, tq=tq, tk=tk, group=g, d=d,
                          block_size=block_size, scale=scale, n_k=n_k),
        grid=(b * hk, n_q, n_k),
        in_specs=[pl.BlockSpec((1, tq, words), lambda i, j, kk: (i, j, 0)),
                  qspec, kspec, kspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b, s, h * d), jnp.float32),
        scratch_shapes=[pltpu.VMEM((g, tq, 1), jnp.float32),
                        pltpu.VMEM((g, tq, 1), jnp.float32),
                        pltpu.VMEM((tq, g * d), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
            vmem_limit_bytes=max(2 * need, 16 * 2 ** 20)),
        interpret=interp, name="chosen_blocks_attention",
    ), bits.reshape(b * hk, s, words), q.reshape(b, s, h * d),
        k.reshape(b, s, hk * d), v.reshape(b, s, hk * d),
        interpret=interpret)
    return out.reshape(b, s, h, d)


def _tile(s: int, target: int, unit: int, within: int | None = None) -> int:
    """The largest divisor of ``s`` up to ``target`` that is whole
    ``unit``s (``s`` itself where it is that short) and, given ``within``,
    divides it or is the whole sequence; 0 where there is none."""
    fits = lambda t: s % t == 0 and (within is None or within % t == 0
                                     or t == s)
    if s <= target and fits(s):
        return s
    return next((t for t in range(target - target % unit, 0, -unit)
                 if fits(t)), 0)


def chosen_blocks_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                            bits: jax.Array, block_size: int,
                            tile_q: int | None = None,
                            tile_k: int | None = None,
                            interpret: bool | None = None) -> jax.Array:
    """Causal softmax attention of every query over the keys of its chosen
    blocks. ``q``: (batch, seq, heads, d); ``k``, ``v``: (batch, seq,
    kv_heads, d); ``bits``: :func:`select_blocks`'s table. Returns (batch,
    seq, heads, d) float32. A k tile's blocks must lie in one 32-block word
    (``tile_k`` divides ``32 * block_size``, or is the sequence)."""
    b, s, h, d = q.shape
    kv_groups(h, k.shape[2])
    span = _WORD * block_size
    tq = _tile(s, TILE_Q, 8) if tile_q is None else tile_q
    tk = (_tile(s, min(TILE_K, span), block_size, within=span)
          if tile_k is None else tile_k)
    if (not tq or not tk or s % tq or s % tk or tk % block_size
            or (span % tk and not tk == s <= span)):
        raise ValueError(f"tiles {tq}/{tk} do not fit seq {s} in blocks of "
                         f"{block_size}: a k tile's blocks must lie in one "
                         f"{_WORD}-block word")
    return _attend(q, k, v, bits, block_size, 1.0 / math.sqrt(d), tq, tk,
                   interpret)
