"""Flash attention forward and backward as Pallas TPU kernels.

Dense attention materializes the (seq × seq) score matrix in HBM; the
flash schedule streams key/value BLOCKS through VMEM and folds them into
the output with the online-softmax update, so HBM traffic is O(seq·d)
and the only score tile ever alive is (block_q × block_k) — exactly the
memory argument that makes long contexts fit. This kernel is the
single-chip sibling of :mod:`kubeshare_tpu.parallel.ringattention`
(same math, the ring distributes the k/v loop over chips; this kernel
blocks it over VMEM).

What a grid step IS follows the call's SHAPES alone (:func:`_blocks`
returns the :class:`Plan`: tile, chunk, addressing):

- **The tile.** Grid: (batch·head blocks, q-blocks, k-blocks) with the k
  dimension innermost. A step has a fixed cost (pipeline bookkeeping, a
  DMA issue and wait per block) that a 128 × 128 tile's ~10 ns of MXU
  work cannot hide, so the tile is the largest divisor of the sequence
  not above :data:`TILE_TARGET` whose live VMEM fits :data:`VMEM_BUDGET`,
  with ``vmem_limit_bytes`` raised past Mosaic's default only when it is
  needed. A sequence up to the target is ONE tile. ``block_q`` /
  ``block_k`` given by the caller are obeyed as given. Tiles wholly above
  the causal diagonal (or outside the window's band) are predicated off
  with ``pl.when``.
- **The chunk.** One tile a sequence would multiply the whole square,
  twice what causal attention needs, and smaller GRID tiles pay a step
  each. So a square tile ON the diagonal is worked INSIDE its step, in a
  loop unrolled over static slices of operands already in VMEM: chunks of
  up to :data:`CHUNK_TARGET` query rows (forward, dQ), each against the
  keys up to its last row, or of keys (dK/dV), each against the queries
  from its first row on; ``window`` narrows either range the same way.
  Four chunks do 10/16 of the square's matmuls. Every live score is still
  computed and no key or query dropped at a chunk's edge: a chunk's mask
  is the tile's. Where a sequence is one k block a chunk's softmax sees
  all its keys at once and its rows go straight to the outputs: no
  running max to rescale, no accumulator in scratch (whose read-modify-
  write between chunks keeps the MXU and the VPU from overlapping); with
  more k blocks the float32 running max / sum / accumulator carry across
  the k steps in VMEM scratch, as do dQ's and dK/dV's sums.
- **The addressing.** The model's (batch, seq, heads, head_dim) array
  viewed as (batch, seq, heads·head_dim) costs nothing, and a BlockSpec
  of (1, rows, 128) lanes on it holds 128 // head_dim heads (two at 64,
  one block a head from 128 up). Where the heads fill such blocks
  (:func:`_heads_in_a_lane_block`) a step is handed a LANE BLOCK of the
  model's own array and works each of its heads in turn: the head's q
  (or dO) with the other heads' lanes zeroed, contracted over all 128
  lanes against the k (or v) block as it lies, meets its own head's
  alone; P·v gives 128 lanes of which the head's are kept by a select —
  the MXU passes a 64-deep contraction and a 64-wide output are padded to
  anyway. o, dq, dk, dv leave as lane-dense blocks in the model's layout,
  dO and O come in float32 as they are (dO cast for the MXU in VMEM, D =
  rowsum(dO ∘ O) taken in the kernels), so nothing is transposed, cast or
  copied around the three calls. Grouped queries on lane blocks: a
  block's q heads read ONE kv head, copied over the kv block's lanes by
  a roll, and dK/dV roll the group's sum back into that head's lanes. Any
  other call (an odd head count, head_dim 80 or 96, a group that splits a
  block over kv heads, a forced block below a sublane tile) keeps the
  FOLDED addressing — (seq, head_dim) blocks of a (batch·heads, seq,
  head_dim) copy made around the calls — with the same kernels, a block
  being one head.

The MXU operands follow the INPUTS' dtype: bfloat16 q, k, v (and the
output cotangent, cast to theirs) meet the MXU as bfloat16 with float32
accumulation, and P and dS are cast to the dtype of the operand they
multiply; float32 inputs keep every operand float32 (which Mosaic, at
its default precision, multiplies in one bfloat16 pass on the chip all
the same; only the interpreter keeps float32 products). The ``1/√d``
scale is applied to the float32 scores and, once, to the finished dQ /
dK sums — never to q before the matmul, so Q·Kᵀ of bfloat16 values is
exact in float32. Running max and sum, ``exp``, the logsumexp, D, masks
and every accumulator are float32 whatever the inputs are.

Differentiable via ``custom_vjp`` with FLASH BACKWARD kernels: the
forward additionally emits the per-row logsumexp L, and the backward
recomputes score blocks from (q, k, L) in VMEM — two Pallas kernels,
one summing dQ over the k loop, one summing dK/dV over the q loop
(separate kernels so each sum is owned by exactly one sequential grid
lane — no cross-program races). Peak memory is O(block²) on the backward
too, so long sequences train, not just infer. Compiled (Mosaic) where
the program is lowered for a TPU, the interpreter elsewhere
(:mod:`.kernelcall`), so CPU CI runs the identical kernel bodies and a
proxy-attached pod's export carries the compiled one.
"""

from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.realjit import real_jit
from .attention import MASK_VALUE, kv_groups
from .kernelcall import kernel_call

#: Rows of q, and of k/v, that one grid step aims to work on
#: (:func:`_blocks`): the one constant of the tile rule. Swept on a v5e
#: at the benchmark's shapes (PERF.md, PR 25): the three kernels of one
#: layer at 8 × 1024 × 12 × 64 bfloat16 take 7.84 ms at 128, 3.52 at 256,
#: 1.81 at 512 and 1.39 at 1024, where the whole causal sequence is one
#: tile: a grid step's fixed cost is what smaller tiles pay for.
TILE_TARGET = 1024
#: Rows of q (forward, dQ) or of k/v (dK/dV) that a tile ON the causal
#: diagonal is worked in, inside the step (:func:`_blocks`): the one
#: constant of the chunk rule. Each chunk multiplies only up to the
#: diagonal, so n chunks do (n + 1) / 2n of the square's matmul work.
CHUNK_TARGET = 256
#: VMEM a derived tile may need: half of the smallest VMEM among the
#: chips this runs on (64 MiB), the other half left to the compiler.
VMEM_BUDGET = 32 * 2 ** 20
#: What Mosaic grants a kernel that asks for nothing (v5e).
_SCOPED_VMEM = 16 * 2 ** 20
_LANES = 128

_NT = (((1,), (1,)), ((), ()))      # a · bᵀ
_NN = (((1,), (0,)), ((), ()))      # a · b
_TN = (((0,), (0,)), ((), ()))      # aᵀ · b


class Plan(NamedTuple):
    """What one call's grid steps are (:func:`_blocks`)."""
    block_q: int
    block_k: int
    #: rows a tile on the causal diagonal is worked in (``block_q`` where
    #: it is worked whole)
    chunk: int
    #: ``"lanes"``: a step is handed a lane block of the model's own
    #: (batch, seq, heads·head_dim) array; ``"folded"``: a (seq, head_dim)
    #: block of a (batch·heads, seq, head_dim) copy
    addressing: str
    #: heads in the block a step is handed (1 when folded)
    heads: int
    vmem_limit: int | None


class _Step(NamedTuple):
    """The static facts of one call: its :class:`Plan` laid over its
    arrays. What a kernel body needs, how each array is viewed for the
    kernels, and which block a grid step is handed.

    A grid row walks (batch, head block). Grouped-query attention lives in
    this index arithmetic, not in an HBM expansion: a q head block reads
    the kv block that holds its group's head, so the smaller k/v stays its
    small self in HBM (the point of GQA: the kv bytes, not the FLOPs,
    bound long-context decode)."""
    block_q: int
    block_k: int
    chunk: int
    n_q: int            # q blocks a sequence
    n_k: int
    folded: bool        # the arrays go as (batch·heads, seq, head_dim) copies
    heads: int          # heads in a block
    head_dim: int
    batch: int
    q_blocks: int       # head blocks of q a batch row has
    kv_blocks: int      # head blocks of k/v
    group_blocks: int   # q head blocks that share one kv head (GQA)
    spread: bool        # a block's q heads all read ONE head of a kv block
    causal: bool
    window: int | None
    scale: float
    vmem_limit: int | None

    @classmethod
    def of(cls, q, k, causal, block_q, block_k, window):
        b, s_q, h, d = q.shape
        s_kv, hk = k.shape[1], k.shape[2]
        plan = _blocks(s_q, s_kv, d, q.dtype, block_q, block_k, causal,
                       window, h, hk)
        per, group = plan.heads, kv_groups(h, hk)
        return cls(block_q=plan.block_q, block_k=plan.block_k,
                   chunk=plan.chunk, n_q=s_q // plan.block_q,
                   n_k=s_kv // plan.block_k,
                   folded=plan.addressing == "folded", heads=per, head_dim=d,
                   batch=b, q_blocks=h // per, kv_blocks=hk // per,
                   group_blocks=max(1, group // per),
                   spread=per > 1 and group > 1, causal=causal, window=window,
                   scale=1.0 / math.sqrt(d), vmem_limit=plan.vmem_limit)

    @property
    def chunked(self):
        return (self.causal and self.block_q == self.block_k
                and self.chunk < self.block_q)

    @property
    def lanes(self):
        return self.heads * self.head_dim

    @property
    def compiler_params(self):
        return (None if self.vmem_limit is None else
                pltpu.CompilerParams(vmem_limit_bytes=self.vmem_limit))

    # -- the arrays as the kernels address them
    def view(self, x):
        return _fold(x) if self.folded else x.reshape(*x.shape[:2], -1)

    def unview(self, x, heads):
        return (_unfold(x, self.batch, heads) if self.folded
                else x.reshape(*x.shape[:2], heads, -1))

    def shape(self, head_blocks, s, dtype, vma):
        dims = ((self.batch * head_blocks, s, self.lanes) if self.folded
                else (self.batch, s, head_blocks * self.lanes))
        return jax.ShapeDtypeStruct(dims, dtype, vma=vma)

    def spec(self, rows, head_blocks, where):
        """A (rows, lanes) block of an array of ``head_blocks`` a batch row,
        at ``where(*grid) -> (batch, head block, seq block)``."""
        def index(*grid):
            bi, hb, sb = where(*grid)
            return ((bi * head_blocks + hb, sb, 0) if self.folded
                    else (bi, sb, hb))
        return pl.BlockSpec((1, rows, self.lanes), index)

    def rowspec(self, where):
        """The (heads, block_q, 1) block of a per-row array kept as
        (batch·heads, seq, 1): lse and the lse cotangent."""
        def index(*grid):
            bi, hb, sb = where(*grid)
            return bi * self.q_blocks + hb, sb, 0
        return pl.BlockSpec((self.heads, self.block_q, 1), index)

    # -- which blocks a step of the q-major grids (forward, dQ) is handed,
    # and which head of its kv block (where ``spread``) its q heads read
    def q_of(self, i, j, kk):
        return i // self.q_blocks, i % self.q_blocks, j

    def kv_of(self, i, j, kk):
        kv_head = (i % self.q_blocks) // self.group_blocks
        return i // self.q_blocks, kv_head // self.kv_heads_walked, kk

    def kv_head_of(self, i):
        return ((i % self.q_blocks) // self.group_blocks) % self.heads

    # -- and a step of the kv-major grid (dK/dV): t walks (head of the kv
    # block, q head block of its group, q block)
    @property
    def kv_heads_walked(self):
        return self.heads if self.spread else 1

    @property
    def walk(self):
        return self.kv_heads_walked * self.group_blocks * self.n_q

    def q_at(self, i, jj, t):
        kv_head = ((i % self.kv_blocks) * self.kv_heads_walked
                   + t // (self.group_blocks * self.n_q))
        return (i // self.kv_blocks,
                kv_head * self.group_blocks + (t // self.n_q)
                % self.group_blocks, t % self.n_q)

    def kv_at(self, i, jj, t):
        return i // self.kv_blocks, i % self.kv_blocks, jj

    def kv_head_at(self, t):
        return t // (self.group_blocks * self.n_q)


def _scores(q, k, q0, k0, st):
    """One masked score tile — the ONLY place the score matmul and the
    causal/band mask live: the backward's P recompute must match the
    forward's softmax bit-for-bit, so all three kernels call this. q and k
    meet the MXU in their own dtype; the scale lands on the float32
    scores. ``q0`` / ``k0``: the sequence positions of the first row and
    the first key."""
    sc = jax.lax.dot_general(q, k, _NT,
                             preferred_element_type=jnp.float32) * st.scale
    if st.causal:
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, (sc.shape[0], 1), 0)
        kpos = k0 + jax.lax.broadcasted_iota(jnp.int32, (1, sc.shape[1]), 1)
        mask = qpos >= kpos
        if st.window is not None:
            # sliding window: query i sees keys in (i - window, i]
            mask = jnp.logical_and(mask, qpos - kpos < st.window)
        sc = jnp.where(mask, sc, MASK_VALUE)
    return sc


def _live(j, kk, st):
    """Does k block ``kk`` intersect q block ``j``'s visible band?"""
    live = jnp.logical_or(not st.causal,
                          kk * st.block_k <= (j + 1) * st.block_q - 1)
    if st.window is not None:
        # the block's LAST key must be within the window of the block's
        # first query: kk·bk + bk − 1 > j·bq − window
        live = jnp.logical_and(
            live, (kk + 1) * st.block_k - 1 > j * st.block_q - st.window)
    return live


def _row_chunks(st, whole=False):
    """A tile in chunks of query rows: ``(rows, keys)`` slices. ``whole``:
    the one chunk that is the tile; else a DIAGONAL tile's, the keys only
    those a chunk's rows can see (up to its last row, and with a window
    from the chunk that holds its first row's oldest key)."""
    if whole:
        yield slice(0, st.block_q), slice(0, st.block_k)
        return
    c = st.chunk
    for r in range(st.block_q // c):
        lo = (0 if st.window is None
              else max(0, (r * c - st.window + 1) // c * c))
        yield slice(r * c, (r + 1) * c), slice(lo, (r + 1) * c)


def _key_chunks(st, whole=False):
    """The same in chunks of keys: ``(keys, rows)``, the rows only those
    that can see a chunk's keys (from its first key on, and with a window
    up to the chunk that holds its last key's last query)."""
    if whole:
        yield slice(0, st.block_k), slice(0, st.block_q)
        return
    c = st.chunk
    for r in range(st.block_k // c):
        hi = (st.block_q if st.window is None else
              min(st.block_q, -(-((r + 1) * c + st.window - 1) // c) * c))
        yield slice(r * c, (r + 1) * c), slice(r * c, hi)


def _tile(j, kk, st, chunks, update):
    """Run ``update(a, b, q0, k0)`` over tile (j, kk): in chunks up to the
    diagonal where the tile lies on it, whole (and only if any of it is
    live) elsewhere. ``q0`` / ``k0``: the positions of the tile's first
    row and key (the mask needs their difference alone)."""
    def whole():
        for a, b in chunks(st, whole=True):
            update(a, b, j * st.block_q, kk * st.block_k)

    if not st.chunked:
        pl.when(_live(j, kk, st))(whole)
        return

    @pl.when(j == kk)
    def _diagonal():
        for a, b in chunks(st):
            update(a, b, 0, 0)

    if st.n_q * st.n_k > 1:
        pl.when(jnp.logical_and(j != kk, _live(j, kk, st)))(whole)


def _lanes_of(head, st):
    """The (1, lanes) mask of one head of the block (``head`` may be
    traced)."""
    d = st.head_dim
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, st.heads * d), 1)
    return jnp.logical_and(lane >= head * d, lane < (head + 1) * d)


def _head_masks(st):
    """One mask a head of the block; ``[None]`` where the block is one
    head."""
    if st.heads == 1:
        return [None]
    return [_lanes_of(i, st) for i in range(st.heads)]


def _only(mask, x):
    """``x`` with the lanes of every other head zeroed: contracted over
    all its lanes against a block as it lies, it meets its own head's."""
    return x if mask is None else jnp.where(mask, x, jnp.zeros_like(x))


def _into(mask, new, old):
    """``new`` in the head's lanes, ``old`` in the others' (which, before
    the first head, hold nothing yet)."""
    return new if mask is None or old is None else jnp.where(mask, new, old)


def _roll(x, shift):
    """``x`` rolled along its lanes. Mosaic rotates 32-bit vectors only: a
    narrower dtype goes as the 32-bit words its sublane pairs pack into,
    which a lane rotation moves whole."""
    if x.dtype.itemsize == 4:
        return pltpu.roll(x, shift, 1)
    return pltpu.bitcast(pltpu.roll(pltpu.bitcast(x, jnp.int32), shift, 1),
                         x.dtype)


def _spread(x, p, st):
    """Head ``p`` of a k/v block, copied to every head's lanes (GQA on
    lane blocks: the block's q heads all read kv head ``p``)."""
    out = x
    for r in range(1, st.heads):
        out = jnp.where(_lanes_of((p + r) % st.heads, st),
                        _roll(x, r * st.head_dim), out)
    return out


def _gather(x, p, st):
    """The sum over the block's heads of ``x``'s lanes, in head ``p``'s
    lanes and zero elsewhere: where a group's dK/dV partials, computed in
    their q heads' lanes, belong."""
    total = x
    for r in range(1, st.heads):
        total = total + _roll(x, r * st.head_dim)
    return jnp.where(_lanes_of(p, st), total, 0.0)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, *scratch, st: _Step):
    """One (q-block, k-block) step over the block's heads; on the diagonal
    the tile is worked in row chunks, each against the keys it can see.
    Where a sequence is ONE k block a chunk's softmax sees all its keys at
    once and goes straight to the outputs; else the running max / sum /
    accumulator carry across the innermost (k) grid dimension in scratch.
    Every head's scores are asked of the MXU before the first softmax: the
    VPU works one head's while the MXU makes the next's."""
    j = pl.program_id(1)          # q block
    kk = pl.program_id(2)         # k block (innermost, sequential)
    masks = _head_masks(st)
    p = st.kv_head_of(pl.program_id(0))
    one_pass = st.n_k == 1

    if not one_pass:
        m_ref, l_ref, acc_ref = scratch

        @pl.when(kk == 0)
        def _init():
            m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
            l_ref[:] = jnp.zeros_like(l_ref)
            acc_ref[:] = jnp.zeros_like(acc_ref)

    def update(rows, keys, q0, k0):
        qb, kb, vb = q_ref[0, rows, :], k_ref[0, keys, :], v_ref[0, keys, :]
        if st.spread:
            kb, vb = _spread(kb, p, st), _spread(vb, p, st)
        scs = [_scores(_only(mask, qb), kb, q0 + rows.start, k0 + keys.start,
                       st) for mask in masks]
        out = None
        for i, (mask, sc) in enumerate(zip(masks, scs)):
            m = sc.max(axis=-1, keepdims=True)
            if one_pass:
                # every row sees itself (or, not causal, every key)
                p_ = jnp.exp(sc - m)
                l = p_.sum(axis=-1, keepdims=True)
            else:
                m_old, m = m_ref[i, rows, :], jnp.maximum(m_ref[i, rows, :], m)
                alpha = jnp.where(m_old > MASK_VALUE * 0.5,
                                  jnp.exp(m_old - m), 0.0)
                p_ = jnp.where(sc > MASK_VALUE * 0.5, jnp.exp(sc - m), 0.0)
                m_ref[i, rows, :] = m
                l_ref[i, rows, :] = (l_ref[i, rows, :] * alpha
                                     + p_.sum(axis=-1, keepdims=True))
            pv = jax.lax.dot_general(p_.astype(vb.dtype), vb, _NN,
                                     preferred_element_type=jnp.float32)
            if one_pass:
                out = _into(mask, pv / l, out)
                # per-row logsumexp: the backward recomputes P = exp(S - L)
                # without re-running the softmax's reductions
                lse_ref[i, rows, :] = m + jnp.log(l)
            else:
                acc = acc_ref[rows, :]
                acc_ref[rows, :] = _into(mask, acc * alpha + pv, acc)
        if one_pass:
            o_ref[0, rows, :] = out.astype(o_ref.dtype)

    _tile(j, kk, st, _row_chunks, update)

    if not one_pass:
        @pl.when(kk == st.n_k - 1)
        def _finish():
            out = acc = acc_ref[:]
            for i, mask in enumerate(masks):
                l = l_ref[i]
                l = jnp.where(l > 0.0, l, 1.0)
                out = _into(mask, acc / l, out)
                lse_ref[i] = m_ref[i] + jnp.log(l)
            o_ref[0] = out.astype(o_ref.dtype)


def _dcap(do, o, mask, glse):
    """D_i = rowsum(dO ∘ O) over the head's lanes, float32, less the lse
    output's cotangent where there is one: ∂L_i/∂S_ij = P_ij, so the extra
    dS term P ∘ g_lse folds into dS = P ∘ (dP − (D − g_lse))."""
    prod = do * o
    if mask is not None:
        prod = jnp.where(mask, prod, 0.0)
    dcap = prod.sum(axis=-1, keepdims=True)
    return dcap if glse is None else dcap - glse


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
               st: _Step, has_glse: bool):
    """dQ pass: one q block owns the sequential k loop, so its accumulator
    has a single writer (and where a sequence is ONE k block there is none:
    a chunk's rows go straight out). dS = P ∘ (dO·Vᵀ − D); dQ = scale ·
    dS·K, the scale applied once to the finished sum. dO arrives float32
    and is cast to v's dtype here; D is taken here from dO and O."""
    glse_ref = rest[0] if has_glse else None
    dq_ref = rest[has_glse]
    j = pl.program_id(1)          # q block
    kk = pl.program_id(2)         # k block (innermost, sequential)
    masks = _head_masks(st)
    p = st.kv_head_of(pl.program_id(0))
    one_pass = st.n_k == 1

    if not one_pass:
        dq_acc = rest[-1]

        @pl.when(kk == 0)
        def _init():
            dq_acc[:] = jnp.zeros_like(dq_acc)

    def update(rows, keys, q0, k0):
        qb, kb, vb = q_ref[0, rows, :], k_ref[0, keys, :], v_ref[0, keys, :]
        if st.spread:
            kb, vb = _spread(kb, p, st), _spread(vb, p, st)
        do, o = do_ref[0, rows, :], o_ref[0, rows, :]
        dob = do.astype(vb.dtype)
        # the MXU is asked for every head's S and dP before the VPU starts
        scs = [_scores(_only(mask, qb), kb, q0 + rows.start, k0 + keys.start,
                       st) for mask in masks]
        dps = [jax.lax.dot_general(_only(mask, dob), vb, _NT,
                                   preferred_element_type=jnp.float32)
               for mask in masks]
        dq = None if one_pass else dq_acc[rows, :]
        for i, (mask, sc, dp) in enumerate(zip(masks, scs, dps)):
            dcap = _dcap(do, o, mask,
                         None if glse_ref is None else glse_ref[i, rows, :])
            ds = jnp.exp(sc - lse_ref[i, rows, :]) * (dp - dcap)
            part = jax.lax.dot_general(ds.astype(kb.dtype), kb, _NN,
                                       preferred_element_type=jnp.float32)
            dq = _into(mask, part if one_pass else dq + part, dq)
        if one_pass:
            dq_ref[0, rows, :] = (dq * st.scale).astype(dq_ref.dtype)
        else:
            dq_acc[rows, :] = dq

    _tile(j, kk, st, _row_chunks, update)

    if not one_pass:
        @pl.when(kk == st.n_k - 1)
        def _finish():
            dq_ref[0] = (dq_acc[:] * st.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, o_ref, lse_ref, *rest,
                st: _Step, has_glse: bool):
    """dK/dV pass: one k/v BLOCK owns the sequential inner loop ``t`` over
    (head of the block, q head block of its group, q block), so the GQA
    group sum happens in the VMEM accumulators and the outputs stay
    kv-sized in HBM; where that loop is ONE step (group 1, one q block a
    sequence) a chunk's keys go straight out. dV = Pᵀ·dO; dK = scale ·
    dSᵀ·Q, the scale applied once to the finished sum. On the diagonal the
    tile is worked in chunks of KEYS, each against the queries that can
    see it."""
    glse_ref = rest[0] if has_glse else None
    dk_ref, dv_ref = rest[has_glse:has_glse + 2]
    jj = pl.program_id(1)         # k block
    t = pl.program_id(2)          # sequential
    qq = t % st.n_q               # q block index within the sequence
    p = st.kv_head_at(t)          # head of the kv block (GQA on lanes)
    masks = _head_masks(st)
    one_pass = st.walk == 1

    if not one_pass:
        dk_acc, dv_acc = rest[-2:]

        @pl.when(t == 0)
        def _init():
            dk_acc[:] = jnp.zeros_like(dk_acc)
            dv_acc[:] = jnp.zeros_like(dv_acc)

    def update(keys, rows, q0, k0):
        qb, kb, vb = q_ref[0, rows, :], k_ref[0, keys, :], v_ref[0, keys, :]
        if st.spread:
            kb, vb = _spread(kb, p, st), _spread(vb, p, st)
        do, o = do_ref[0, rows, :], o_ref[0, rows, :]
        dob = do.astype(vb.dtype)
        qms = [_only(mask, qb) for mask in masks]
        doms = [_only(mask, dob) for mask in masks]
        scs = [_scores(qm, kb, q0 + rows.start, k0 + keys.start, st)
               for qm in qms]
        dps = [jax.lax.dot_general(dom, vb, _NT,
                                   preferred_element_type=jnp.float32)
               for dom in doms]
        dk = dv = 0.0
        for i, mask in enumerate(masks):
            dcap = _dcap(do, o, mask,
                         None if glse_ref is None else glse_ref[i, rows, :])
            p_ = jnp.exp(scs[i] - lse_ref[i, rows, :])
            ds = p_ * (dps[i] - dcap)
            # the other heads' lanes of qm / dom are zero, so each head's
            # product lands in its own lanes and the heads just add
            dv = dv + jax.lax.dot_general(
                p_.astype(dob.dtype), doms[i], _TN,
                preferred_element_type=jnp.float32)
            dk = dk + jax.lax.dot_general(
                ds.astype(qb.dtype), qms[i], _TN,
                preferred_element_type=jnp.float32)
        if st.spread:
            dk, dv = _gather(dk, p, st), _gather(dv, p, st)
        if one_pass:
            dk_ref[0, keys, :] = (dk * st.scale).astype(dk_ref.dtype)
            dv_ref[0, keys, :] = dv.astype(dv_ref.dtype)
        else:
            dk_acc[keys, :] += dk
            dv_acc[keys, :] += dv

    # the band's liveness with the roles swapped: q block qq against k
    # block jj
    _tile(qq, jj, st, _key_chunks, update)

    if not one_pass:
        @pl.when(t == st.walk - 1)
        def _finish():
            dk_ref[0] = (dk_acc[:] * st.scale).astype(dk_ref.dtype)
            dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


def _fold(x):
    """(b, s, h, d) → (b·h, s, d): the folded addressing's copy."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _vma(*xs):
    """Varying-manual-axes union of the inputs: pallas outputs inside
    ``shard_map`` (the ring composition) must declare how they vary."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _pad(n, m):
    return -(-n // m) * m


def _tile_vmem_bytes(bq, bk, rows, lanes, heads, itemsize):
    """VMEM one grid step keeps alive, the most over the three kernels:
    the operand and output blocks two deep (padded to (8, 128) tiles: a
    (bq, 1) row vector takes 128 lanes, a head of 64 folded takes 128),
    the scratch, the operands' masked / cast / spread copies, and the
    float32 score-sized temporaries (S, P, dP, dS and the two casts for
    the MXU) of the most ``rows`` worked at once: ONE chunk's where the
    sequence is one tile, a whole tile's otherwise — a chunk's are dead
    before the next one's are made. An upper bound: compiled for a v5e
    the kernels take about three quarters of it."""
    rq, rk, width = _pad(bq, 8), _pad(bk, 8), _pad(lanes, _LANES)
    per_row = heads * rq * _LANES * 4                # lse (or g_lse), a head
    # dQ / dK/dV read q, k, v, the float32 dO and O, and lse
    operands = 2 * ((rq + 2 * rk) * width * itemsize + 2 * rq * width * 4
                    + per_row)
    outputs = 2 * max(rq * width * 4 + per_row,      # o and lse
                      2 * rk * width * itemsize)     # dK and dV
    scratch = max(rq * width * 4 + 2 * per_row,      # acc, m, l
                  2 * rk * width * 4)                # the dK/dV accumulators
    copies = 4 * max(rq, rk) * width * itemsize
    casts = 2 * itemsize if itemsize < 4 else 0      # P and dS for the MXU
    scores = _pad(rows, 8) * _pad(max(bq, bk), _LANES) * (4 * 4 + casts)
    return operands + outputs + scratch + copies + scores


def _largest_tile(s, target, sublanes):
    """The largest divisor of ``s`` not above ``target`` that Mosaic can
    block: ``s`` itself when it is short enough (a whole dimension is
    always a legal block), else a multiple of 128 (full lanes of the
    score tile), else of the dtype's sublane count; 0 if there is none."""
    if s <= target:
        return s
    for step in (128, sublanes):
        for tile in range(target - target % step, 0, -step):
            if s % tile == 0:
                return tile
    return 0


def _heads_in_a_lane_block(d, heads, kv_heads, sublanes, block_q, block_k):
    """How many heads a (rows, 128-lane) block of the model's own (batch,
    seq, heads·head_dim) array holds, or 0 where the call cannot be
    addressed so: head_dim must tile the lanes (or the lanes it), q and kv
    heads must fill whole blocks, a block's q heads must read one kv head
    (or each its own: group 1), and a block the caller forces must be
    whole sublane tiles."""
    if d % _LANES == 0:
        per = 1
    elif _LANES % d == 0:
        per = _LANES // d
    else:
        return 0
    group = kv_groups(heads, kv_heads)
    if heads % per or kv_heads % per or (group > 1 and group % per):
        return 0
    if any(b is not None and b % sublanes for b in (block_q, block_k)):
        return 0
    return per


def _blocks(s_q, s_kv, d, dtype, block_q, block_k, causal, window=None,
            heads=1, kv_heads=1) -> Plan:
    """The :class:`Plan` of one call, read from its shapes alone.

    Tile: a block the caller gives is obeyed (clamped to the sequence);
    one left ``None`` is the largest divisor up to :data:`TILE_TARGET`,
    the target halved until the step's VMEM fits :data:`VMEM_BUDGET`.
    Chunk: where the call is causal and its tiles square, a tile on the
    diagonal is worked in the largest chunks up to :data:`CHUNK_TARGET`
    (the whole tile where it is that short). Addressing:
    :func:`_heads_in_a_lane_block`. The limit is ``None`` while Mosaic's
    default covers the need."""
    if window is not None:
        if not causal:
            raise ValueError("window requires causal=True (the band is "
                             "defined looking back from each query)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    if causal and s_q != s_kv:
        raise ValueError(f"causal needs equal q/kv lengths, got {s_q}/{s_kv}"
                         " (mask positions are same-origin)")
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    per = _heads_in_a_lane_block(d, heads, kv_heads, sublanes, block_q,
                                 block_k)
    lanes = max(d, _LANES) if per else d
    target = TILE_TARGET
    while True:
        bq = (min(block_q, s_q) if block_q is not None
              else _largest_tile(s_q, target, sublanes))
        bk = (min(block_k, s_kv) if block_k is not None
              else _largest_tile(s_kv, target, sublanes))
        if not bq or not bk or s_q % bq or s_kv % bk:
            raise ValueError(f"seq q={s_q}/kv={s_kv} must be divisible by "
                             f"blocks {bq}/{bk}")
        chunk = ((_largest_tile(bq, CHUNK_TARGET, sublanes) or bq)
                 if causal and bq == bk else bq)
        one_tile = causal and s_q == bq == bk
        need = _tile_vmem_bytes(bq, bk, chunk if one_tile else max(bq, bk),
                                lanes, per or 1, itemsize)
        if (need <= VMEM_BUDGET or target <= sublanes
                or (block_q is not None and block_k is not None)):
            break
        target //= 2
    return Plan(bq, bk, chunk, "lanes" if per else "folded", per or 1,
                None if need <= _SCOPED_VMEM else need)


def _jit(fn):
    """One traced unit a signature, by the GENUINE ``jax.jit`` also in a
    proxy-attached tenant: the attach shim's stand-in inlines a jit it
    meets inside a trace, uncached, so a 12-layer model traced these
    kernels and lowered them to Mosaic twelve times over (PR 33: 25 s of a
    tenant's set-up); a nested jit is traced once and lowered once."""
    return real_jit()(fn, static_argnames=("causal", "block_q", "block_k",
                                           "interpret", "window"))


@_jit
def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    """``(o, lse)``: o float32 in q's layout, lse (batch·heads, seq, 1)."""
    st = _Step.of(q, k, causal, block_q, block_k, window)
    b, s_q, h, _ = q.shape
    vma = _vma(q, k, v)
    qspec = st.spec(st.block_q, st.q_blocks, st.q_of)
    kspec = st.spec(st.block_k, st.kv_blocks, st.kv_of)

    out, lse = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_fwd_kernel, st=st),
        grid=(b * st.q_blocks, st.n_q, st.n_k),
        in_specs=[qspec, kspec, kspec],
        out_specs=[qspec, st.rowspec(st.q_of)],
        out_shape=[
            st.shape(st.q_blocks, s_q, jnp.float32, vma),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[] if st.n_k == 1 else [
            pltpu.VMEM((st.heads, st.block_q, 1), jnp.float32),  # running max
            pltpu.VMEM((st.heads, st.block_q, 1), jnp.float32),  # running sum
            pltpu.VMEM((st.block_q, st.lanes), jnp.float32),  # accumulator
        ],
        compiler_params=st.compiler_params,
        interpret=interp, name="flash_fwd",
    ), st.view(q), st.view(k), st.view(v), interpret=interpret)
    return st.unview(out, h), lse


@_jit
def _flash_bwd(q, k, v, o, lse, g, g_lse, causal, block_q, block_k,
               interpret, window=None):
    st = _Step.of(q, k, causal, block_q, block_k, window)
    b, s_q, h, _ = q.shape
    s_kv, hk = k.shape[1], k.shape[2]
    vma = _vma(q, k, v, o, lse, g)

    # dO and O go in as they are, float32: the kernels cast dO to v's
    # dtype for the MXU (dO·Vᵀ, Pᵀ·dO) and take D from the two
    operands = [st.view(x) for x in (q, k, v, g.astype(jnp.float32), o)]
    operands.append(lse)
    has_glse = g_lse is not None
    if has_glse:
        operands.append(g_lse.astype(jnp.float32)
                        .transpose(0, 2, 1).reshape(b * h, s_q, 1))

    def specs(q_where, kv_where):
        qspec = st.spec(st.block_q, st.q_blocks, q_where)
        kspec = st.spec(st.block_k, st.kv_blocks, kv_where)
        rows = st.rowspec(q_where)
        return ([qspec, kspec, kspec, qspec, qspec, rows]
                + [rows] * has_glse), qspec, kspec

    in_specs, qspec, _ = specs(st.q_of, st.kv_of)
    dq = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_dq_kernel, st=st, has_glse=has_glse),
        grid=(b * st.q_blocks, st.n_q, st.n_k),
        in_specs=in_specs, out_specs=qspec,
        out_shape=st.shape(st.q_blocks, s_q, q.dtype, vma),
        scratch_shapes=([] if st.n_k == 1 else
                        [pltpu.VMEM((st.block_q, st.lanes), jnp.float32)]),
        compiler_params=st.compiler_params,
        interpret=interp, name="flash_dq",
    ), *operands, interpret=interpret)

    in_specs, _, kspec = specs(st.q_at, st.kv_at)
    dk, dv = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_dkv_kernel, st=st, has_glse=has_glse),
        grid=(b * st.kv_blocks, st.n_k, st.walk),
        in_specs=in_specs, out_specs=[kspec, kspec],
        out_shape=[st.shape(st.kv_blocks, s_kv, k.dtype, vma),
                   st.shape(st.kv_blocks, s_kv, v.dtype, vma)],
        scratch_shapes=[] if st.walk == 1 else [
            pltpu.VMEM((st.block_k, st.lanes), jnp.float32),
            pltpu.VMEM((st.block_k, st.lanes), jnp.float32)],
        compiler_params=st.compiler_params,
        interpret=interp, name="flash_dkv",
    ), *operands, interpret=interpret)

    return st.unview(dq, h), st.unview(dk, hk), st.unview(dv, hk)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash(q, k, v, causal, block_q, block_k, interpret, window):
    out, _lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                           window)
    return out


def _flash_vjp_fwd(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                          window)
    return out, (q, k, v, out, lse)


def _flash_vjp_bwd(causal, block_q, block_k, interpret, window, res, g):
    q, k, v, out, lse = res
    return _flash_bwd(q, k, v, out, lse, g, None, causal, block_q, block_k,
                      interpret, window)


_flash.defvjp(_flash_vjp_fwd, _flash_vjp_bwd)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _flash_lse(q, k, v, causal, block_q, block_k, interpret, window):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                          window)
    b, s, h, _ = q.shape
    return out, lse.reshape(b, h, s).transpose(0, 2, 1)


def _flash_lse_vjp_fwd(q, k, v, causal, block_q, block_k, interpret,
                       window):
    out, lse = _flash_fwd(q, k, v, causal, block_q, block_k, interpret,
                          window)
    b, s, h, _ = q.shape
    return (out, lse.reshape(b, h, s).transpose(0, 2, 1)), \
        (q, k, v, out, lse)


def _flash_lse_vjp_bwd(causal, block_q, block_k, interpret, window, res,
                       g):
    q, k, v, out, lse = res
    g_out, g_lse = g
    return _flash_bwd(q, k, v, out, lse, g_out, g_lse, causal, block_q,
                      block_k, interpret, window)


_flash_lse.defvjp(_flash_lse_vjp_fwd, _flash_lse_vjp_bwd)


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int | None = None,
                    block_k: int | None = None,
                    interpret: bool | None = None,
                    window: int | None = None) -> jax.Array:
    """Drop-in for :func:`~kubeshare_tpu.ops.attention.dot_product_attention`
    (same (batch, seq, heads, head_dim) layout, fp32 output).

    What a grid step is follows the call's shapes (:func:`_blocks`):
    ``block_q`` / ``block_k`` left ``None`` give tiles of up to
    :data:`TILE_TARGET` rows, one tile for a sequence that short (given,
    they are obeyed); a causal tile on the diagonal is worked inside its
    step in chunks of up to :data:`CHUNK_TARGET` rows, each only up to the
    diagonal; and where the heads fill 128-lane blocks (head_dim 64 with
    an even number of q and kv heads, head_dim 128, ...) the kernels read
    and write the arrays as they are — no transpose, cast or copy around
    the calls — else a (batch·heads, seq, head_dim) copy. The matmul
    operands keep the dtype of ``q``/``k``/``v`` — bfloat16 inputs feed
    the MXU bfloat16 with float32 accumulation, float32 inputs stay
    float32 — and the softmax is float32 either way.

    Grouped-query / multi-query attention: pass k/v with ``kv_heads``
    dividing q's ``heads`` — the group mapping happens in block index
    arithmetic (:class:`_Step`), so the smaller k/v is never expanded
    in HBM.

    ``window`` (requires ``causal``) = sliding-window attention: query
    ``i`` sees keys in ``(i - window, i]``. Off-band BLOCKS are
    predicated off entirely (and a diagonal tile's chunks start at the
    band's edge), so compute scales with seq·window, not seq² — the
    Mistral-style band at kernel cost. Composes with ulysses (full
    sequence per device after the head exchange); the RING path stays
    full-causal (its per-step switch has no global offsets).

    ``interpret=None`` follows the platform the program is lowered for:
    compiled for a TPU, interpreter elsewhere (the interpreter runs the
    identical kernel body, so CPU CI covers it bit-for-bit). Plug into ``mha_apply(attn_fn=...)`` /
    ``transformer.apply`` for the single-chip long-context path.
    """
    return _flash(q, k, v, causal, block_q, block_k, interpret, window)


def flash_attention_lse(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True, block_q: int | None = None,
                        block_k: int | None = None,
                        interpret: bool | None = None,
                        window: int | None = None):
    """:func:`flash_attention` that ALSO returns the per-row logsumexp
    ``lse[b, i, h] = log Σ_j exp(q_i·k_j·scale)`` (fp32, masked keys
    excluded). Partial attentions over disjoint key sets merge exactly::

        lse = logaddexp(lse_a, lse_b)
        out = out_a·exp(lse_a − lse) + out_b·exp(lse_b − lse)

    — the composition :mod:`kubeshare_tpu.parallel.ringattention` uses
    to run this kernel per ring step. Differentiable in both outputs
    (the lse cotangent folds into the same backward kernels)."""
    return _flash_lse(q, k, v, causal, block_q, block_k, interpret,
                      window)
