"""Flash attention forward and backward as Pallas TPU kernels.

Dense attention materializes the (seq × seq) score matrix in HBM; the
flash schedule streams key/value BLOCKS through VMEM and folds them into
the output with the online-softmax update, so HBM traffic is O(seq·d)
and the only score tile ever alive is (block_q × block_k) — exactly the
memory argument that makes long contexts fit. This kernel is the
single-chip sibling of :mod:`kubeshare_tpu.parallel.ringattention`
(same math, the ring distributes the k/v loop over chips; this kernel
blocks it over VMEM).

Grid: (batch·head, q-blocks, k-blocks) with the k dimension innermost —
each program sees ONE (block_q × d) q tile and ONE (block_k × d) k/v
tile, so VMEM usage is independent of sequence length; the fp32 running
max/sum/accumulator live in VMEM scratch and carry across the k steps
(the q/out tiles are revisited, Pallas keeps them resident). Fully
masked causal blocks (k entirely above the diagonal) are predicated off
with ``pl.when``.

The tile is what one grid step works on, and a step has a fixed cost
(pipeline bookkeeping, a DMA issue and wait per block) that a 128 × 128
tile's ~10 ns of MXU work cannot hide: at 8 × 1024 × 12 heads that was
6,144 steps a call, every one bound by its overhead. So the tile follows
the SHAPE (:func:`_blocks`): the largest divisors of the sequence not
above :data:`TILE_TARGET` whose live VMEM — double-buffered operand
blocks, the float32 score-sized temporaries, the scratch — fits
:data:`VMEM_BUDGET`, with ``vmem_limit_bytes`` raised past Mosaic's
default only when the tile needs it. A sequence up to the target is ONE
tile. ``block_q`` / ``block_k`` given by the caller are obeyed as given.

The MXU operands follow the INPUTS' dtype: bfloat16 q, k, v (and the
output cotangent, cast to theirs) meet the MXU as bfloat16 with float32
accumulation, and P and dS are cast to the dtype of the operand they
multiply; float32 inputs keep every operand float32 (which Mosaic, at
its default precision, multiplies in one bfloat16 pass on the chip all
the same; only the interpreter keeps float32 products). The ``1/√d``
scale is applied to the float32 scores and, once, to the finished dQ /
dK accumulators — never to q before the matmul, so Q·Kᵀ of bfloat16
values is exact in float32. Running max and sum, ``exp``, the logsumexp,
D, masks and every accumulator are float32 whatever the inputs are.

Differentiable via ``custom_vjp`` with FLASH BACKWARD kernels: the
forward additionally emits the per-row logsumexp L, and the backward
recomputes score blocks from (q, k, L) in VMEM — two Pallas kernels,
one accumulating dQ over the k loop, one accumulating dK/dV over the q
loop (separate kernels so each accumulator is owned by exactly one
sequential grid lane — no cross-program races). Peak memory is
O(block²) on the backward too, so long sequences train, not just
infer. Compiled (Mosaic) where the program is lowered for a TPU, the
interpreter elsewhere (:mod:`.kernelcall`), so CPU CI runs the identical
kernel bodies and a proxy-attached pod's export carries the compiled one.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .attention import MASK_VALUE, kv_groups
from .kernelcall import kernel_call

#: Rows of q, and of k/v, that one grid step aims to work on
#: (:func:`_blocks`): the one constant of the tile rule. Swept on a v5e
#: at the benchmark's shapes (PERF.md, PR 25): the three kernels of one
#: layer at 8 × 1024 × 12 × 64 bfloat16 take 7.84 ms at 128, 3.52 at 256,
#: 1.81 at 512 and 1.39 at 1024, where the whole causal sequence is one
#: tile: the masked half it multiplies costs less than the steps it saves.
TILE_TARGET = 1024
#: VMEM a derived tile may need: half of the smallest VMEM among the
#: chips this runs on (64 MiB), the other half left to the compiler.
VMEM_BUDGET = 32 * 2 ** 20
#: What Mosaic grants a kernel that asks for nothing (v5e).
_SCOPED_VMEM = 16 * 2 ** 20


def _score_tile(q_ref, k_ref, j, kk, block_q, block_k, causal, scale,
                window=None):
    """One (bq × bk) masked score tile — the ONLY place the score matmul
    and causal/band mask live: the backward's P recompute must match
    the forward's softmax bit-for-bit, so both call this. q and k meet
    the MXU in their own dtype; the scale lands on the float32 scores."""
    sc = jax.lax.dot_general(q_ref[0], k_ref[0], (((1,), (1,)), ((), ())),
                             preferred_element_type=jnp.float32) * scale
    if causal:
        qpos = j * block_q + jax.lax.broadcasted_iota(
            jnp.int32, (block_q, 1), 0)
        kpos = kk * block_k + jax.lax.broadcasted_iota(
            jnp.int32, (1, block_k), 1)
        mask = qpos >= kpos
        if window is not None:
            # sliding window: query i sees keys in (i - window, i]
            mask = jnp.logical_and(mask, qpos - kpos < window)
        sc = jnp.where(mask, sc, MASK_VALUE)
    return sc


def _live_fwd(j, kk, block_q, block_k, causal, window):
    """Does k block ``kk`` intersect q block ``j``'s visible band?"""
    live = jnp.logical_or(not causal, kk * block_k <= (j + 1) * block_q - 1)
    if window is not None:
        # the block's LAST key must be within the window of the block's
        # first query: kk·bk + bk − 1 > j·bq − window
        live = jnp.logical_and(
            live, (kk + 1) * block_k - 1 > j * block_q - window)
    return live


def _kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
            block_q: int, block_k: int, n_k: int, causal: bool,
            scale: float, window: int | None = None):
    """One (q-block, k-block) step. Scratch m/l/acc carry across the
    innermost (k) grid dimension."""
    j = pl.program_id(1)          # q block
    kk = pl.program_id(2)         # k block (innermost, sequential)

    @pl.when(kk == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, MASK_VALUE)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Causal: the whole k block is masked iff its first row starts after
    # the q block's last query. Predicating the update off skips the two
    # matmuls — about half the causal FLOPs.
    live = _live_fwd(j, kk, block_q, block_k, causal, window)

    @pl.when(live)
    def _update():
        sc = _score_tile(q_ref, k_ref, j, kk, block_q, block_k, causal,
                         scale, window)                    # (bq, bk)
        vb = v_ref[0]
        m = m_ref[:]
        m_new = jnp.maximum(m, sc.max(axis=-1, keepdims=True))
        alpha = jnp.where(m > MASK_VALUE * 0.5, jnp.exp(m - m_new), 0.0)
        p = jnp.where(sc > MASK_VALUE * 0.5, jnp.exp(sc - m_new), 0.0)
        m_ref[:] = m_new
        l_ref[:] = l_ref[:] * alpha + p.sum(axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * alpha + jax.lax.dot_general(
            p.astype(vb.dtype), vb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _finish():
        l = l_ref[:]
        o_ref[0] = (acc_ref[:] / jnp.where(l > 0.0, l, 1.0)
                    ).astype(o_ref.dtype)
        # per-row logsumexp: the backward recomputes P = exp(S - L)
        # without re-running the online-softmax reduction
        lse_ref[0] = m_ref[:] + jnp.log(jnp.where(l > 0.0, l, 1.0))


def _fold(x):
    """(b, s, h, d) → (b·h, s, d): one grid row per batch·head."""
    b, s, h, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(b * h, s, d)


def _unfold(x, b, h):
    bh, s, d = x.shape
    return x.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def _vma(*xs):
    """Varying-manual-axes union of the inputs: pallas outputs inside
    ``shard_map`` (the ring composition) must declare how they vary."""
    return frozenset().union(*(jax.typeof(x).vma for x in xs))


def _tile_vmem_bytes(bq, bk, d, itemsize):
    """VMEM one grid step keeps alive at a (bq × bk) tile, the most over
    the three kernels: blocks are padded to (8, 128) tiles (head_dim 64
    fills half the lanes; a (bq, 1) row vector takes 128). An upper
    bound: compiled for a v5e, 1024 × 1024 × 64 bfloat16 fits in 10 MiB
    where this counts 24."""
    def pad(n, m):
        return -(-n // m) * m
    rq, rk, lanes = pad(bq, 8), pad(bk, 8), pad(d, 128)
    operands = 2 * 2 * (rq + rk) * lanes * itemsize  # q, dO, k, v; 2-deep
    rows = 2 * 2 * rq * 128 * 4                      # lse, D; 2-deep
    outputs = 2 * 2 * max(rq, rk) * lanes * 4        # dK and dV; 2-deep
    scratch = 2 * max(rq, rk) * lanes * 4 + 2 * rq * 128 * 4
    scores = 4 * rq * pad(bk, 128) * 4               # S, P, dP, dS
    return operands + rows + outputs + scratch + scores


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


def _blocks(s_q, s_kv, d, dtype, block_q, block_k, causal, window=None):
    """``(block_q, block_k, vmem_limit_bytes)`` for one call. A block the
    caller gives is obeyed (clamped to the sequence); one left ``None``
    follows the shape: the largest tile up to :data:`TILE_TARGET`, the
    target halved until the step's VMEM fits :data:`VMEM_BUDGET`. The
    limit is ``None`` while Mosaic's default covers the need."""
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
    target = TILE_TARGET
    while True:
        bq = (min(block_q, s_q) if block_q is not None
              else _largest_tile(s_q, target, sublanes))
        bk = (min(block_k, s_kv) if block_k is not None
              else _largest_tile(s_kv, target, sublanes))
        if not bq or not bk or s_q % bq or s_kv % bk:
            raise ValueError(f"seq q={s_q}/kv={s_kv} must be divisible by "
                             f"blocks {bq}/{bk}")
        need = _tile_vmem_bytes(bq, bk, d, itemsize)
        if (need <= VMEM_BUDGET or target <= sublanes
                or (block_q is not None and block_k is not None)):
            break
        target //= 2
    return bq, bk, (None if need <= _SCOPED_VMEM else need)


def _compiler_params(vmem_limit):
    return (None if vmem_limit is None
            else pltpu.CompilerParams(vmem_limit_bytes=vmem_limit))


def _kv_row_map(h, hk):
    """Grid row (over batch·q-heads) → k/v array row (over batch·kv-heads).

    Grouped-query attention lives HERE, not in an HBM expansion: q row
    ``i = bi·h + hq`` reads k/v row ``bi·hk + hq // (h//hk)`` — the
    group's shared k/v tile is simply addressed by every member's
    programs, so the smaller k/v stays its small self in HBM (the point
    of GQA: the kv bytes, not the FLOPs, bound long-context decode)."""
    if h == hk:
        return lambda i: i
    group = kv_groups(h, hk)
    return lambda i: (i // h) * hk + (i % h) // group


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "window"))
def _flash_fwd(q, k, v, causal, block_q, block_k, interpret, window=None):
    b, s_q, h, d = q.shape
    s_kv, hk = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    bq, bk, vmem = _blocks(s_q, s_kv, d, q.dtype, block_q, block_k, causal,
                           window)
    kvrow = _kv_row_map(h, hk)
    n_k = s_kv // bk
    qr, kr, vr = _fold(q), _fold(k), _fold(v)
    vma = _vma(q, k, v)

    out, lse = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_kernel, block_q=bq, block_k=bk, n_k=n_k,
                          causal=causal, scale=scale, window=window),
        grid=(b * h, s_q // bq, n_k),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (kvrow(i), kk, 0)),
            pl.BlockSpec((1, bk, d), lambda i, j, kk: (kvrow(i), kk, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0)),
            pl.BlockSpec((1, bq, 1), lambda i, j, kk: (i, j, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((b * h, s_q, d), jnp.float32, vma=vma),
            jax.ShapeDtypeStruct((b * h, s_q, 1), jnp.float32, vma=vma),
        ],
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),   # running max
            pltpu.VMEM((bq, 1), jnp.float32),   # running sum
            pltpu.VMEM((bq, d), jnp.float32),   # output accumulator
        ],
        compiler_params=_compiler_params(vmem),
        interpret=interp, name="flash_fwd",
    ), qr, kr, vr, interpret=interpret)
    return _unfold(out, b, h), lse


def _recompute_p(q_ref, k_ref, lse_ref, j, kk, block_q, block_k, causal,
                 scale, window=None):
    """Shared by both backward kernels: rebuild one (bq × bk) probability
    tile from q, k and the saved logsumexp — no running max needed.
    Masked entries: exp(MASK_VALUE - L) underflows to exactly 0."""
    sc = _score_tile(q_ref, k_ref, j, kk, block_q, block_k, causal, scale,
                     window)
    return jnp.exp(sc - lse_ref[0])


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref, dq_ref,
                   dq_acc, *, block_q: int, block_k: int, n_k: int,
                   causal: bool, scale: float,
                   window: int | None = None):
    """dQ pass: one q block owns the sequential k loop, so dq_acc has a
    single writer. dS = P ∘ (dO·Vᵀ − D); dQ = scale · dS·K, the scale
    applied once to the finished accumulator."""
    j = pl.program_id(1)          # q block
    kk = pl.program_id(2)         # k block (innermost, sequential)

    @pl.when(kk == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    live = _live_fwd(j, kk, block_q, block_k, causal, window)

    @pl.when(live)
    def _update():
        p = _recompute_p(q_ref, k_ref, lse_ref, j, kk, block_q, block_k,
                         causal, scale, window)
        kb = k_ref[0]
        dp = jax.lax.dot_general(do_ref[0], v_ref[0],
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dcap_ref[0])
        dq_acc[:] += jax.lax.dot_general(
            ds.astype(kb.dtype), kb, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(kk == n_k - 1)
    def _finish():
        dq_ref[0] = (dq_acc[:] * scale).astype(dq_ref.dtype)


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dcap_ref,
                    dk_ref, dv_ref, dk_acc, dv_acc, *, block_q: int,
                    block_k: int, n_q: int, group: int, causal: bool,
                    scale: float, window: int | None = None):
    """dK/dV pass: one K/V ROW (kv head) owns the sequential inner loop
    ``t = g·n_q + qq`` over its GROUP of q heads × q blocks, so the GQA
    group sum happens in the VMEM accumulator and the outputs stay
    kv-sized in HBM (group=1 collapses to the plain per-head loop).
    dV = Pᵀ·dO; dK = scale · dSᵀ·Q, the scale applied once to the
    finished accumulator."""
    jj = pl.program_id(1)         # k block
    t = pl.program_id(2)          # (q head in group, q block) — sequential
    qq = t % n_q                  # q block index within the sequence

    @pl.when(t == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    # Same band-liveness as the forward/dQ passes with the roles
    # swapped: does q block qq intersect k block jj's visible band?
    live = _live_fwd(qq, jj, block_q, block_k, causal, window)

    @pl.when(live)
    def _update():
        p = _recompute_p(q_ref, k_ref, lse_ref, qq, jj, block_q, block_k,
                         causal, scale, window)
        qb, dob = q_ref[0], do_ref[0]
        dv_acc[:] += jax.lax.dot_general(
            p.astype(dob.dtype), dob, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dp = jax.lax.dot_general(dob, v_ref[0], (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - dcap_ref[0])
        dk_acc[:] += jax.lax.dot_general(
            ds.astype(qb.dtype), qb, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(t == group * n_q - 1)
    def _finish():
        dk_ref[0] = (dk_acc[:] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[:].astype(dv_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("causal", "block_q", "block_k",
                                    "interpret", "window"))
def _flash_bwd(q, k, v, o, lse, g, g_lse, causal, block_q, block_k,
               interpret, window=None):
    b, s_q, h, d = q.shape
    s_kv, hk = k.shape[1], k.shape[2]
    scale = 1.0 / math.sqrt(d)
    bq, bk, vmem = _blocks(s_q, s_kv, d, q.dtype, block_q, block_k, causal,
                           window)
    kvrow = _kv_row_map(h, hk)
    n_q, n_k = s_q // bq, s_kv // bk
    vma = _vma(q, k, v, o, lse, g)

    qr, kr, vr = _fold(q), _fold(k), _fold(v)
    # dO meets V (dO·Vᵀ) and P (Pᵀ·dO) on the MXU: it goes in v's dtype
    dor = _fold(g.astype(v.dtype))
    # D_i = rowsum(dO ∘ O) in float32: O(s·d) elementwise, XLA fuses it —
    # not worth a kernel pass of its own.
    dcap = _fold((g.astype(jnp.float32) * o).sum(-1, keepdims=True))
    if g_lse is not None:
        # lse output cotangent: ∂L_i/∂S_ij = P_ij, so the extra dS term
        # P ∘ g_lse folds into the same kernels as dcap := D − g_lse
        # (dS = P ∘ (dP − D + g_lse)).
        dcap = dcap - (g_lse.astype(jnp.float32)
                       .transpose(0, 2, 1).reshape(b * h, s_q, 1))

    qspec = pl.BlockSpec((1, bq, d), lambda i, j, kk: (i, j, 0))
    kspec = pl.BlockSpec((1, bk, d), lambda i, j, kk: (kvrow(i), kk, 0))
    rowspec = pl.BlockSpec((1, bq, 1), lambda i, j, kk: (i, j, 0))

    dq = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_bwd_dq_kernel, block_q=bq, block_k=bk, n_k=n_k,
                          causal=causal, scale=scale, window=window),
        grid=(b * h, n_q, n_k),
        in_specs=[qspec, kspec, kspec, qspec, rowspec, rowspec],
        out_specs=qspec,
        out_shape=jax.ShapeDtypeStruct((b * h, s_q, d), q.dtype, vma=vma),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_compiler_params(vmem),
        interpret=interp, name="flash_dq",
    ), qr, kr, vr, dor, lse, dcap, interpret=interpret)

    # dK/dV grid: one row per batch·KV-head; k blocks outer; the
    # sequential inner dim walks this kv head's whole GROUP of q heads ×
    # q blocks (t = g·n_q + qq), so the group sum lives in the VMEM
    # accumulator and dK/dV stay kv-sized in HBM. The q-side row for
    # (i, t): batch (i // hk), q head (i % hk)·group + t // n_q.
    group = h // hk

    def qrow(i, t):
        return (i // hk) * h + (i % hk) * group + t // n_q

    qspec2 = pl.BlockSpec((1, bq, d),
                          lambda i, jj, t: (qrow(i, t), t % n_q, 0))
    kspec2 = pl.BlockSpec((1, bk, d), lambda i, jj, t: (i, jj, 0))
    rowspec2 = pl.BlockSpec((1, bq, 1),
                            lambda i, jj, t: (qrow(i, t), t % n_q, 0))
    dk, dv = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, block_q=bq, block_k=bk, n_q=n_q,
                          group=group, causal=causal, scale=scale,
                          window=window),
        grid=(b * hk, n_k, group * n_q),
        in_specs=[qspec2, kspec2, kspec2, qspec2, rowspec2, rowspec2],
        out_specs=[kspec2, kspec2],
        out_shape=[jax.ShapeDtypeStruct((b * hk, s_kv, d), k.dtype,
                                        vma=vma),
                   jax.ShapeDtypeStruct((b * hk, s_kv, d), v.dtype,
                                        vma=vma)],
        scratch_shapes=[pltpu.VMEM((bk, d), jnp.float32),
                        pltpu.VMEM((bk, d), jnp.float32)],
        compiler_params=_compiler_params(vmem),
        interpret=interp, name="flash_dkv",
    ), qr, kr, vr, dor, lse, dcap, interpret=interpret)

    return _unfold(dq, b, h), _unfold(dk, b, hk), _unfold(dv, b, hk)


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

    ``block_q`` / ``block_k`` left ``None`` follow the call's shapes
    (:func:`_blocks`: up to :data:`TILE_TARGET` rows each, one tile for a
    sequence that short); given, they are obeyed. The matmul operands
    keep the dtype of ``q``/``k``/``v`` — bfloat16 inputs feed the MXU
    bfloat16 with float32 accumulation, float32 inputs stay float32 —
    and the softmax is float32 either way.

    Grouped-query / multi-query attention: pass k/v with ``kv_heads``
    dividing q's ``heads`` — the group mapping happens in block index
    arithmetic (``_kv_row_map``), so the smaller k/v is never expanded
    in HBM.

    ``window`` (requires ``causal``) = sliding-window attention: query
    ``i`` sees keys in ``(i - window, i]``. Off-band BLOCKS are
    predicated off entirely, so compute scales with seq·window, not
    seq² — the Mistral-style band at kernel cost. Composes with
    ulysses (full sequence per device after the head exchange); the
    RING path stays full-causal (its per-step switch has no global
    offsets).

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
