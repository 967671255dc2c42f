"""Decayed linear attention ("lightning attention") as a chunked scan, one
Pallas TPU kernel.

For head ``h`` with decay rate ``lam_h > 0`` and NO softmax::

    o_t = sum_{s <= t} exp(-lam_h (t - s)) (q_t . k_s / sqrt(d)) v_s

The plain sum is quadratic in the sequence. The scan works a sequence in
chunks of ``C`` positions and carries ONE (head_dim x head_dim) float32
state a head across them: for the chunk that starts at ``n C``

    S_n   = sum_{s < n C} exp(-lam (n C - s)) k_s^T v_s
    o_i   = scale * ( exp(-lam i) q_i S_n                     (the past)
                      + sum_{j <= i} exp(-lam (i - j)) (q_i . k_j) v_j )
    S_n+1 = exp(-lam C) S_n + sum_i exp(-lam (C - i)) k_i^T v_i

with ``i``, ``j`` positions inside the chunk. Every factor is at most 1:
nothing is split into ``exp(-lam i) exp(+lam j)``, which overflows float32
for the fast-decaying heads at any chunk worth having. Work is
``O(seq * (C + head_dim) * head_dim)`` a head.

The grid is (batch x heads, chunks) with the chunks innermost and
sequential: the state lives in VMEM scratch across them, as does the
chunk's (C x C) decay matrix, which depends on the head alone and is made
once a head. With ``head_dim`` a multiple of 128 a step is handed a lane
block of the model's own (batch, seq, heads x head_dim) array: nothing is
transposed or copied around the call; any other head size goes as a
(batch x heads, seq, head_dim) copy (the tiny presets of the tests).

Operands meet the MXU in the inputs' dtype with float32 accumulation; the
decay, the state and the output are float32. Compiled (Mosaic) where the
program is lowered for a TPU, the interpreter elsewhere
(:mod:`.kernelcall`). Forward only: the scoring path has no backward.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..utils.realjit import real_jit
from .kernelcall import kernel_call

#: Positions a grid step works: the intra-chunk products grow with it
#: (2 C head_dim a position), a step's fixed cost shrinks with it.
CHUNK = 512
_LANES = 128

_NT = (((1,), (1,)), ((), ()))      # a . b^T
_NN = (((1,), (0,)), ((), ()))      # a . b
_TN = (((0,), (0,)), ((), ()))      # a^T . b


def slopes(heads: int, exponent: float = 8.0) -> jax.Array:
    """Lightning attention's slope rule: ``lam_h = 2^(-exponent h /
    heads)``, h = 1..heads (the ALiBi ladder)."""
    h = jnp.arange(1, heads + 1, dtype=jnp.float32)
    return jnp.exp2(-exponent * h / heads)


def _kernel(lam_ref, q_ref, k_ref, v_ref, o_ref, state_ref, decay_ref, *,
            chunk: int, scale: float, heads: int):
    c = pl.program_id(1)
    lam = lam_ref[pl.program_id(0) % heads]          # a float32 scalar
    pos = jax.lax.broadcasted_iota(jnp.int32, (chunk, 1), 0).astype(
        jnp.float32)

    @pl.when(c == 0)
    def _first_chunk_of_a_head():
        state_ref[:] = jnp.zeros_like(state_ref)
        row = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
        col = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
        gap = jnp.maximum(row - col, 0).astype(jnp.float32)
        decay_ref[:] = jnp.where(row >= col, jnp.exp(-lam * gap), 0.0)

    q, k, v = q_ref[0], k_ref[0], v_ref[0]
    state = state_ref[:]
    sc = jax.lax.dot_general(q, k, _NT, preferred_element_type=jnp.float32)
    intra = jax.lax.dot_general((sc * decay_ref[:]).astype(v.dtype), v, _NN,
                                preferred_element_type=jnp.float32)
    past = jax.lax.dot_general(q, state.astype(q.dtype), _NN,
                               preferred_element_type=jnp.float32)
    o_ref[0] = (scale * (intra + jnp.exp(-lam * pos) * past)).astype(
        o_ref.dtype)
    kw = (k.astype(jnp.float32) * jnp.exp(-lam * (chunk - pos))).astype(
        k.dtype)
    state_ref[:] = (jnp.exp(-lam * chunk) * state
                    + jax.lax.dot_general(kw, v, _TN,
                                          preferred_element_type=jnp.float32))


@functools.partial(real_jit(), static_argnames=("chunk", "scale",
                                                "interpret"))
def _scan(q, k, v, lam, chunk, scale, interpret):
    b, s, h, d = q.shape
    lanes = d % _LANES == 0
    if lanes:       # the model's own array, a head = d // 128 lane blocks
        view = lambda x: x.reshape(b, s, h * d)
        index = lambda i, c: (i // h, c, i % h)
        out_shape = (b, s, h * d)
    else:           # a (batch x heads, seq, head_dim) copy
        view = lambda x: x.transpose(0, 2, 1, 3).reshape(b * h, s, d)
        index = lambda i, c: (i, c, 0)
        out_shape = (b * h, s, d)
    block = pl.BlockSpec((1, chunk, d), index)
    out = kernel_call(lambda interp: pl.pallas_call(
        functools.partial(_kernel, chunk=chunk, scale=scale, heads=h),
        grid=(b * h, s // chunk),
        # the rates whole in scalar memory: a step reads its head's
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  block, block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        scratch_shapes=[pltpu.VMEM((d, d), jnp.float32),
                        pltpu.VMEM((chunk, chunk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interp, name="lightning_scan",
    ), lam.astype(jnp.float32), view(q), view(k), view(v),
        interpret=interpret)
    if lanes:
        return out.reshape(b, s, h, d)
    return out.reshape(b, h, s, d).transpose(0, 2, 1, 3)


def lightning_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                        lam: jax.Array, chunk: int | None = None,
                        interpret: bool | None = None) -> jax.Array:
    """``q``, ``k``, ``v``: (batch, seq, heads, head_dim), ``lam``: (heads,)
    decay rates; returns (batch, seq, heads, head_dim) float32. ``chunk``
    (default :data:`CHUNK`, the sequence where it is shorter) need not
    divide the sequence: the tail is padded with zero keys, which add
    nothing to any state, and the padded rows are dropped."""
    b, s, h, d = q.shape
    if k.shape != q.shape or v.shape != q.shape or lam.shape != (h,):
        raise ValueError(f"q {q.shape}, k {k.shape}, v {v.shape} must be "
                         f"alike and lam {lam.shape} one rate a head")
    chunk = min(CHUNK if chunk is None else int(chunk), s)
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    pad = -s % chunk
    if pad:
        q, k, v = (jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
                   for x in (q, k, v))
    out = _scan(q, k, v, lam, chunk, 1.0 / math.sqrt(d), interpret)
    return out[:, :s] if pad else out


def decayed_sum(q, k, v, lam) -> jax.Array:
    """The plain quadratic sum the scan stands for, float32: what the
    tests hold :func:`lightning_attention` to at small sizes."""
    d, s = q.shape[-1], q.shape[1]
    scale = 1.0 / math.sqrt(d)
    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    gap = (jnp.arange(s)[:, None] - jnp.arange(s)[None, :]).astype(
        jnp.float32)
    decay = jnp.where(gap >= 0, jnp.exp(-lam[:, None, None]
                                        * jnp.maximum(gap, 0.0)), 0.0)
    sc = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                    precision=jax.lax.Precision.HIGHEST) * scale
    return jnp.einsum("bhqk,bkhd->bqhd", sc * decay[None], v,
                      precision=jax.lax.Precision.HIGHEST)
