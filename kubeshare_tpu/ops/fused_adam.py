"""Fused Adam update as a Pallas TPU kernel.

The optimizer update is the HBM-bandwidth-bound op of every training
step: it streams four arrays in (params, grads, m, v) and three out, 28
bytes a float32 parameter. Left to the reference's stack this is a
torch/CUDA `foreach` kernel; the TPU-native answer is one Pallas pass a
leaf in which every tensor crosses HBM exactly once.

A grid step has a fixed cost (a DMA issue and wait per operand, pipeline
bookkeeping: ~0.25 us on a v5e) that a small block's few kilobytes cannot
hide, so the block follows the LEAF (:func:`_block`): whole rows of the
leaf, as many as :data:`BLOCK_BYTES` an operand holds, the seven
double-buffered operand blocks declared to Mosaic as the kernel's
``vmem_limit_bytes``. The leaf is handed over in the layout the chip
keeps it in (:func:`_as_stored`) — never flattened, padded or sliced,
which on the chip are copies of the whole leaf — and the grid is ``cdiv``
of the shape by the block: Pallas reads a ragged edge block past the end
and stores only what is inside.

The three outputs are NEW buffers, not aliases of p, m, v: a step whose
arguments are not donated (every proxy-attached tenant's) may not write
its parameters' buffers, so XLA would copy each aliased operand first (8
bytes a parameter each), and the optax wrapper reads ``p`` again after
the call. What the kernel adds at the peak is the new state the step
returns anyway. On the chip the leaves are pinned to HBM, in and out
(:func:`_fused`): the 28 bytes a parameter are the kernel's own.

XLA fuses the optax chain well on its own; this kernel exists for the
cases it doesn't (long chains interleaved with collectives), as the
framework's demonstration of the Pallas path for hot ops, and because a
custom call keeps the scope it was traced under. The kernel is compiled
(Mosaic) where the program is lowered for a TPU and interpreted elsewhere
(:mod:`.kernelcall`), so CPU CI covers the same kernel body; nothing
drops to the ``jnp`` reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .kernelcall import kernel_call

#: Bytes of ONE operand that a grid step aims to move (at four bytes an
#: element): the one constant of the block rule. Swept on a v5e at the
#: benchmark's leaf shapes (PERF.md, PR 29): a (50257, 768) float32 leaf
#: takes 10.79 ms in 8 x 128 blocks (4 KB), 3.17 ms at 24 KB, 1.67 at 96 KB
#: and 1.61-1.62 ms (82% of 28 B x n / 819 GB/s, what XLA's own fusion
#: reads) from 0.4 MB to 6 MB, flat; the VMEM the body needs grows with
#: the block, so the constant sits low on the plateau.
BLOCK_BYTES = 2 ** 20
#: p, g, m, v in and p, m, v out, each block double-buffered by the
#: pipeline.
_BUFFERS = 2 * 7
_LANES = 128            # the minor tile of every TPU layout
#: What Mosaic grants a kernel that asks for nothing (v5e): the least the
#: call declares, whatever the block.
_SCOPED_VMEM = 16 * 2 ** 20


def _bias_corrections(t, b1, b2):
    """``(1 - b1^t, 1 - b2^t)`` — computed by XLA OUTSIDE the kernel: the
    chip's kernel compiler has no scalar ``pow`` (Mosaic: "failed to
    legalize operation 'math.powf'"), and two scalars per step are not
    worth a vector exp/log inside it."""
    return 1.0 - b1 ** t, 1.0 - b2 ** t


def _adam_math(p, g, m, v, c1, c2, lr, b1, b2, eps):
    """One Adam step (bias-corrected, Kingma & Ba 2014) given the two
    bias corrections — shared by the kernel body and the reference so
    they cannot drift. The arithmetic is float32 whatever the leaf's
    dtype: a narrower leaf is widened on the way in and rounded once on
    the way out (in bfloat16 ``b2 * v`` would not even decay)."""
    dtypes = p.dtype, m.dtype, v.dtype
    p, g, m, v = (x.astype(jnp.float32) for x in (p, g, m, v))
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * (g * g)
    m_hat = m_new / c1
    v_hat = v_new / c2
    p_new = p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return tuple(x.astype(d) for x, d in zip((p_new, m_new, v_new), dtypes))


def adam_update_reference(p, g, m, v, step, lr=1e-3, b1=0.9, b2=0.999,
                          eps=1e-8):
    """Pure-jnp Adam step; ``step`` is the 1-based step count."""
    c1, c2 = _bias_corrections(jnp.asarray(step, jnp.float32), b1, b2)
    return _adam_math(p, g, m, v, c1, c2, lr, b1, b2, eps)


def _kernel(corr_ref, p_ref, g_ref, m_ref, v_ref,
            p_out, m_out, v_out, *, lr, b1, b2, eps):
    p_out[...], m_out[...], v_out[...] = _adam_math(
        p_ref[...], g_ref[...], m_ref[...], v_ref[...],
        corr_ref[0], corr_ref[1], lr, b1, b2, eps)


def _round_up(n: int, to: int) -> int:
    return -(-n // to) * to


def _kept_transposed(shape) -> bool:
    """Does the chip keep this leaf TRANSPOSED (2-D leaves only)? XLA's
    TPU backend lays an array out in (8, 128) tiles with whichever of its
    two dimensions pads less as the lanes: ``(768, 50257)`` float32 lives
    as ``{0,1:T(8,128)}``, 50257 rows of 768 lanes (read from the programs
    compiled for a v5e, PR 29). Mosaic takes its operands row-major, so
    such a leaf goes to the kernel as its transpose, which is then a
    bitcast; handed over as it is, XLA transposes every operand in and
    every output back, seven copies of the leaf. A wrong guess here costs
    those copies, never the result."""
    if len(shape) != 2:
        return False
    rows, cols = shape
    return (_round_up(cols, 8) * _round_up(rows, _LANES)
            < _round_up(rows, 8) * _round_up(cols, _LANES))


def _block(shape, dtype):
    """``(block, vmem_limit_bytes)`` for a 1-D or 2-D view of a leaf.

    Whole rows, as many as :data:`BLOCK_BYTES` holds in multiples of the
    dtype's sublane count; rows too wide for that (over 32 K lanes) are
    cut in multiples of 128 lanes, one tile of rows at a time. A dimension
    the budget covers is taken whole, whatever its size. The budget counts
    four bytes an element whatever the dtype: the arithmetic is float32.
    The limit declares the pipeline's buffers of that block, padded to
    tiles as VMEM holds them, and as much again for what the body keeps
    live; never less than Mosaic grants unasked."""
    itemsize = jnp.dtype(dtype).itemsize
    sublanes = 32 // itemsize
    budget = BLOCK_BYTES // 4
    if len(shape) == 1:
        (n,) = shape
        if itemsize < 4:
            # unpacking a narrow 1-D block costs Mosaic several times its
            # float32 size in VMEM (compiled for a v5e: 131,072 bfloat16
            # elements do not fit 16 MiB, 65,536 do)
            budget //= 4
        if n > budget:
            block = (budget,)
        elif itemsize == 4:
            block = (n,)
        else:
            # Mosaic cannot mask inside a packed 32-bit word, so a narrow
            # 1-D leaf's one block is rounded up to the tile XLA keeps it
            # in (a power of two, 128 words to 1024 elements) and the
            # edge is left to Pallas like any other
            tile = min(1024, max(_LANES * 4 // itemsize,
                                 1 << (n - 1).bit_length()))
            block = (_round_up(n, tile),)
        padded = _round_up(block[0], sublanes * _LANES)
    else:
        rows, cols = shape
        lanes = _round_up(cols, _LANES)
        if sublanes * lanes <= budget:
            r = budget // lanes // sublanes * sublanes
            block = (min(rows, r), cols)
        else:
            block = (min(rows, sublanes),
                     budget // sublanes // _LANES * _LANES)
        padded = (_round_up(block[0], sublanes)
                  * _round_up(block[1], _LANES))
    return block, max(_SCOPED_VMEM, 2 * _BUFFERS * padded * 4)


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "interpret"))
def _fused(p, g, m, v, step, lr, b1, b2, eps, interpret):
    block, vmem = _block(p.shape, p.dtype)
    grid = tuple(pl.cdiv(n, b) for n, b in zip(p.shape, block))
    tile = pl.BlockSpec(block, lambda *i: i, memory_space=pltpu.VMEM)
    kernel = functools.partial(_kernel, lr=lr, b1=b1, b2=b2, eps=eps)
    corr = jnp.stack(_bias_corrections(jnp.asarray(step, jnp.float32),
                                       b1, b2))

    def make_call(interp):
        call = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                      tile, tile, tile, tile],
            out_specs=[tile, tile, tile],
            out_shape=[pltpu.HBM(x.shape, x.dtype) for x in (p, m, v)],
            compiler_params=pltpu.CompilerParams(vmem_limit_bytes=vmem),
            interpret=interp, name="fused_adam")
        if interp:
            return call
        # On the chip the leaves stay in HBM, in and out: left free, XLA
        # parks whole leaves in VMEM around the call (a 9 MB leaf fits
        # 128 MiB many times) and moves them there by asynchronous copies
        # of its own, so the kernel would stream from VMEM and its 28
        # bytes a parameter would be somebody else's.
        return lambda corr, *leaf: call(corr, *(
            pltpu.with_memory_space_constraint(x, pltpu.HBM) for x in leaf))

    return kernel_call(make_call, corr, p, g, m, v, interpret=interpret)


def _as_stored(x):
    """The 1-D or 2-D view of a leaf that the kernel blocks: a 1-D leaf
    as it is, a 2-D leaf as it is or transposed
    (:func:`_kept_transposed`), more dimensions with the leading ones
    collapsed (a copy on the chip unless the last but one is a multiple
    of the tile's rows)."""
    if _kept_transposed(x.shape):
        return x.T
    return x.reshape(x.shape if x.ndim == 1 else (-1, *x.shape[-1:]))


def adam_update(p, g, m, v, step, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                interpret: bool | None = None):
    """Adam step over one tensor via the Pallas kernel: p, g, m, v in,
    new p, m, v out, 28 bytes a float32 parameter.

    ``interpret=None`` follows the platform the program is lowered for:
    compiled for a TPU, interpreter elsewhere (the interpreter runs the
    identical kernel body, so CPU CI exercises the real code path).
    The kernel sees the leaf as the chip stores it (:func:`_as_stored`)
    and the block follows that view (:func:`_block`).
    """
    p, g, m, v = (jnp.asarray(x) for x in (p, g, m, v))
    out = _fused(*(_as_stored(x) for x in (p, g, m, v)), step, lr, b1, b2,
                 eps, interpret)
    return tuple(x.T if _kept_transposed(p.shape) else x.reshape(p.shape)
                 for x in out)


def adam_update_tree(params, grads, mu, nu, step, **hyper):
    """Pytree version: one fused kernel launch per leaf."""
    flat_p, tree = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_leaves(grads)
    flat_m = jax.tree_util.tree_leaves(mu)
    flat_v = jax.tree_util.tree_leaves(nu)
    out = [adam_update(p, g, m, v, step, **hyper)
           for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    unzip = lambda i: jax.tree_util.tree_unflatten(
        tree, [o[i] for o in out])
    return unzip(0), unzip(1), unzip(2)


def fused_adam(lr=1e-3, b1=0.9, b2=0.999, eps=1e-8):
    """The kernel as an ``optax.GradientTransformation`` — a drop-in for
    ``optax.adam`` anywhere the framework takes an optimizer (e.g.
    ``models.common.run_training(optimizer=fused_adam(1e-3))``).

    optax's contract returns *updates* rather than new params, so this
    wrapper computes ``p_new - p`` and the caller adds it back: one more
    pass over the parameters (12 bytes each) that XLA cannot fold away,
    ``(p_new - p) + p`` not being ``p_new`` in floating point. Callers
    that want the kernel's 28 bytes alone use :func:`adam_update_tree`
    directly.
    """
    import optax

    def init(params):
        zeros = lambda t: jax.tree_util.tree_map(jnp.zeros_like, t)
        return {"count": jnp.zeros([], jnp.float32),
                "mu": zeros(params), "nu": zeros(params)}

    def update(grads, state, params=None):
        if params is None:
            raise ValueError("fused_adam needs params")
        count = state["count"] + 1.0
        p_new, mu, nu = adam_update_tree(params, grads, state["mu"],
                                         state["nu"], step=count,
                                         lr=lr, b1=b1, b2=b2, eps=eps)
        updates = jax.tree_util.tree_map(lambda n, o: n - o, p_new, params)
        return updates, {"count": count, "mu": mu, "nu": nu}

    return optax.GradientTransformation(init, update)
