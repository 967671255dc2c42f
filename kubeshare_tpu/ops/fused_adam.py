"""Fused Adam update as a Pallas TPU kernel.

The optimizer update is the HBM-bandwidth-bound op of every training
step: it streams four arrays in (params, grads, m, v) and three out.
Left to the reference's stack this is a torch/CUDA `foreach` kernel; the
TPU-native answer is one Pallas pass — every tensor is read exactly once
from HBM and the three outputs alias their inputs, so the kernel adds no
allocation at all (``input_output_aliases``).

XLA usually fuses the optax chain well on its own; this kernel exists
for the cases it doesn't (long chains interleaved with collectives) and
as the framework's demonstration of the Pallas path for hot ops. The
kernel is compiled (Mosaic) where the program is lowered for a TPU and
interpreted elsewhere (:mod:`.kernelcall`), so CPU CI covers the same
kernel body bit-for-bit; nothing drops to the ``jnp`` reference.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from .kernelcall import kernel_call

# Tiles: float32 min tile is (8, 128); one row-block of 1024 lanes keeps
# the kernel shape-agnostic after the pad-and-reshape below.
_LANES = 128
_ROWS = 8


def _bias_corrections(t, b1, b2):
    """``(1 - b1^t, 1 - b2^t)`` — computed by XLA OUTSIDE the kernel: the
    chip's kernel compiler has no scalar ``pow`` (Mosaic: "failed to
    legalize operation 'math.powf'"), and two scalars per step are not
    worth a vector exp/log inside it."""
    return 1.0 - b1 ** t, 1.0 - b2 ** t


def _adam_math(p, g, m, v, c1, c2, lr, b1, b2, eps):
    """One Adam step (bias-corrected, Kingma & Ba 2014) given the two
    bias corrections — shared by the kernel body and the reference so
    they cannot drift."""
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * (g * g)
    m_hat = m_new / c1
    v_hat = v_new / c2
    p_new = p - lr * m_hat / (jnp.sqrt(v_hat) + eps)
    return p_new, m_new, v_new


def adam_update_reference(p, g, m, v, step, lr=1e-3, b1=0.9, b2=0.999,
                          eps=1e-8):
    """Pure-jnp Adam step; ``step`` is the 1-based step count."""
    c1, c2 = _bias_corrections(jnp.asarray(step, p.dtype), b1, b2)
    return _adam_math(p, g, m, v, c1, c2, lr, b1, b2, eps)


def _kernel(corr_ref, p_ref, g_ref, m_ref, v_ref,
            p_out, m_out, v_out, *, lr, b1, b2, eps):
    c1 = corr_ref[0].astype(p_ref.dtype)
    c2 = corr_ref[1].astype(p_ref.dtype)
    p_new, m_new, v_new = _adam_math(
        p_ref[:], g_ref[:], m_ref[:], v_ref[:], c1, c2, lr, b1, b2, eps)
    p_out[:] = p_new
    m_out[:] = m_new
    v_out[:] = v_new


@functools.partial(jax.jit, static_argnames=("lr", "b1", "b2", "eps",
                                             "interpret"))
def _fused_flat(p, g, m, v, step, lr, b1, b2, eps, interpret):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n = p.shape[0]
    block = _ROWS * _LANES
    pad = (-n) % block
    def shape2d(x):
        return jnp.pad(x, (0, pad)).reshape(-1, _LANES)
    p2, g2, m2, v2 = (shape2d(x) for x in (p, g, m, v))
    rows = p2.shape[0]
    grid = (rows // _ROWS,)

    tile = pl.BlockSpec((_ROWS, _LANES), lambda i: (i, 0),
                        memory_space=pltpu.VMEM)
    out_shape = [jax.ShapeDtypeStruct(p2.shape, p2.dtype)] * 3
    kernel = functools.partial(_kernel, lr=lr, b1=b1, b2=b2, eps=eps)
    corr = jnp.stack(_bias_corrections(jnp.asarray(step, jnp.float32),
                                       b1, b2))
    p3, m3, v3 = kernel_call(lambda interp: pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[pl.BlockSpec(memory_space=pltpu.SMEM),
                  tile, tile, tile, tile],
        out_specs=[tile, tile, tile],
        out_shape=out_shape,
        # p, m, v update in place: zero extra HBM for the step
        input_output_aliases={1: 0, 3: 1, 4: 2},
        interpret=interp, name="fused_adam",
    ), corr, p2, g2, m2, v2, interpret=interpret)
    unpad = lambda x: x.reshape(-1)[:n]
    return unpad(p3), unpad(m3), unpad(v3)


def adam_update(p, g, m, v, step, lr=1e-3, b1=0.9, b2=0.999, eps=1e-8,
                interpret: bool | None = None):
    """Adam step over one tensor via the Pallas kernel.

    ``interpret=None`` follows the platform the program is lowered for:
    compiled for a TPU, interpreter elsewhere (the interpreter runs the
    identical kernel body, so CPU CI exercises the real code path).
    Arbitrary shapes are flattened, padded to the (8, 128) float32 tile,
    and restored.
    """
    shape = p.shape
    flat = lambda x: jnp.asarray(x).reshape(-1)
    p2, m2, v2 = _fused_flat(flat(p), flat(g), flat(m), flat(v),
                             step, lr, b1, b2, eps, interpret)
    return p2.reshape(shape), m2.reshape(shape), v2.reshape(shape)


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
    wrapper computes ``p_new - p`` — XLA folds the subtract/add pair away
    under jit; callers that want the strictly zero-copy path use
    :func:`adam_update_tree` directly.
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
