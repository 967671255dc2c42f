"""Pallas fused-Adam kernel vs the jnp reference and optax.

The kernel runs in interpreter mode on CPU — the same kernel body the
TPU compiles, so these tests pin the math, the block rule (whole rows of
the leaf as the chip stores it, a ragged edge block left to Pallas) and
the contract: p, g, m, v in, new p, m, v out.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp
import optax

from kubeshare_tpu.ops import fused_adam as fad
from kubeshare_tpu.ops.fused_adam import (adam_update,
                                          adam_update_reference,
                                          adam_update_tree)

#: Lanes beyond which one operand's eight rows pass the block's budget:
#: a leaf wider than this is cut in lanes as well as in rows.
WIDE = fad.BLOCK_BYTES // (8 * 4) + 1000

#: (shape, dtype, grid steps the block rule must give it). The layouts the
#: benchmark's cells have, at sizes the interpreter can run: the edge
#: block of every ragged case reads beyond the leaf.
CASES = [
    ((1024,), jnp.float32, (1,)),
    ((8, 128), jnp.float32, (1, 1)),
    ((37,), jnp.float32, (1,)),
    ((3, 5, 7), jnp.float32, (1, 1)),
    ((1003, 256), jnp.float32, (1, 1)),       # ragged rows, aligned lanes
    ((256, 1003), jnp.float32, (1, 1)),       # kept transposed on the chip
    ((50257,), jnp.float32, (1,)),            # the vocabulary bias
    ((2 * fad.BLOCK_BYTES // 4 + 77,), jnp.float32, (3,)),   # ragged 1-D
    ((20, WIDE), jnp.float32, (3, 2)),        # ragged in both directions
    ((2100, 256), jnp.float32, (3, 1)),       # ragged last block of rows
    ((3,), jnp.float32, (1,)),                # smaller than one tile
    ((2100, 256), jnp.bfloat16, (3, 1)),      # 16 sublanes a tile
    # rank 3, as a stack of experts' matrices: the leading axes collapse
    # into rows (a bitcast on the chip where the last but one is whole
    # tiles of rows), ragged last block of rows
    ((8, 520, 256), jnp.float32, (5, 1)),
    ((4, 16, 384), jnp.float32, (1, 1)),
    ((8, 130, 256), jnp.bfloat16, (2, 1)),
]


@pytest.mark.parametrize("shape,dtype,grid", CASES)
def test_kernel_matches_reference(shape, dtype, grid):
    rng = np.random.default_rng(0)
    p, g, m, v = (jnp.asarray(rng.normal(size=shape), dtype)
                  for _ in range(4))
    v = jnp.abs(v)
    got = adam_update(p, g, m, v, step=3, lr=1e-2)
    want = adam_update_reference(p, g, m, v, step=3, lr=1e-2)
    # float32 arithmetic either way; a bfloat16 leaf is rounded once on the
    # way out, where the jitted kernel's fused multiply-add and the eager
    # reference's two roundings land an ulp apart in 0.6% of the elements
    tol = 1e-6 if dtype == jnp.float32 else float(jnp.finfo(dtype).eps)
    for a, b in zip(got, want):
        assert a.shape == shape and a.dtype == dtype
        # the interpreter fills what an edge block reads beyond the leaf
        # with NaN: one leaking into a stored element shows here
        assert bool(jnp.isfinite(a).all())
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=tol, atol=tol)
    # the case is the layout its comment says: the grid the rule gives
    view = jax.eval_shape(fad._as_stored,
                          jax.ShapeDtypeStruct(shape, dtype)).shape
    block, _ = fad._block(view, dtype)
    assert tuple(-(-n // b) for n, b in zip(view, block)) == grid


def test_matches_optax_over_steps():
    """Several chained steps track optax.adam on the same trajectory."""
    rng = np.random.default_rng(1)
    p = rng.normal(size=(256,)).astype(np.float32)
    opt = optax.adam(1e-3, b1=0.9, b2=0.999, eps=1e-8)
    state = opt.init(jnp.asarray(p))
    p_opt = jnp.asarray(p)
    p_ker = jnp.asarray(p)
    m = jnp.zeros_like(p_ker)
    v = jnp.zeros_like(p_ker)
    for t in range(1, 6):
        g = jnp.asarray(rng.normal(size=p.shape).astype(np.float32))
        updates, state = opt.update(g, state, p_opt)
        p_opt = optax.apply_updates(p_opt, updates)
        p_ker, m, v = adam_update(p_ker, g, m, v, step=t)
        np.testing.assert_allclose(np.asarray(p_ker), np.asarray(p_opt),
                                   rtol=2e-5, atol=2e-6)


def test_tree_version_descends_loss():
    """The fused step actually optimizes a two-layer net's loss."""
    rng = np.random.default_rng(2)
    params = {"w1": rng.normal(size=(16, 32)).astype(np.float32) * 0.1,
              "w2": rng.normal(size=(32, 1)).astype(np.float32) * 0.1}
    x = rng.normal(size=(64, 16)).astype(np.float32)
    y = rng.normal(size=(64, 1)).astype(np.float32)

    def loss_fn(params):
        h = jnp.tanh(x @ params["w1"])
        return jnp.mean((h @ params["w2"] - y) ** 2)

    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses = []
    for t in range(1, 30):
        l, g = jax.value_and_grad(loss_fn)(params)
        params, mu, nu = adam_update_tree(params, g, mu, nu, step=t,
                                          lr=1e-2)
        losses.append(float(l))
    assert losses[-1] < 0.5 * losses[0]


def test_optax_wrapper_plugs_into_run_training():
    """fused_adam() drops into the shared train machinery as-is."""
    from kubeshare_tpu.models import mnist
    from kubeshare_tpu.models.common import run_training
    from kubeshare_tpu.ops.fused_adam import fused_adam

    res = run_training(mnist.init, mnist.loss_fn, mnist.batch_fn,
                       steps=8, optimizer=fused_adam(1e-3))
    assert res.steps == 8
    assert np.isfinite(res.final_loss)
