"""The LFM2-style decoder (``models/lfm2.py`` and the ops it brought)
against its plain reference (``benchmark/reference/lfm2.py``: float32,
``highest``, no kernels, a dense loop over the held experts) at tiny
sizes on the CPU, where the Pallas grouped products run in the
interpreter: the same kernel bodies the chip compiles.

Tolerances. With ``Config(dtype="float32")`` the program and the
reference compute the same float32 mathematics in another order (fused
q/k/v, sorted rows against a masked loop, three shifted adds against a
padded sum): they agree to float32 round-off, and every limit below is a
few dozen ulps of the quantity it bounds (``F32``). Parameters or matmuls
in bfloat16 (8 bits of mantissa: 4e-3 an operand) or a dropped or
mis-weighted expert (a term of order one in the layer's output) miss
these by two orders of magnitude; ``test_bfloat16_...`` shows the first.
"""

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models import MODEL_NAMES, get_model
from kubeshare_tpu.ops import attention as A
from kubeshare_tpu.ops import moe
from kubeshare_tpu.ops.flash_attention import flash_attention
from kubeshare_tpu.ops.shortconv import (causal_depthwise_conv,
                                         short_conv_apply, short_conv_init)

REPO = Path(__file__).resolve().parent.parent
F32 = 2e-5          # relative, of the largest element: ~170 float32 ulps


def _load(rel: str):
    path = REPO / rel
    spec = importlib.util.spec_from_file_location(
        "t_" + path.stem + "_" + path.parent.name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/lfm2.py")


@pytest.fixture(scope="module")
def binding():
    sys.path.insert(0, str(REPO / "benchmark"))
    return _load("benchmark/models/lfm2.py")


M = get_model("lfm2")

#: 1 dense + one period of 4, a share of 4 of 16 experts from the 4th, a
#: sliced vocabulary, 8 query heads on 2 kv heads: under the file's key names
TINY = {
    "hidden_size": 64, "num_attention_heads": 8, "num_key_value_heads": 2,
    "intermediate_size": 160, "moe_intermediate_size": 48,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1,
    "vocab_size": 128, "conv_L_cache": 3, "norm_eps": 1e-05,
    "max_position_embeddings": 128,
    "rope_parameters": {"rope_theta": 1000000},
    "published": {"num_experts": 16, "vocab_size": 1024},
    "deployment": {"first_expert": 4},
}
KEY = np.asarray([7, 2026], np.uint32)


def flat(tree) -> dict:
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path): leaf for path, leaf in paths}


def close(got, want, tol=F32):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    scale = max(np.abs(want).max(), 1e-30)
    assert got.shape == want.shape
    assert np.abs(got - want).max() <= tol * scale, (
        np.abs(got - want).max() / scale)


def batch(rows=2, seq=32, seed=3):
    tok = np.random.default_rng(seed).integers(
        0, TINY["vocab_size"], (rows, seq + 1)).astype(np.int32)
    return jnp.asarray(tok[:, :-1]), jnp.asarray(tok[:, 1:])


@pytest.fixture(scope="module")
def pair(ref, binding):
    """The program in float32 and the reference on the same seed."""
    cfg = dataclasses.replace(binding._config(TINY), dtype="float32")
    params = M.init(jnp.asarray(KEY), cfg)
    return cfg, params, ref.init(KEY, TINY)


# -- the model against the plain reference ------------------------------------

def test_init_redraws_the_same_leaves_under_the_same_names(pair):
    _, params, want = pair
    got = flat(params)
    assert sorted(got) == sorted(want)
    # the same draws; the reference's init is jitted, and XLA's fused
    # ``normal * 0.02`` lands an ulp from the eager product
    for name in want:
        np.testing.assert_allclose(np.asarray(got[name]),
                                   np.asarray(want[name]), rtol=1e-6,
                                   atol=0, err_msg=name)


@pytest.mark.parametrize("attn", ["dense", "flash"])
def test_logits_match_the_reference(pair, ref, attn):
    cfg, params, rp = pair
    tokens, _ = batch()
    fn = None if attn == "dense" else flash_attention
    got = M.apply(params, tokens, cfg, attn_fn=fn)
    close(got, ref.logits_fn(rp, tokens, TINY))


def test_loss_and_every_leafs_gradient_match_the_reference(pair, ref):
    cfg, params, rp = pair
    tokens, targets = batch()
    loss, grads = jax.value_and_grad(M.loss_fn)(
        params, (tokens, targets), cfg, attn_fn=flash_attention, remat=True)
    want_loss, want = ref.loss_and_grads(rp, tokens, targets, TINY)
    assert abs(float(loss) - float(want_loss)) <= F32 * float(want_loss)
    got = flat(grads)
    assert sorted(got) == sorted(want)
    # a leaf's gradient against the largest leaf's: gains and taps are
    # small sums of many terms, and float32 cancellation is of the terms
    biggest = max(float(jnp.abs(g).max()) for g in want.values())
    for name, g in want.items():
        err = float(jnp.abs(got[name] - g).max())
        assert err <= 10 * F32 * max(float(jnp.abs(g).max()),
                                     1e-2 * biggest), (name, err)
    assert float(jnp.abs(got["layers/1/moe/expert_bias"]).max()) == 0.0


def test_bfloat16_parameters_would_fail_the_same_comparison(pair, ref):
    """What the tolerance is for: the reference's own weights rounded to
    bfloat16 miss the float32 logits by a hundred times the limit."""
    _, _, rp = pair
    tokens, _ = batch()
    want = ref.logits_fn(rp, tokens, TINY)
    rounded = {k: v.astype(jnp.bfloat16).astype(jnp.float32)
               for k, v in rp.items()}
    got = ref.logits_fn(rounded, tokens, TINY)
    worst = float(jnp.abs(got - want).max() / jnp.abs(want).max())
    assert worst > 50 * F32


def test_the_default_bfloat16_model_stays_near_the_reference(ref, binding):
    """bfloat16 matmuls and residual stream: 8 bits of mantissa through
    five layers; the loss (a mean over 64 tokens) holds to 1e-3."""
    cfg = binding._config(TINY)
    params = M.init(jnp.asarray(KEY), cfg)
    tokens, targets = batch()
    loss = M.loss_fn(params, (tokens, targets), cfg)
    want, _ = ref.loss_and_grads(ref.init(KEY, TINY), tokens, targets, TINY)
    assert abs(float(loss) - float(want)) <= 1e-3 * float(want)


def test_zoo_contract_no_environment_read_no_module_constant_set():
    import re
    assert "lfm2" in MODEL_NAMES
    src = (REPO / "kubeshare_tpu/models/lfm2.py").read_text()
    assert not re.search(r"os\.environ|getenv|^import os", src, re.M)
    bound = (REPO / "benchmark/models/lfm2.py").read_text()
    assert not re.search(r"^\s*(?:M|zoo)\.\w+\s*=[^=]", bound, re.M)
    params = M.init(jax.random.PRNGKey(0))
    loss = M.loss_fn(params, M.batch_fn(jax.random.PRNGKey(1)))
    assert np.isfinite(float(loss))
    with pytest.raises(ValueError, match="unknown layer types"):
        M.Config(layer_types=("conv", "window"))
    with pytest.raises(ValueError, match="not among"):
        M.Config(experts=16, experts_held=8, first_expert=12)


def test_the_benchmark_configuration_counts_the_programs_leaves(binding):
    cfg = json.loads((REPO / "benchmark/configs/lfm2-24b-a2b.json")
                     .read_text())
    shapes = jax.eval_shape(binding.init(cfg),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    leaves = flat(shapes)
    assert sum(int(np.prod(s.shape)) for s in leaves.values()) \
        == cfg["parameters_as_run"] == 469_285_248
    assert leaves["layers/1/moe/w1"].shape == (8, 2048, 1536)
    assert leaves["layers/1/moe/router"].shape == (2048, 64)
    assert leaves["layers/1/attn/qkv"].shape == (2048, 2048 + 2 * 512)
    assert leaves["layers/0/mlp/w1"].shape == (2048, 11776)
    assert leaves["embed"].shape == (8192, 2048)
    counts = _load("benchmark/counts/lfm2.py")
    # the matrices that surely multiply: the held experts' are left out
    # (how many rows reach them is the run's own: a floor)
    mult = sum(int(np.prod(s.shape)) for n, s in leaves.items()
               if s.ndim >= 2 and "/moe/w" not in n and "/conv/conv" not in n)
    assert counts.multiplying_params(cfg) == mult
    assert counts.attention_layers(cfg) == 1


# -- the expert layer ----------------------------------------------------------

def _layer_params(dim=32, width=48, experts=16, seed=5):
    p = moe.topk_moe_init(jax.random.PRNGKey(seed), dim, width, experts)
    return dict(p, expert_bias=jax.random.normal(
        jax.random.PRNGKey(seed + 1), (experts,)) * 0.05)


def _share(p, first, held):
    cut = slice(first, first + held)
    return dict(p, w1=p["w1"][cut], w3=p["w3"][cut], w2=p["w2"][cut])


def _uncut(ref, p, x, top_k):
    """The reference's expert layer over ALL the experts."""
    c = {"top_k": top_k, "scaling": 1.0, "held": p["w1"].shape[0],
         "first": 0}
    flatp = {f"l/moe/{k}": v for k, v in p.items()}
    return jnp.stack([ref._experts_ff(flatp, "l", row, c, None)
                      for row in x])


def test_the_eight_shares_of_a_layer_add_up_to_the_uncut_reference(ref):
    p = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(9), (2, 24, 32))
    parts = [moe.topk_moe_apply(_share(p, first, 2), x, 4, first)
             for first in range(0, 16, 2)]
    close(sum(parts), _uncut(ref, p, x, 4))
    # and no share is the whole: each adds something
    assert all(float(jnp.abs(part).max()) > 0 for part in parts)


def _everyone_picks(p, experts):
    """A router that scores every expert alike and a bias that selects
    ``experts`` for every token."""
    bias = jnp.full_like(p["expert_bias"], -1.0).at[jnp.asarray(experts)].set(
        1.0)
    return dict(p, router=jnp.zeros_like(p["router"]), expert_bias=bias)


def test_nothing_is_dropped_when_every_token_picks_the_same_four_held(ref):
    p = _everyone_picks(_layer_params(), [4, 5, 6, 7])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    r = moe.topk_route(_share(p, 4, 4), x.reshape(-1, 32), 4, first_held=4)
    assert r.group_sizes.tolist() == [48, 48, 48, 48]  # every pair a row
    assert sorted(r.position.reshape(-1).tolist()) == list(range(192))
    close(r.weights, jnp.full((48, 4), 0.25), 1e-5)
    close(moe.topk_moe_apply(_share(p, 4, 4), x, 4, 4), _uncut(ref, p, x, 4))


def test_a_share_none_of_whose_experts_is_picked_adds_exact_zeros():
    p = _everyone_picks(_layer_params(), [0, 1, 2, 3])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, 32))
    share = _share(p, 8, 4)
    r = moe.topk_route(share, x.reshape(-1, 32), 4, first_held=8)
    assert r.group_sizes.tolist() == [0, 0, 0, 0]
    assert not bool(r.here.any()) and float(r.weights.sum()) == 0.0
    out = moe.topk_moe_apply(share, x, 4, 8)
    assert float(jnp.abs(out).max()) == 0.0
    grads = jax.grad(lambda q: moe.topk_moe_apply(q, x, 4, 8).sum())(share)
    assert all(float(jnp.abs(g).max()) == 0.0 for g in grads.values())


def test_expert_bias_changes_who_is_picked_and_not_the_weights():
    p = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(4), (40, 32))
    scores = jax.nn.sigmoid(x @ p["router"])

    def picked(bias):
        q = dict(p, expert_bias=bias)
        _, sel = jax.lax.top_k(scores + bias, 4)
        return sel, moe.topk_route(q, x, 4).weights

    plain, w0 = picked(jnp.zeros(16))
    tilted, w1 = picked(jnp.zeros(16).at[3].set(10.0))
    assert bool((tilted == 3).any(-1).all()) and not bool(
        (plain == 3).any(-1).all())
    # the weights are the UNBIASED scores of whoever was picked
    for sel, w in ((plain, w0), (tilted, w1)):
        s = jnp.take_along_axis(scores, sel, -1)
        close(w, s / (s.sum(-1, keepdims=True) + 1e-6), 1e-5)
    assert float(w1.max()) < 1.0        # a bias of 10 weighs nothing


@pytest.mark.parametrize("sizes", [[5, 0, 11, 8], [0, 0, 0, 0],
                                   [64, 0, 0, 0], [16, 16, 16, 16],
                                   [0, 0, 0, 3]])
def test_grouped_product_and_its_gradient_match_the_masked_dense_loop(sizes):
    """``grouped_matmul`` through the interpreter (the kernel body the chip
    compiles) against a dense loop with a row mask a group. The counts may
    sum to fewer than the rows: the rows beyond belong to no held group,
    are not read and come back unwritten (whatever the buffer held: the
    comparison is over the rows of the groups, and the rows beyond must
    leave the matrices' gradient alone whatever their cotangent is)."""
    rng = np.random.default_rng(0)
    lhs = jnp.asarray(rng.normal(size=(64, 24)), jnp.float32)
    rhs = jnp.asarray(rng.normal(size=(4, 24, 40)), jnp.float32)
    gs = jnp.asarray(sizes, jnp.int32)
    ends = np.cumsum(sizes)

    def dense(lhs, rhs):
        row = jnp.arange(64)[:, None]
        return sum(jnp.where((row >= ends[g] - sizes[g]) & (row < ends[g]),
                             lhs @ rhs[g], 0.0) for g in range(4))

    read = (jnp.arange(64) < ends[-1])[:, None]

    def grouped(a, b):
        return jnp.where(read, moe.grouped_matmul(a, b, gs), 0.0)

    close(grouped(lhs, rhs), dense(lhs, rhs))
    cot = jnp.asarray(rng.normal(size=(64, 40)), jnp.float32)
    _, back = jax.vjp(lambda a, b: moe.grouped_matmul(a, b, gs), lhs, rhs)
    d_lhs, d_rhs = back(cot)        # the rows beyond get a cotangent too
    want = jax.grad(lambda a, b: (dense(a, b) * cot).sum(),
                    argnums=(0, 1))(lhs, rhs)
    close(jnp.where(read, d_lhs, 0.0), want[0])
    close(d_rhs, want[1])
    assert d_rhs.dtype == rhs.dtype and bool(jnp.isfinite(d_rhs).all())


def test_tiles_of_the_grouped_products_at_the_published_widths():
    assert moe._tiling(32768, 2048, 1536) == (512, 1024, 768)
    assert moe._tiling(32768, 1536, 2048) == (512, 768, 1024)
    assert moe._tiling(128, 32, 48) == (128, 32, 48)


# -- the row moves between token order and the sorted buffer (moe_rows) -------
#
# The parent's moves with ``jnp.take``, kept here as the reference: each
# kernel must give the same rows, bit for bit where a row is copied, and
# the same sums in the same order of choices (to float32 round-off, which
# already differs between two compiles of the reference itself).

def _take_rows_of(buffer, r, j):
    rows = jnp.take(buffer, r.position[:, j], axis=0)
    return jnp.where(r.here[:, j, None], rows.astype(jnp.float32), 0.0)


def _take_moves(x, ys, g, gx, r):
    k = r.position.shape[1]
    by_row = jnp.take(r.weights.reshape(-1), r.order)
    return {
        "dispatch": jnp.take(x, r.order // k, axis=0),
        "combine": sum(r.weights[:, j, None] * _take_rows_of(ys, r, j)
                       for j in range(k)),
        "d_ys": (jnp.take(g, r.order // k, axis=0)
                 * by_row[:, None]).astype(ys.dtype),
        "d_w": jnp.stack([(_take_rows_of(ys, r, j) * g).sum(-1)
                          for j in range(k)], 1),
        "d_x": sum(_take_rows_of(gx, r, j)
                   for j in range(k)).astype(gx.dtype)}


def _kernel_moves(x, ys, g, gx, r):
    d_ys, d_w, _ = moe._combine_bwd((ys, r.weights, r), g)
    return {"dispatch": moe.dispatch(x, r),
            "combine": moe.combine(ys, r.weights, r),
            "d_ys": d_ys, "d_w": d_w, "d_x": moe._dispatch_bwd(r, gx)[0]}


def _routing_with(here, seed=0, held=8):
    """A routing whose held pairs are ``here`` (tokens, top_k), a token's
    choices of distinct random held experts, laid out as ``topk_route``
    lays them out (by expert, each expert's rows in pair order, the pairs
    not held last)."""
    rng = np.random.default_rng(seed)
    tokens, top_k = here.shape
    expert = np.argsort(rng.random((tokens, held)), 1)[:, :top_k]
    group = np.where(here, expert, held).reshape(-1)
    order = np.argsort(group, kind="stable")
    weights = np.where(here, rng.uniform(0.1, 1.0, here.shape), 0.0)
    return moe.Routing(
        jnp.asarray(order, jnp.int32),
        jnp.asarray(np.argsort(order).reshape(here.shape), jnp.int32),
        jnp.asarray(here), jnp.asarray(weights, jnp.float32),
        jnp.asarray(np.bincount(group, minlength=held + 1)[:held],
                    jnp.int32))


TOKENS, DIM = 320, 256      # token blocks of 160, buffer blocks of 256 rows


def _here_of(present, seed=1):
    """``present`` held pairs at random places, or a random mask."""
    rng = np.random.default_rng(seed)
    if present == "mask":
        return rng.random((TOKENS, 4)) < 0.125
    here = np.zeros(TOKENS * 4, bool)
    here[rng.choice(TOKENS * 4, present, replace=False)] = True
    return here.reshape(TOKENS, 4)


def _move_inputs(r, seed=2):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    rows = TOKENS * 4
    return (jax.random.normal(ks[0], (TOKENS, DIM)).astype(jnp.bfloat16),
            jax.random.normal(ks[1], (rows, DIM)).astype(jnp.bfloat16),
            jax.random.normal(ks[2], (TOKENS, DIM)),
            jax.random.normal(ks[3], (rows, DIM)).astype(jnp.bfloat16))


@pytest.mark.parametrize("present", [0, 1, 255, 256, 257, TOKENS * 4,
                                     "mask"])
def test_row_moves_match_the_take_reference_on_every_row_read(present):
    """0 rows, 1, one on each side of a buffer block's edge (256), every
    pair held, and a random eighth: ``dispatch`` and ``combine`` and both
    their ways back against the ``jnp.take`` moves they replace. Rows of
    the buffer past ``sum(group_sizes)`` are read by nobody and compared
    by nobody."""
    r = _routing_with(_here_of(present))
    n = int(r.group_sizes.sum())
    assert n == int(r.here.sum())
    got = _kernel_moves(*_move_inputs(r), r)
    want = _take_moves(*_move_inputs(r), r)
    for name in ("dispatch", "d_ys"):           # copies: bit for bit
        np.testing.assert_array_equal(
            np.asarray(got[name][:n], np.float32),
            np.asarray(want[name][:n], np.float32))
    for name in ("combine", "d_w", "d_x"):      # sums, in the same order
        close(got[name], want[name], 1e-6)
    none = ~np.asarray(r.here).any(-1)          # tokens with no held pair
    assert float(jnp.abs(got["combine"][none]).max(initial=0)) == 0.0
    assert float(jnp.abs(got["d_x"][none]).max(initial=0)) == 0.0
    assert float(jnp.abs(got["d_w"][~np.asarray(r.here)]).max(
        initial=0)) == 0.0


@pytest.mark.parametrize("present", [1, TOKENS * 4, "mask"])
def test_row_moves_past_what_one_kernel_call_holds_match_the_take_reference(
        present, monkeypatch):
    """VMEM for fewer tokens' rows than there are: the buffer side's moves
    go by XLA's row gathers, and still give every row read what the
    reference gives."""
    monkeypatch.setattr(moe, "RESIDENT_BYTES", 64 * DIM * 2)
    r = _routing_with(_here_of(present, seed=3), seed=3)
    n = int(r.group_sizes.sum())
    got = _kernel_moves(*_move_inputs(r), r)
    want = _take_moves(*_move_inputs(r), r)
    for name in ("dispatch", "d_ys"):
        np.testing.assert_array_equal(
            np.asarray(got[name][:n], np.float32),
            np.asarray(want[name][:n], np.float32))
    for name in ("combine", "d_w", "d_x"):
        close(got[name], want[name], 1e-6)


def test_a_token_count_off_the_band_is_padded_and_adds_up_to_the_reference(
        ref):
    """21 tokens, not a multiple of the 16 the moves work in: the layer
    pads them with pairs held nowhere, and its eight shares, and their
    gradients against the input, still add up to the uncut layer's."""
    p = _layer_params()
    x = jax.random.normal(jax.random.PRNGKey(11), (3, 7, 32))
    shares = [_share(p, first, 2) for first in range(0, 16, 2)]
    total = lambda x: sum(moe.topk_moe_apply(q, x, 4, 2 * i)  # noqa: E731
                          for i, q in enumerate(shares))
    close(total(x), _uncut(ref, p, x, 4))
    close(jax.grad(lambda x: (total(x) ** 2).sum())(x),
          jax.grad(lambda x: (_uncut(ref, p, x, 4) ** 2).sum())(x))


def test_no_row_of_a_pair_not_held_reaches_a_result():
    """NaN in every row nothing should read: the tokens with no held
    choice (their input and their cotangent) and the buffer's rows past
    ``sum(group_sizes)`` (the expert results and their cotangent there).
    Every result the held pairs make stays finite and equal to the
    reference's over the same, NaN-free, rows."""
    r = _routing_with(_here_of("mask", seed=5), seed=5)
    n = int(r.group_sizes.sum())
    x, ys, g, gx = _move_inputs(r, seed=6)
    none = jnp.asarray(~np.asarray(r.here).any(-1))[:, None]
    tail = (jnp.arange(TOKENS * 4) >= n)[:, None]
    poisoned = (jnp.where(none, jnp.nan, x).astype(x.dtype),
                jnp.where(tail, jnp.nan, ys).astype(ys.dtype),
                jnp.where(none, jnp.nan, g),
                jnp.where(tail, jnp.nan, gx).astype(gx.dtype))
    got = _kernel_moves(*poisoned, r)
    want = _take_moves(x, ys, g, gx, r)
    held = ~np.asarray(none)[:, 0]
    for name, rows in (("dispatch", slice(0, n)), ("d_ys", slice(0, n)),
                       ("combine", held), ("d_x", held), ("d_w", held)):
        part = np.asarray(got[name][rows], np.float32)
        assert np.isfinite(part).all(), name
        close(part, np.asarray(want[name][rows], np.float32), 1e-6)


# -- the short convolution -----------------------------------------------------

def test_convolution_is_causal():
    p = short_conv_init(jax.random.PRNGKey(0), 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    bumped = x.at[:, 7].add(1.0)
    a, b = short_conv_apply(p, x), short_conv_apply(p, bumped)
    np.testing.assert_array_equal(np.asarray(a[:, :7]), np.asarray(b[:, :7]))
    assert float(jnp.abs(a[:, 7:10] - b[:, 7:10]).min(axis=(0, 2)).min()) > 0
    np.testing.assert_array_equal(np.asarray(a[:, 10:]),
                                  np.asarray(b[:, 10:]))    # three taps


def test_convolution_equals_a_left_padded_conv1d_of_length_three():
    z = jax.random.normal(jax.random.PRNGKey(1), (2, 12, 16))
    taps = jax.random.normal(jax.random.PRNGKey(2), (3, 16))
    want = jax.lax.conv_general_dilated(
        z, taps[:, None, :], window_strides=(1,), padding=[(2, 0)],
        dimension_numbers=("NWC", "WIO", "NWC"), feature_group_count=16,
        precision=jax.lax.Precision.HIGHEST)
    close(causal_depthwise_conv(z, taps), want)


# -- attention: q/k norm before RoPE, 4 query heads a kv head --------------------

def _attn_params(dim=64, heads=8, kv_heads=2):
    p = A.gqa_init(jax.random.PRNGKey(0), dim, heads, kv_heads)
    hd = dim // heads
    return dict(p, q_norm=1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(1), (hd,)), k_norm=1.0 + 0.3 * jax.random.normal(
        jax.random.PRNGKey(2), (hd,)))


def _by_hand(p, x, heads, norm_first: bool, base=1e6, eps=1e-5):
    b, s, dim = x.shape
    hd = dim // heads
    kvd = (p["qkv"].shape[1] - dim) // 2
    qkv = x @ p["qkv"]
    q = qkv[..., :dim].reshape(b, s, heads, hd)
    k = qkv[..., dim:dim + kvd].reshape(b, s, kvd // hd, hd)
    v = qkv[..., dim + kvd:].reshape(b, s, kvd // hd, hd)
    def norm(x, gain):      # over each head's own features
        xf = x.astype(jnp.float32)
        return xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True)
                                  + eps) * gain

    if norm_first:
        q = A.rope(norm(q, p["q_norm"]), base=base)
        k = A.rope(norm(k, p["k_norm"]), base=base)
    else:
        q = norm(A.rope(q, base=base), p["q_norm"])
        k = norm(A.rope(k, base=base), p["k_norm"])
    k, v = A.expand_kv(k, v, heads)     # every query head its own copy
    o = A.dot_product_attention(q, k, v, causal=True)
    return o.reshape(b, s, dim) @ p["out"]


def test_qk_norm_sits_before_rope():
    p = _attn_params()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 16, 64))
    got = A.gqa_apply(p, x, 8, rope_base=1e6)
    close(got, _by_hand(p, x, 8, norm_first=True))
    after = _by_hand(p, x, 8, norm_first=False)
    assert float(jnp.abs(got - after).max()) > 1e-2 * float(
        jnp.abs(got).max())


@pytest.mark.parametrize("attn_fn", [None, flash_attention],
                         ids=["dense", "flash"])
def test_four_query_heads_share_a_kv_head_as_expanded_k_v_would(attn_fn):
    p = _attn_params()
    x = jax.random.normal(jax.random.PRNGKey(3), (2, 32, 64))
    got = A.gqa_apply(p, x, 8, attn_fn=attn_fn, rope_base=1e6)
    close(got, _by_hand(p, x, 8, norm_first=True))
