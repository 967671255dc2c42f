"""Pallas flash-attention kernel vs the dense reference (interpreter
mode on CPU — identical kernel body to the TPU path)."""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.ops.attention import dot_product_attention, mha_apply, mha_init
from kubeshare_tpu.ops.flash_attention import flash_attention

fa = importlib.import_module("kubeshare_tpu.ops.flash_attention")

# compile-heavy float32 cases: excluded from the default lane; the tile
# rule's table and the bfloat16 cases below run in it
slow = pytest.mark.slow


def qkv(b=2, s=64, h=2, d=16, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 3)
    return tuple(jax.random.normal(k, (b, s, h, d), jnp.float32)
                 for k in keys)


@slow
@pytest.mark.parametrize("causal", [True, False])
def test_flash_matches_dense(causal):
    q, k, v = qkv()
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@slow
def test_flash_multiple_block_shapes():
    q, k, v = qkv(s=64)
    ref = dot_product_attention(q, k, v)
    for bq, bk in ((8, 32), (32, 8), (64, 64)):
        out = flash_attention(q, k, v, block_q=bq, block_k=bk)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=1e-5, rtol=1e-5,
                                   err_msg=f"bq={bq} bk={bk}")


@slow
@pytest.mark.parametrize("causal", [True, False])
def test_flash_gradients_match_dense(causal):
    q, k, v = qkv(s=32)

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, causal=causal) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, causal=causal,
                                block_q=16, block_k=16) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@slow
def test_flash_gradients_asymmetric_blocks():
    """The dQ pass loops k blocks, the dK/dV pass loops q blocks — bq≠bk
    exercises both block indexers against the dense reference."""
    q, k, v = qkv(s=64)

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v) * 0.5).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for bq, bk in ((8, 32), (32, 8)):
        def loss_flash(q, k, v):
            return (flash_attention(q, k, v, block_q=bq, block_k=bk)
                    * 0.5).sum()
        g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
        for a, b in zip(g_out, g_ref):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=1e-4, rtol=1e-4,
                                       err_msg=f"bq={bq} bk={bk}")


@slow
def test_flash_gradient_dtypes_match_primals():
    """custom_vjp cotangents must come back in the primal dtypes (bf16
    params train without an accidental fp32 upcast in the grads)."""
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv(s=32))
    g = jax.grad(lambda q, k, v: flash_attention(
        q, k, v, block_q=16, block_k=16).sum(), argnums=(0, 1, 2))(q, k, v)
    assert all(a.dtype == jnp.bfloat16 for a in g)


@slow
@pytest.mark.parametrize("kv_heads", [1, 2])
@pytest.mark.parametrize("causal", [True, False])
def test_flash_gqa_matches_dense(causal, kv_heads):
    """Grouped-query (kv_heads=2) and multi-query (kv_heads=1): the
    kernel maps each q head's programs onto its group's k/v rows."""
    q, _, _ = qkv(h=4)
    keys = jax.random.split(jax.random.PRNGKey(7), 2)
    k, v = (jax.random.normal(kk, (2, 64, kv_heads, 16), jnp.float32)
            for kk in keys)
    ref = dot_product_attention(q, k, v, causal=causal)
    out = flash_attention(q, k, v, causal=causal, block_q=16, block_k=16)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@slow
def test_flash_gqa_gradients_match_dense():
    """dK/dV must group-sum the per-q-head partials exactly."""
    q, _, _ = qkv(s=32, h=4)
    keys = jax.random.split(jax.random.PRNGKey(8), 2)
    k, v = (jax.random.normal(kk, (2, 32, 2, 16), jnp.float32)
            for kk in keys)

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=16, block_k=16) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@slow
def test_flash_gqa_rejects_ragged_heads():
    q, _, _ = qkv(h=4)
    k = v = jax.random.normal(jax.random.PRNGKey(9), (2, 64, 3, 16))
    with pytest.raises(ValueError, match="divisible by kv_heads"):
        flash_attention(q, k, v, block_q=16, block_k=16)


@slow
def test_flash_rejects_ragged_blocks():
    q, k, v = qkv(s=48)
    with pytest.raises(ValueError, match="divisible"):
        flash_attention(q, k, v, block_q=32, block_k=32)


@slow
def test_gqa_mha_flash_matches_dense_path():
    """A grouped-query MHA block (kv_heads from the weight shape) runs
    both attention bodies on the SAME params — kernel vs reference."""
    params = mha_init(jax.random.PRNGKey(0), dim=32, heads=4, kv_heads=2)
    assert params["qkv"].shape == (32, 32 + 2 * 16)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    dense = mha_apply(params, x, heads=4)
    out = mha_apply(params, x, heads=4,
                    attn_fn=lambda q, k, v: flash_attention(
                        q, k, v, block_q=16, block_k=16))
    assert out.shape == (2, 32, 32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


@slow
def test_flash_plugs_into_mha():
    params = mha_init(jax.random.PRNGKey(0), dim=32, heads=2)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 32, 32))
    dense = mha_apply(params, x, heads=2)
    out = mha_apply(params, x, heads=2,
                    attn_fn=lambda q, k, v: flash_attention(
                        q, k, v, block_q=16, block_k=16))
    np.testing.assert_allclose(np.asarray(out), np.asarray(dense),
                               atol=1e-4, rtol=1e-4)


@slow
@pytest.mark.parametrize("window", [1, 5, 16, 40, 64])
def test_flash_sliding_window_matches_dense(window):
    """Band widths below/at/above the block size, including the full
    sequence (window >= seq == plain causal)."""
    q, k, v = qkv()
    ref = dot_product_attention(q, k, v, causal=True, window=window)
    out = flash_attention(q, k, v, block_q=16, block_k=16, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@slow
def test_flash_sliding_window_gradients_match_dense():
    q, k, v = qkv(s=32)

    def loss_ref(q, k, v):
        return (dot_product_attention(q, k, v, window=7) ** 2).sum()

    def loss_flash(q, k, v):
        return (flash_attention(q, k, v, block_q=8, block_k=8,
                                window=7) ** 2).sum()

    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


@slow
def test_flash_sliding_window_with_gqa():
    q, _, _ = qkv(h=4)
    keys = jax.random.split(jax.random.PRNGKey(11), 2)
    k, v = (jax.random.normal(kk, (2, 64, 2, 16), jnp.float32)
            for kk in keys)
    ref = dot_product_attention(q, k, v, causal=True, window=10)
    out = flash_attention(q, k, v, block_q=16, block_k=16, window=10)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=1e-5, rtol=1e-5)


@slow
def test_flash_window_requires_causal():
    q, k, v = qkv()
    with pytest.raises(ValueError, match="causal"):
        flash_attention(q, k, v, causal=False, window=8,
                        block_q=16, block_k=16)
    with pytest.raises(ValueError, match=">= 1"):
        flash_attention(q, k, v, window=0, block_q=16, block_k=16)


# -- bfloat16 operands, float32 softmax --------------------------------------
# The kernel hands the MXU its inputs' dtype. Against the dense reference
# on the SAME bfloat16 values: a float32-operand kernel differs by the
# bfloat16 rounding of its gradient outputs alone (~2e-3 of the largest
# value; 2e-7 on the float32 output) and passes these by 10x; a dropped
# 1/√d, or a dQ / dK scaled twice or not at all, is off by 8x at d=64.

BF16_CASES = {
    # derived tiles: 128 rows is one tile a head
    "derived-single-tile": dict(s=128, h=2, hk=2, blocks=None, window=None),
    "multi-tile-causal": dict(s=64, h=2, hk=2, blocks=(16, 32), window=None),
    "multi-tile-window": dict(s=64, h=2, hk=2, blocks=(16, 16), window=24),
    "multi-tile-gqa": dict(s=64, h=4, hk=2, blocks=(32, 16), window=None),
}


def _gap(got, ref):
    ref = np.asarray(ref, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - ref).max()
                 / np.abs(ref).max())


@pytest.mark.parametrize("case", BF16_CASES)
def test_flash_bf16_operands_match_dense_on_the_same_values(case):
    c = BF16_CASES[case]
    keys = jax.random.split(jax.random.PRNGKey(3), 4)
    q = jax.random.normal(keys[0], (1, c["s"], c["h"], 64)).astype(jnp.bfloat16)
    k, v = (jax.random.normal(kk, (1, c["s"], c["hk"], 64)).astype(jnp.bfloat16)
            for kk in keys[1:3])
    w = jax.random.normal(keys[3], q.shape)     # a generic output cotangent
    blocks = ({} if c["blocks"] is None
              else dict(block_q=c["blocks"][0], block_k=c["blocks"][1]))

    def flash(q, k, v):
        return flash_attention(q, k, v, window=c["window"], **blocks)

    def dense(q, k, v):
        return dot_product_attention(q, k, v, window=c["window"])

    out, vjp = jax.vjp(flash, q, k, v)
    ref, ref_vjp = jax.vjp(dense, *(x.astype(jnp.float32) for x in (q, k, v)))
    assert out.dtype == jnp.float32
    assert _gap(out, ref) < 1e-2
    for got, want, name in zip(vjp(w), ref_vjp(w), "qkv"):
        assert got.dtype == jnp.bfloat16
        assert _gap(got, want) < 2e-2, f"d{name}"


# -- lane blocks of the model's own array, the causal triangle in chunks -------
# head_dim 64 puts two heads in a 128-lane block: a grid step is handed the
# (batch, seq, heads·64) array's own lanes and works each head in turn, and a
# tile on the diagonal in chunks of CHUNK_TARGET rows (forced to 64 here, so a
# 256-token sequence is four chunks on the interpreter). Same tolerances as
# the bfloat16 cases above; float32 on the interpreter keeps float32 products.

LANE_CASES = {
    "two-heads": dict(h=2, hk=2, d=64, window=None, addressing="lanes"),
    "four-heads": dict(h=4, hk=4, d=64, window=None, addressing="lanes"),
    # 100 keys back from row 64 r + i starts inside the chunk before
    "window-across-a-chunk-edge": dict(h=2, hk=2, d=64, window=100,
                                       addressing="lanes"),
    # a block's two q heads read ONE kv head, spread over the block's lanes
    "gqa-8-on-2": dict(h=8, hk=2, d=64, window=None, addressing="lanes"),
    # and the shapes that keep the folded copy, chunked all the same
    "falls-back-3-heads": dict(h=3, hk=3, d=64, window=None,
                               addressing="folded"),
    "falls-back-head-dim-80": dict(h=2, hk=2, d=80, window=None,
                                   addressing="folded"),
    "falls-back-gqa-group-3": dict(h=6, hk=2, d=64, window=None,
                                   addressing="folded"),
}


@pytest.fixture
def four_chunks(monkeypatch):
    """CHUNK_TARGET 64 for one test: the jitted passes are traced anew
    around it, since the constant is no part of their cache's key."""
    for fn in (fa._flash_fwd, fa._flash_bwd):
        fn.clear_cache()
    monkeypatch.setattr(fa, "CHUNK_TARGET", 64)
    yield
    for fn in (fa._flash_fwd, fa._flash_bwd):
        fn.clear_cache()


def _lane_inputs(c, dtype, s=256):
    keys = jax.random.split(jax.random.PRNGKey(5), 5)
    q = jax.random.normal(keys[0], (1, s, c["h"], c["d"])).astype(dtype)
    k, v = (jax.random.normal(kk, (1, s, c["hk"], c["d"])).astype(dtype)
            for kk in keys[1:3])
    return q, k, v, jax.random.normal(keys[3], q.shape), \
        jax.random.normal(keys[4], q.shape[:3])


@pytest.mark.parametrize("case,dtype", [
    (case, dtype) for case, c in LANE_CASES.items()
    for dtype in (jnp.bfloat16, jnp.float32)
    # one case each for the shapes that fall back
    if dtype == jnp.bfloat16 or c["addressing"] == "lanes"],
    ids=lambda x: x if isinstance(x, str) else jnp.dtype(x).name)
def test_flash_in_chunks_matches_dense_forward_and_gradients(
        four_chunks, case, dtype):
    c = LANE_CASES[case]
    q, k, v, w, _ = _lane_inputs(c, dtype)
    plan = fa._blocks(256, 256, c["d"], dtype, None, None, True, c["window"],
                      c["h"], c["hk"])
    assert (plan.block_q, plan.chunk, plan.addressing) == (
        256, 64, c["addressing"])
    out, vjp = jax.vjp(lambda *a: flash_attention(*a, window=c["window"]),
                       q, k, v)
    ref, ref_vjp = jax.vjp(
        lambda *a: dot_product_attention(*a, window=c["window"]),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    tight = dtype == jnp.float32
    assert out.dtype == jnp.float32
    assert _gap(out, ref) < (1e-5 if tight else 1e-2)
    for got, want, name in zip(vjp(w), ref_vjp(w), "qkv"):
        assert got.dtype == dtype and got.shape == want.shape
        assert _gap(got, want) < (1e-5 if tight else 2e-2), f"d{name}"


def _dense_with_lse(q, k, v, window):
    from kubeshare_tpu.ops.attention import expand_kv
    s, d = q.shape[1], q.shape[-1]
    kx, _ = expand_kv(k, v, q.shape[2])
    scores = jnp.einsum("bqhd,bkhd->bqhk", q, kx) / np.sqrt(d)
    idx = jnp.arange(s)
    mask = idx[:, None] >= idx[None, :]
    if window is not None:
        mask &= idx[:, None] - idx[None, :] < window
    scores = jnp.where(mask[None, :, None, :], scores, -1e30)
    return (dot_product_attention(q, k, v, window=window),
            jax.nn.logsumexp(scores, axis=-1))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", ["two-heads", "gqa-8-on-2",
                                  "window-across-a-chunk-edge"])
def test_flash_lse_in_chunks_with_its_lse_cotangent(four_chunks, case, dtype):
    """Both outputs and, through both cotangents, the three gradients: the
    lse cotangent rides into the kernels as one more per-row operand."""
    from kubeshare_tpu.ops.flash_attention import flash_attention_lse
    c = LANE_CASES[case]
    q, k, v, w, w_lse = _lane_inputs(c, dtype)
    (out, lse), vjp = jax.vjp(
        lambda *a: flash_attention_lse(*a, window=c["window"]), q, k, v)
    (ref, ref_lse), ref_vjp = jax.vjp(
        lambda *a: _dense_with_lse(*a, c["window"]),
        *(x.astype(jnp.float32) for x in (q, k, v)))
    tight = dtype == jnp.float32
    assert lse.shape == q.shape[:3] and lse.dtype == jnp.float32
    assert _gap(out, ref) < (1e-5 if tight else 1e-2)
    assert _gap(lse, ref_lse) < (1e-5 if tight else 1e-2)
    for got, want, name in zip(vjp((w, w_lse)), ref_vjp((w, w_lse)), "qkv"):
        assert got.dtype == dtype
        assert _gap(got, want) < (1e-5 if tight else 2e-2), f"d{name}"


def test_two_tiles_a_sequence_chunk_the_diagonal_and_carry_the_rest(
        four_chunks):
    """Blocks of 128 on 256 tokens: the diagonal tiles in two chunks of 64
    through the running max / sum / accumulators, the tile below them
    whole, the one above never (8-on-2 heads: dK/dV walk four steps)."""
    c = LANE_CASES["gqa-8-on-2"]
    q, k, v, w, _ = _lane_inputs(c, jnp.float32)
    plan = fa._blocks(256, 256, 64, jnp.float32, 128, 128, True, None, 8, 2)
    assert (plan.block_q, plan.chunk, plan.addressing) == (128, 64, "lanes")
    out, vjp = jax.vjp(lambda *a: flash_attention(
        *a, block_q=128, block_k=128), q, k, v)
    ref, ref_vjp = jax.vjp(dot_product_attention, q, k, v)
    assert _gap(out, ref) < 1e-5
    for got, want in zip(vjp(w), ref_vjp(w)):
        assert _gap(got, want) < 1e-5


# -- the tile rule -------------------------------------------------------------
@pytest.mark.parametrize("what", ["forward", "gradients"])
def test_flash_32_query_on_8_kv_heads_across_a_tile_boundary(what):
    """The LFM2 cell's head layout (32 query heads on 8 kv heads, head 64)
    with the sequence cut in two tiles each way: a query tile reads its
    group's k/v row across the boundary, and dK/dV sum the group's four
    heads times two q tiles in one accumulator."""
    keys = jax.random.split(jax.random.PRNGKey(11), 3)
    q = jax.random.normal(keys[0], (1, 64, 32, 64), jnp.float32)
    k, v = (jax.random.normal(kk, (1, 64, 8, 64), jnp.float32)
            for kk in keys[1:])
    if what == "forward":
        out = flash_attention(q, k, v, block_q=32, block_k=32)
        np.testing.assert_allclose(
            np.asarray(out), np.asarray(dot_product_attention(q, k, v)),
            atol=1e-5, rtol=1e-5)
        return
    g_ref = jax.grad(lambda *a: (dot_product_attention(*a) ** 2).sum(),
                     argnums=(0, 1, 2))(q, k, v)
    g_out = jax.grad(lambda *a: (flash_attention(
        *a, block_q=32, block_k=32) ** 2).sum(), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_out, g_ref):
        assert a.shape == b.shape
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=2e-4, rtol=2e-4)


#: (sequence, head_dim, operand dtype) -> the tile the rule gives
TILE_TABLE = [(128, 64, jnp.bfloat16, 128), (256, 64, jnp.bfloat16, 256),
              (512, 64, jnp.bfloat16, 512), (1024, 64, jnp.bfloat16, 1024),
              (1024, 128, jnp.bfloat16, 1024), (8192, 64, jnp.bfloat16, 1024),
              (4096, 64, jnp.bfloat16, 1024),   # the LFM2 cell's sequence
              (1024, 64, jnp.float32, 1024), (96, 64, jnp.bfloat16, 96),
              (3000, 64, jnp.float32, 1000),    # no multiple of 128 divides
              (2048, 512, jnp.bfloat16, 512),   # the budget halves the target
              (4096, 4096, jnp.float32, 64)]


def _need(plan, s, d, dtype):
    """What the rule counted for ``plan`` (one head folded, as the table's
    calls are)."""
    one_tile = s == plan.block_q == plan.block_k
    return fa._tile_vmem_bytes(
        plan.block_q, plan.block_k,
        plan.chunk if one_tile else max(plan.block_q, plan.block_k),
        max(d, 128) if plan.addressing == "lanes" else d, plan.heads,
        jnp.dtype(dtype).itemsize)


@pytest.mark.parametrize("s,d,dtype,tile", TILE_TABLE)
def test_tiles_follow_the_shape(s, d, dtype, tile):
    itemsize = jnp.dtype(dtype).itemsize
    plan = fa._blocks(s, s, d, dtype, None, None, True)
    bq, bk = plan[:2]
    assert (bq, bk) == (tile, tile)
    assert s % bq == 0 and bq <= fa.TILE_TARGET
    # Mosaic's block rule: a whole dimension, or whole sublane tiles of rows
    assert bq == s or bq % (32 // itemsize) == 0
    # a diagonal tile is worked in whole chunks, never above the target
    assert bq % plan.chunk == 0 and plan.chunk <= max(fa.CHUNK_TARGET, 1)
    need = _need(plan, s, d, dtype)
    assert need <= fa.VMEM_BUDGET
    # the compiler is asked for more only where its default would not do
    assert plan.vmem_limit == (None if need <= 16 * 2 ** 20 else need)
    # a caller's blocks win, one at a time too
    assert fa._blocks(s, s, d, dtype, 8, 4, True)[:2] == (8, 4)
    # (the other is then derived beside it, and may be larger for it)
    assert fa._blocks(s, s, d, dtype, 8, None, True)[0] == 8
    assert fa._blocks(s, s, d, dtype, None, 4, True)[1] == 4


#: (what, seq, heads, kv heads, head_dim, dtype) -> (tile, chunk, addressing,
#: heads a block holds): the attention calls of the benchmark's three cells
#: and of the scorer's four buckets, and the shapes that must fall back
PLAN_TABLE = [
    ("gpt2s-pair-even trainer", 1024, 12, 12, 64, jnp.bfloat16,
     (1024, 256, "lanes", 2)),
    ("gpt2m-score-vs-train trainer", 1024, 16, 16, 64, jnp.bfloat16,
     (1024, 256, "lanes", 2)),
    ("lfm2moe-solo-elastic trainer", 4096, 32, 8, 64, jnp.bfloat16,
     (1024, 256, "lanes", 2)),
    ("scorer bucket 128", 128, 16, 16, 64, jnp.bfloat16,
     (128, 128, "lanes", 2)),
    ("scorer bucket 256", 256, 16, 16, 64, jnp.bfloat16,
     (256, 256, "lanes", 2)),
    ("scorer bucket 512", 512, 16, 16, 64, jnp.bfloat16,
     (512, 256, "lanes", 2)),
    ("scorer bucket 1024", 1024, 16, 16, 64, jnp.bfloat16,
     (1024, 256, "lanes", 2)),
    ("head_dim 128: a block is a head, any group", 1024, 6, 2, 128,
     jnp.bfloat16, (1024, 256, "lanes", 1)),
    ("head_dim 32: four heads a block", 256, 8, 8, 32, jnp.float32,
     (256, 256, "lanes", 4)),
    ("an odd head count", 1024, 3, 3, 64, jnp.bfloat16,
     (1024, 256, "folded", 1)),
    ("head_dim 80 tiles no lane block", 1024, 8, 8, 80, jnp.bfloat16,
     (1024, 256, "folded", 1)),
    ("head_dim 96", 1024, 8, 8, 96, jnp.bfloat16, (1024, 256, "folded", 1)),
    ("a group of 3 splits a block over two kv heads", 1024, 6, 2, 64,
     jnp.bfloat16, (1024, 256, "folded", 1)),
    ("kv heads that fill half a block", 256, 8, 1, 64, jnp.bfloat16,
     (256, 256, "folded", 1)),
]


@pytest.mark.parametrize("what,s,h,hk,d,dtype,want", PLAN_TABLE,
                         ids=[row[0] for row in PLAN_TABLE])
def test_the_plan_of_a_call_follows_its_shapes(what, s, h, hk, d, dtype,
                                               want):
    plan = fa._blocks(s, s, d, dtype, None, None, True, None, h, hk)
    assert (plan.block_q, plan.chunk, plan.addressing, plan.heads) == want
    assert plan.block_k == plan.block_q
    # not causal: nothing to skip, the tile is worked whole
    assert fa._blocks(s, s, d, dtype, None, None, False, None, h,
                      hk).chunk == plan.block_q
    # a block the caller forces below a sublane tile cannot be a lane block
    assert fa._blocks(s, s, d, dtype, 4, 4, True, None, h,
                      hk).addressing == "folded"


def test_tile_rule_rejects_a_sequence_it_cannot_block():
    with pytest.raises(ValueError, match="divisible"):
        fa._blocks(3000, 3000, 64, jnp.bfloat16, None, None, True)
    with pytest.raises(ValueError, match="divisible"):
        fa._blocks(1024, 1024, 64, jnp.bfloat16, 48, None, True)
