"""The MiniCPM-SALA-style decoder (``models/minicpm_sala.py`` and the ops it
brought: the lightning scan, the block selection, the attention over
chosen blocks) against its plain reference
(``benchmark/reference/minicpm_sala.py``: float32, ``highest``, no kernels,
the quadratic sum, an explicit mask) at tiny sizes on the CPU, where the
Pallas kernels run in the interpreter: the same kernel bodies the chip
compiles.

Tolerances. With ``Config(dtype="float32")`` the program and the reference
compute the same float32 mathematics in another order (a scan against a
sum, an online softmax against a whole one, fused against sliced
projections): they agree to float32 round-off, and ``F32`` is a few dozen
ulps of the largest element. bfloat16 operands (4e-3 each) miss it by two
orders of magnitude; ``test_bfloat16_...`` shows that.
"""

import dataclasses
import importlib
import importlib.util
import json
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubeshare_tpu.models import FORWARD_ONLY, MODEL_NAMES, get_model
from kubeshare_tpu.ops import attention as A
from kubeshare_tpu.ops import sparse_attention as SP
from kubeshare_tpu.ops.linear_attention import (decayed_sum,
                                                lightning_attention, slopes)

fa = importlib.import_module("kubeshare_tpu.ops.flash_attention")
M = get_model("minicpm_sala")
REPO = Path(__file__).resolve().parent.parent
F32 = 3e-5

#: the tiny preset as a configuration FILE would state it (the reference
#: and the counts read a file's keys, the program a ``Config``)
TINY = {
    "model_type": "minicpm_sala", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "intermediate_size": 160, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 256, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "lightning-attn"],
    "published": {"num_hidden_layers": 8, "vocab_size": 2048},
    "deployment": {"positions_as_run": 256},
    "assumed": {"kernel_size": 8, "kernel_stride": 4, "block_size": 8,
                "init_blocks": 1, "window_size": 16, "topk": 4,
                "dense_len": 64, "decay_exponent": 8, "scan_chunk": 16},
    "precision": {"params": "float32", "matmul": "bfloat16",
                  "control": "float8_e4m3fn"},
    "reference": "benchmark/reference/minicpm_sala.py",
    "binding": "benchmark/models/minicpm_sala.py",
    "counts": "benchmark/counts/minicpm_sala.py",
}


def _load(rel: str):
    path = REPO / rel
    spec = importlib.util.spec_from_file_location(
        "t_" + path.stem + "_" + path.parent.name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def ref():
    return _load("benchmark/reference/minicpm_sala.py")


@pytest.fixture(scope="module")
def binding():
    sys.path.insert(0, str(REPO / "benchmark"))
    return _load("benchmark/models/minicpm_sala.py")


@pytest.fixture(scope="module")
def counts():
    return _load("benchmark/counts/minicpm_sala.py")


KEY = np.array([7, 2**31 + 5], np.uint32)


def _tokens(seq, seed=3):
    return np.random.default_rng(seed).integers(0, 256, (1, seq)).astype(
        np.int32)


def _close(got, want, tol=F32):
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    assert np.max(np.abs(got - want)) <= tol * np.max(np.abs(want))


def _flat(tree):
    paths = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                     for k in path): leaf for path, leaf in paths}


# -- the program against the reference ----------------------------------------

@pytest.mark.parametrize("seq", [64, 128], ids=["dense-path", "sparse-path"])
def test_logits_match_the_reference_in_float32(ref, binding, seq):
    """Both sides of ``dense_len`` (64): the same weights from the same
    key, the same logits to float32 round-off."""
    params = jax.jit(binding.init(TINY))(KEY)
    want = ref.init(KEY, TINY)
    have = _flat(params)
    assert set(have) == set(want)
    for name, leaf in have.items():
        np.testing.assert_array_equal(np.asarray(leaf), np.asarray(want[name]))
    tokens = _tokens(seq)
    got = jax.jit(binding.logits(TINY))(params, tokens)
    assert got.dtype == jnp.float32 and got.shape == (1, seq, 256)
    _close(got, ref.logits_fn(want, tokens, TINY))


def test_bfloat16_misses_the_float32_tolerance_and_stays_near(ref, binding):
    cfg = dict(TINY, precision=dict(TINY["precision"], params="bfloat16"))
    params = jax.jit(binding.init(cfg))(KEY)
    assert params["layers"][1]["mlp"]["w1"].dtype == jnp.bfloat16
    assert params["layers"][1]["mixer"]["lam"].dtype == jnp.float32
    tokens = _tokens(128)
    got = np.asarray(jax.jit(binding.logits(cfg))(params, tokens))
    want = np.asarray(ref.logits_fn(ref.init(KEY, cfg), tokens, cfg))
    gap = np.max(np.abs(got - want)) / np.max(np.abs(want))
    assert 100 * F32 < gap < 0.05


def test_the_reference_scores_and_its_control_reads_apart(ref):
    p = ref.init(KEY, TINY)
    tokens = np.zeros((1, 128), np.int32)
    tokens[0, :100] = _tokens(100)[0]
    full = ref.score(p, tokens, 100, TINY)
    assert np.isfinite(full) and full < 0
    assert ref.score(p, tokens, 99, TINY) != full
    low = ref.score(p, tokens, 100, TINY, "int8")
    assert 1e-6 < abs(low - full) / abs(full) < 0.1


# -- the lightning scan --------------------------------------------------------

@pytest.mark.parametrize("seq,chunk", [(64, 16), (50, 16), (40, None),
                                       (96, 96), (33, 8)])
def test_the_chunked_scan_is_the_plain_sum(seq, chunk):
    """Chunks that do and do not divide the length; the fast heads decay
    by e^-0.84 a position, the slow ones by e^-0.004: both ends of the
    ladder in one call."""
    q, k, v = (jax.random.normal(key, (2, seq, 8, 16))
               for key in jax.random.split(jax.random.PRNGKey(seq), 3))
    lam = slopes(8)
    _close(lightning_attention(q, k, v, lam, chunk=chunk),
           decayed_sum(q, k, v, lam))


def test_the_scan_takes_a_head_of_128_lanes_as_it_lies():
    q, k, v = (jax.random.normal(key, (1, 256, 2, 128))
               for key in jax.random.split(jax.random.PRNGKey(1), 3))
    lam = slopes(2)
    _close(lightning_attention(q, k, v, lam, chunk=64),
           decayed_sum(q, k, v, lam))
    with pytest.raises(ValueError):
        lightning_attention(q, k, v, slopes(3))


# -- the selection and the attention over chosen blocks ------------------------

SEL = dict(kernel_size=8, stride=4, block_size=8, init_blocks=1,
           window_blocks=2, topk=4)


def _qkv(seq, heads=4, kv=2, d=16, seed=0):
    kq, kk, kv_ = jax.random.split(jax.random.PRNGKey(seed), 3)
    return (jax.random.normal(kq, (1, seq, heads, d)),
            jax.random.normal(kk, (1, seq, kv, d)),
            jax.random.normal(kv_, (1, seq, kv, d)))


@pytest.mark.parametrize("seq,rows", [(128, 32), (128, None), (256, 64)])
def test_the_chosen_blocks_are_the_references(ref, seq, rows):
    q, k, _ = _qkv(seq)
    got = SP.unpack(SP.select_blocks(q, k, rows=rows, **SEL), seq // 8)
    c = ref._sizes(TINY)
    kc = ref._pooled(k[0], c, (seq - 8) // 4 + 1)
    want = ref._chosen(q[0], kc, jnp.arange(seq), c, seq // 8, None)
    np.testing.assert_array_equal(np.asarray(got[0]),
                                  np.asarray(want).transpose(1, 0, 2))
    own = np.arange(seq) // 8
    # the forced blocks counted among the top-k, never a later block
    np.testing.assert_array_equal(np.asarray(got.sum(-1))[0, 0],
                                  np.minimum(4, own + 1))
    assert bool(got[0, :, :, 0].all())
    assert not bool((np.asarray(got)[0, 0]
                     & (np.arange(seq // 8)[None] > own[:, None])).any())


def test_attention_over_the_chosen_blocks_is_softmax_over_those_keys(ref):
    q, k, v = _qkv(128, seed=2)
    bits = SP.select_blocks(q, k, **SEL)
    chosen = SP.unpack(bits, 16)[0].transpose(1, 0, 2)     # (seq, kv, blocks)
    allowed = (jnp.repeat(chosen, 8, axis=-1)
               & (jnp.arange(128)[None, :] <= jnp.arange(128)[:, None])[
                   :, None])
    want = ref._softmax_attention(q[0], k[0], v[0], allowed,
                                  ref._sizes(TINY), None)
    _close(SP.chosen_blocks_attention(q, k, v, bits, 8)[0], want)


def test_with_every_block_chosen_the_sparse_path_is_the_dense_one():
    q, k, v = _qkv(128, seed=4)
    bits = SP.select_blocks(q, k, **dict(SEL, topk=16))
    _close(SP.chosen_blocks_attention(q, k, v, bits, 8),
           A.dot_product_attention(q, k, v, causal=True))


def test_a_k_tile_must_lie_in_one_word_of_the_bit_table():
    q, k, v = _qkv(512, seed=5)
    bits = SP.select_blocks(q, k, **SEL)
    assert bits.shape == (1, 2, 512, 2)
    with pytest.raises(ValueError, match="one 32-block word"):
        SP.chosen_blocks_attention(q, k, v, bits, 8, tile_k=384)
    _close(SP.chosen_blocks_attention(q, k, v, bits, 8, tile_q=64),
           SP.chosen_blocks_attention(q, k, v, bits, 8, tile_k=128))


# -- causality -----------------------------------------------------------------

@pytest.mark.parametrize("seq", [64, 128], ids=["dense-path", "sparse-path"])
def test_later_tokens_and_padding_change_no_earlier_logit(binding, seq):
    """Tokens after ``t``, and zero padding to a bucket, change no logit
    at or before ``t``: a compressed window is seen only once it has
    ENDED, which is where a sparse layer would leak."""
    params = jax.jit(binding.init(TINY))(KEY)
    logits = jax.jit(binding.logits(TINY))
    tokens = _tokens(seq, seed=9)
    base = np.asarray(logits(params, tokens))
    for t in (seq // 2 + 3, seq - 10):
        other = tokens.copy()
        other[0, t + 1:] = _tokens(seq, seed=11)[0, t + 1:]
        padded = tokens.copy()
        padded[0, t + 1:] = 0
        for changed in (other, padded):
            got = np.asarray(logits(params, changed))
            np.testing.assert_allclose(got[0, :t + 1], base[0, :t + 1],
                                       rtol=0, atol=1e-6)
            assert np.max(np.abs(got[0, t + 1:] - base[0, t + 1:])) > 1e-4


# -- the counts ----------------------------------------------------------------

def _leaves(binding, cfg):
    shapes = jax.eval_shape(binding.init(cfg),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    return sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(shapes))


def test_parameters_counted_from_the_leaves_equal_the_counts(binding, counts):
    assert _leaves(binding, TINY) == counts.parameters(TINY)
    cfg = json.loads((REPO / "benchmark" / "configs" / "minicpm-sala.json")
                     .read_text())
    n = _leaves(binding, cfg)
    assert n == counts.parameters(cfg) == cfg["parameters_as_run"]
    assert round(n / 1e6, 1) == 1184.6
    assert counts.sizes(cfg) == {"vocab": 9181, "positions": 32768}


def test_counts_of_the_published_configuration(counts):
    cfg = json.loads((REPO / "benchmark" / "configs" / "minicpm-sala.json")
                     .read_text())
    n = counts.multiplying_params(cfg)
    assert round(n / 1e6, 1) == 1147.0
    # 2 N a token and a little more: the scans and the attention
    assert 2 * n * 300 < counts.score_flops(cfg, 300) < 2.02 * n * 300
    long = counts.score_flops(cfg, 32768)
    assert 2 * n * 32768 < long < 2.1 * n * 32768
    # the scan: a chunk's triangle, the state's read and update; memory
    # binds it (33 KB a position against 6.3 MFLOP): q, k, v in and o out
    # at the model's two bytes, whatever the kernel writes
    lin = counts.linear_attention_layer(cfg, 32768)
    assert lin["flops"] == 32768 * 32 * (2 * 128 * 513 + 4 * 128 * 128)
    assert lin["bytes"] == 32768 * 4096 * 8
    assert counts.sparse_attention_layer(cfg, 8192) is None
    sp = counts.sparse_attention_layer(cfg, 16384)
    # topk * block_size keys a query, fewer near the start: under the
    # whole 4,096 and over three quarters of it
    whole = 4.0 * 32 * 128 * 16384 * 4096
    assert 0.75 * whole < sp["flops"] < whole
    assert counts.linear_attention_layers(cfg) == 3
    assert counts.sparse_attention_layers(cfg) == 1


# -- what the shared ops gained -------------------------------------------------

def test_gqa_apply_keeps_its_behaviour_and_takes_no_rotary_and_a_gate():
    key = jax.random.PRNGKey(0)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, 64))
    plain = A.gqa_init(key, 64, 4, 2)
    gated = A.gqa_init(key, 64, 4, 2, gated=True)
    assert set(gated) == set(plain) | {"gate"}
    for name in plain:
        np.testing.assert_array_equal(plain[name], gated[name])
    base = A.gqa_apply(plain, x, 4)
    np.testing.assert_array_equal(
        base, A.gqa_apply(gated, x, 4, use_rope=True, gated=False))
    seen = {}

    def spy(q, k, v):
        seen["q"], seen["k"] = q, k
        return A.dot_product_attention(q, k, v)

    out = A.gqa_apply(gated, x, 4, attn_fn=spy, use_rope=False, gated=True)
    qkv = x @ gated["qkv"]
    q = qkv[..., :64].reshape(2, 16, 4, 16)
    want_q = q * jax.lax.rsqrt(jnp.mean(q * q, -1, keepdims=True) + 1e-5)
    _close(seen["q"], want_q)            # normed, not rotated
    o = A.dot_product_attention(seen["q"], seen["k"],
                                qkv[..., 96:].reshape(2, 16, 2, 16))
    want = (o.reshape(2, 16, 64) * jax.nn.sigmoid(x @ gated["gate"])
            ) @ gated["out"]
    _close(out, want)
    assert np.max(np.abs(out - base)) > 1e-3


def test_the_flash_kernel_takes_32_query_heads_on_2_kv_heads_of_128():
    """The dense path's call as the benchmark makes it: one head a lane
    block, 16 q head blocks reading one kv head, nothing folded."""
    plan = fa._blocks(8192, 8192, 128, jnp.bfloat16, None, None, True,
                      None, 32, 2)
    assert plan.addressing == "lanes" and plan.heads == 1
    assert 8192 % plan.block_q == 0 and plan.block_q == plan.block_k
    q, k, v = _qkv(64, heads=32, kv=2, d=128, seed=6)
    _close(fa.flash_attention(q, k, v, causal=True),
           A.dot_product_attention(q, k, v, causal=True))


def test_config_refuses_what_it_cannot_run():
    assert FORWARD_ONLY == ("minicpm_sala",) and not set(
        FORWARD_ONLY) & set(MODEL_NAMES)
    assert not hasattr(M, "loss_fn")
    with pytest.raises(ValueError, match="unknown mixer"):
        M.Config(mixer_types=("minicpm4", "mamba"))
    with pytest.raises(ValueError, match="whole"):
        M.Config(block_size=6)
    cfg = dataclasses.replace(M.TINY, layers_published=32)
    assert abs(cfg.residual_scale - 1.4 / 32 ** 0.5) < 1e-12
    out = jax.jit(M.score_fn)(M.init(jax.random.PRNGKey(0)),
                              M.batch_fn(jax.random.PRNGKey(1)))
    assert out.shape == (2,) and bool(jnp.all(jnp.isfinite(out)))
