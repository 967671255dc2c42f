"""``counts/gpt2.py`` (found through each configuration's ``counts``) and
``flops.py`` against counts worked by hand for both configurations, the
attention's bytes against the arrays the program's kernels declare, and a
family that offers no count of a layer."""

import importlib.util
import json
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(Path(__file__).resolve().parent))
sys.path.insert(0, str(BENCH.parent))
import helpers  # noqa: E402

PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture
def readlib():
    """This checkout's ``readlib`` (other tests leave a rehearsal tree's
    in ``sys.modules``); ``readlib.flops`` is its ``flops.py``."""
    helpers.forget_other_trees(BENCH.parent)
    import readlib
    return readlib


def cfg(name):
    return json.loads((BENCH / "configs" / f"{name}.json").read_text())


@pytest.mark.parametrize("name,multiplying,total", [
    # 12 layers x 12 x 768^2 + 768 x 50257 ; every leaf of the program's init
    ("gpt2-small", 12 * 12 * 768 * 768 + 768 * 50257, 163_050_577),
    ("gpt2-medium", 24 * 12 * 1024 * 1024 + 1024 * 50257, 406_238_289),
])
def test_parameter_counts(readlib, name, multiplying, total):
    c = cfg(name)
    assert readlib.named(c, "counts").multiplying_params(c) == multiplying
    assert readlib.sizes(c) == {"vocab": 50257, "positions": 1024}
    assert c["parameters_as_run"] == total


def test_small_training_step_by_hand(readlib):
    c = cfg("gpt2-small")
    n = 123_532_032                      # 84,934,656 + 38,597,376
    k = readlib.named(c, "counts")
    assert k.multiplying_params(c) == n
    tokens = 8 * 1024
    dense = 6 * n * tokens               # 6.07e12
    # one causal matmul of one layer: 8 rows x 2 x 1024^2 x 768 / 2
    one = 8 * 1024 * 1024 * 768
    assert k.attention_matmul_flops(c, 8, 1024) == one
    assert k.train_flops(c, 8, 1024) == dense + 12 * 6 * one
    assert k.train_flops(c, 8, 1024) == pytest.approx(6.536e12, rel=1e-3)


def test_medium_scoring_request_by_hand(readlib):
    c = cfg("gpt2-medium")
    n = 24 * 12 * 1024 * 1024 + 1024 * 50257
    want = 2 * n * 300 + 24 * 2 * (300 * 300 * 1024)
    assert readlib.named(c, "counts").score_flops(c, 300) == want


def test_attention_layer_bytes_and_bound(readlib):
    c = cfg("gpt2-small")
    k = readlib.named(c, "counts")
    elems = 8 * 1024 * 768
    fwd = k.attention_layer(c, 8, 1024, backward=False)
    assert fwd["bytes"] == 3 * elems * 2 + elems * 4
    assert fwd["flops"] == 2 * 8 * 1024 * 1024 * 768
    both = k.attention_layer(c, 8, 1024, backward=True)
    assert both["flops"] == 6 * 8 * 1024 * 1024 * 768
    assert k.attention_layers(c) == 12
    # 30 bytes an element (188.7 MB a layer) against 6144 operations: at
    # 1024 tokens and head_dim 64 the float32 o and do make even attention
    # memory-bound
    assert both["bytes"] == 30 * elems == 188_743_680
    secs, bound = readlib.flops.least_seconds(both, PEAKS)
    assert bound == "memory" and secs == pytest.approx(30 * elems / 819e9)
    assert readlib.flops.least_seconds(fwd, PEAKS)[1] == "memory"


def test_attention_layer_bytes_are_the_arrays_the_kernels_declare(readlib):
    """The count is tied to the program, not to a comment: q, k, v, o, do,
    dq, dk, dv as ``ops/flash_attention.py`` declares them for bfloat16
    q, k, v (``jax.eval_shape`` of the forward and of its backward; nothing
    runs). Left out on both sides: the row statistics ``lse`` and D, 8
    bytes a row and head, under 1% of the rest."""
    import jax
    import jax.numpy as jnp

    from kubeshare_tpu.ops.flash_attention import flash_attention

    rows, seq, heads, hd = 2, 256, 4, 64
    c = {"n_embd": heads * hd, "n_head": heads, "n_layer": 1,
         "vocab_size": 8}
    k = readlib.named({"counts": "benchmark/counts/gpt2.py"}, "counts")
    qkv = [jax.ShapeDtypeStruct((rows, seq, heads, hd), jnp.bfloat16)] * 3

    def nbytes(*arrays):
        return sum(a.size * a.dtype.itemsize for a in arrays)

    def backward(q, k_, v, do):
        o, vjp = jax.vjp(lambda *a: flash_attention(*a, causal=True),
                         q, k_, v)
        return (o, *vjp(do))

    o = jax.eval_shape(lambda *a: flash_attention(*a, causal=True), *qkv)
    o_again, dq, dk, dv = jax.eval_shape(backward, *qkv, o)     # do is o's
    assert k.attention_layer(c, rows, seq, backward=False)["bytes"] == (
        nbytes(*qkv, o))
    assert k.attention_layer(c, rows, seq, backward=True)["bytes"] == (
        nbytes(*qkv, o) + nbytes(*qkv, o_again, o, dq, dk, dv))
    assert (dq.dtype, dk.dtype, dv.dtype, o.dtype) == (
        jnp.bfloat16, jnp.bfloat16, jnp.bfloat16, jnp.float32)


def test_a_family_without_a_layer_count_leaves_its_roofline_silent(readlib):
    """``attn_roofline`` on one made-up traced run: GPT-2's counts give a
    share; a family whose counts offer no ``attention_layer`` gives
    nothing, and never 0."""
    family2 = Path(__file__).resolve().parent / "data" / "family2"
    spec = importlib.util.spec_from_file_location(
        "counts_family2", family2 / "counts.py")
    other = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(other)
    assert not hasattr(other, "attention_layer")
    reader = readlib.reader("attn_roofline")
    trainer = {"role": "train", "entry": {"batch": 8, "seq_len": 1024},
               "done": {"done_at_s": [0.5, 1.0, 1.5, 2.0]}}
    run = {"config": cfg("gpt2-small"), "peaks": PEAKS,
           "tenants": [trainer],
           "trace": {"scopes": {"bench_attn": {"seconds": 0.1}},
                     "from_s": 0.0, "to_s": 2.0}}
    one = 30 * 8 * 1024 * 768 / 819e9
    assert reader.read(run) == pytest.approx(100 * 4 * 12 * one / 0.1)
    monkey = dict(run, config={"hidden_size": 768, "num_hidden_layers": 12,
                               "vocab_size": 50257,
                               "counts": "benchmark/tests/data/family2/"
                                         "counts.py"})
    assert reader.read(monkey) is None


def test_adam_is_memory_bound_at_28_bytes_a_parameter(readlib):
    need = readlib.flops.adam(163_050_577)
    assert need["bytes"] == 28 * 163_050_577
    secs, bound = readlib.flops.least_seconds(need, PEAKS)
    assert bound == "memory" and secs == pytest.approx(5.574e-3, rel=1e-3)
