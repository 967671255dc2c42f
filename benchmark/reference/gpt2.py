"""Plain reference for the GPT-2 configurations: straightforward
``jax.numpy`` in float32 at ``highest`` matmul precision. No kernels, no
bfloat16, no proxy; imports nothing of the program and takes nothing the
program made. Weights come from the seed by the same draws the program's
``models/transformer.init`` makes (re-stated here, not imported).

Follows GPT-2 as published (learned positions, pre-norm LayerNorm, fused
QKV multi-head causal attention, GELU(tanh) 4x MLP) with the departures
the configuration's file states: output head untied with a bias, no bias
on the attention projections, no dropout.

``quant="int8"`` / ``"fp8"`` is the CONTROL, never the reference: every
matmul's two operands pass through a per-tensor-scaled int8 or
float8_e4m3 round trip first: the nearest precision below the bfloat16
the configuration states for its matmuls.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
LN_EPS = 1e-5


class _Frozen(dict):
    """A hashable view of the configuration for ``static_argnums``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


# -- weights from the seed ---------------------------------------------------

def _uniform(key, shape, scale):
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale)


def init(key_words, cfg: dict) -> dict:
    """Flat ``{"blocks/0/attn/qkv": array, ...}`` in float32."""
    return _init(jnp.asarray(np.asarray(key_words, np.uint32)), _Frozen(cfg))


def _init_impl(key, cfg) -> dict:
    d, layers = int(cfg["n_embd"]), int(cfg["n_layer"])
    vocab, seq = int(cfg["vocab_size"]), int(cfg["n_positions"])
    ekey, pkey, okey, *bkeys = jax.random.split(key, 3 + layers)
    p = {"embed": jax.random.normal(ekey, (vocab, d)) * 0.02,
         "pos": jax.random.normal(pkey, (seq, d)) * 0.02}

    def dense(name, k, n_in, n_out):
        wkey, bkey = jax.random.split(k)
        s = math.sqrt(1.0 / n_in)
        p[f"{name}/w"] = _uniform(wkey, (n_in, n_out), s)
        p[f"{name}/b"] = _uniform(bkey, (n_out,), s)

    for i, lkey in enumerate(bkeys):
        k1, k2, k3 = jax.random.split(lkey, 3)
        kq, ko = jax.random.split(k1)
        s = math.sqrt(1.0 / d)
        p[f"blocks/{i}/attn/qkv"] = _uniform(kq, (d, 3 * d), s)
        p[f"blocks/{i}/attn/out"] = _uniform(ko, (d, d), s)
        for ln in ("ln1", "ln2"):
            p[f"blocks/{i}/{ln}/scale"] = jnp.ones((d,))
            p[f"blocks/{i}/{ln}/bias"] = jnp.zeros((d,))
        dense(f"blocks/{i}/fc", k2, d, 4 * d)
        dense(f"blocks/{i}/proj", k3, 4 * d, d)
    p["ln_f/scale"], p["ln_f/bias"] = jnp.ones((d,)), jnp.zeros((d,))
    dense("out", okey, d, vocab)
    return p


_init = jax.jit(_init_impl, static_argnums=(1,))


# -- forward -----------------------------------------------------------------

def _q8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 127.0
    return jnp.clip(jnp.round(x / s), -127, 127) * s


def _f8(x):
    s = jnp.maximum(jnp.max(jnp.abs(x)), 1e-30) / 448.0
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _ste(x, q):
    """A low-precision round trip with a straight-through gradient."""
    return x + jax.lax.stop_gradient(q(x) - x)


def _mm(eq, a, b, quant):
    if quant == "int8":
        a, b = _ste(a, _q8), _ste(b, _q8)
    elif quant == "fp8":
        a, b = _ste(a, _f8), _ste(b, _f8)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _ln(x, scale, bias):
    mean = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mean), -1, keepdims=True)
    return (x - mean) * jax.lax.rsqrt(var + LN_EPS) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def _block(p, i, x, heads, quant):
    b, s, d = x.shape
    hd = d // heads
    h = _ln(x, p[f"blocks/{i}/ln1/scale"], p[f"blocks/{i}/ln1/bias"])
    qkv = _mm("bsd,de->bse", h, p[f"blocks/{i}/attn/qkv"], quant)
    q, k, v = (qkv[..., j * d:(j + 1) * d].reshape(b, s, heads, hd)
               for j in range(3))
    sc = _mm("bqhd,bkhd->bhqk", q, k, quant) / math.sqrt(hd)
    mask = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
    sc = jnp.where(mask[None, None], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    o = _mm("bhqk,bkhd->bqhd", w, v, quant).reshape(b, s, d)
    x = x + _mm("bsd,de->bse", o, p[f"blocks/{i}/attn/out"], quant)
    h = _ln(x, p[f"blocks/{i}/ln2/scale"], p[f"blocks/{i}/ln2/bias"])
    h = _gelu_tanh(_mm("bsd,de->bse", h, p[f"blocks/{i}/fc/w"], quant)
                   + p[f"blocks/{i}/fc/b"])
    return x + (_mm("bse,ed->bsd", h, p[f"blocks/{i}/proj/w"], quant)
                + p[f"blocks/{i}/proj/b"])


def logits_fn(p, tokens, cfg, quant=None):
    """``tokens`` (rows, seq) int32 -> logits (rows, seq, vocab) f32."""
    heads, layers = int(cfg["n_head"]), int(cfg["n_layer"])
    x = p["embed"][tokens] + p["pos"][:tokens.shape[1]]
    for i in range(layers):
        x = jax.checkpoint(_block, static_argnums=(1, 3, 4))(
            p, i, x, heads, quant)
    x = _ln(x, p["ln_f/scale"], p["ln_f/bias"])
    return _mm("bsd,dv->bsv", x, p["out/w"], quant) + p["out/b"]


def _nll_sum(p, tokens, targets, cfg, quant):
    logp = jax.nn.log_softmax(logits_fn(p, tokens, cfg, quant))
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))


# -- training: loss, gradients, Adam -----------------------------------------

def loss_and_grads(p, tokens, targets, cfg, quant=None, rows_per_block=1):
    """Mean cross entropy over every token of the batch and its gradient,
    accumulated over blocks of rows so that it fits beside the state."""
    frozen = _Frozen(cfg)
    total, acc = 0.0, None
    for r in range(0, tokens.shape[0], rows_per_block):
        s, g = _grad_fn(p, tokens[r:r + rows_per_block],
                  targets[r:r + rows_per_block], frozen, quant)
        total = total + s
        acc = g if acc is None else _add(acc, g)
    n = tokens.shape[0] * tokens.shape[1]
    return total / n, _scale(acc, 1.0 / n)


_grad_fn = jax.jit(jax.value_and_grad(_nll_sum), static_argnums=(3, 4))
_add = jax.jit(lambda a, b: jax.tree_util.tree_map(jnp.add, a, b),
               donate_argnums=(0,))
_scale = jax.jit(lambda t, c: jax.tree_util.tree_map(lambda a: a * c, t),
                 donate_argnums=(0,))


def adam_init(p):
    zeros = {k: jnp.zeros_like(v) for k, v in p.items()}
    return {"t": 0, "m": zeros, "v": dict(zeros)}


@jax.jit
def _adam_leaf(p, g, m, v, c1, c2, lr, b1, b2, eps):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v


def adam_step(p, g, state, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba 2014), bias-corrected, epsilon outside the root."""
    t = state["t"] + 1
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new_p, new_m, new_v = {}, {}, {}
    for k in p:
        new_p[k], new_m[k], new_v[k] = _adam_leaf(
            p[k], g[k], state["m"][k], state["v"][k], c1, c2, lr, b1, b2,
            eps)
    return new_p, {"t": t, "m": new_m, "v": new_v}


_norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                            for k, v in t.items()})


def norms(tree: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(_norms(tree)).items()}


def train_readings(key_words, cfg, batches, lr, quant=None, rows=None,
                   keep_state=False):
    """Follow a trainer's first ``len(batches)`` steps from the seed:
    each step's loss, the first gradient's norm per leaf and the norm of
    the parameters' change per leaf after the last step.

    ``rows`` (a slice) and ``keep_state`` plant the faults the tests read:
    part of the batch left out with the mean over the rest, and a step
    that returns its state unchanged."""
    p0 = init(key_words, cfg)
    p, state = p0, adam_init(p0)
    losses, grad_norms = [], None
    for i, (tokens, targets) in enumerate(batches):
        if rows is not None:
            tokens, targets = tokens[rows], targets[rows]
        loss, g = loss_and_grads(p, jnp.asarray(tokens),
                                 jnp.asarray(targets), cfg, quant)
        losses.append(float(loss))
        if i == 0:
            grad_norms = norms(g)
        if not keep_state:
            p, state = adam_step(p, g, state, lr)
        del g
    delta = norms({k: p[k] - p0[k] for k in p})
    return {"losses": losses, "grad_norms": grad_norms,
            "delta_norms": delta}


# -- scoring -----------------------------------------------------------------

def _score_impl(p, tokens, length, cfg, quant):
    logp = jax.nn.log_softmax(logits_fn(p, tokens, cfg, quant)[0, :-1])
    got = jnp.take_along_axis(logp, tokens[0, 1:, None], -1)[:, 0]
    live = jnp.arange(1, tokens.shape[1]) < length
    return jnp.sum(jnp.where(live, got, 0.0)) / jnp.sum(live)


_score = jax.jit(_score_impl, static_argnums=(3, 4))


def score(p, tokens, length, cfg, quant=None) -> float:
    """Mean log-probability of ``tokens[0, 1:length]`` given the prefix.
    ``tokens`` is (1, bucket), padded past ``length``: attention is
    causal, so no real position sees the padding, and one compiled shape
    serves a whole bucket."""
    return float(_score(p, jnp.asarray(tokens), jnp.int32(length),
                        _Frozen(cfg), quant))
