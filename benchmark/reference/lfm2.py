"""Plain reference for the LFM2 (``lfm2_moe``) configurations:
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision.
No kernels, no bfloat16, no proxy; imports nothing of the program and
takes nothing the program made. Weights come from the seed by the same
key splits, draws and leaf names as the program's ``models/lfm2.init``
(re-stated here, not imported).

Follows the family as its ``config.json`` and published modelling code
describe it. With ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g`` every
layer is ``h = x + Op(RMSNorm(x; g_op))``, ``y = h + FF(RMSNorm(h; g_ff))``:

- ``Op`` conv: ``[B, C, X] = split3(u W_in)``; ``z = B * X``;
  ``c_t = sum_j k_j z_{t-(L-1)+j}`` (depthwise, causal, zeros before the
  start); ``Op = (C * c) W_out``;
- ``Op`` attention: fused ``[q, k, v] = u W_qkv`` (q ``heads`` x 64, k and
  v ``kv_heads`` x 64, no bias); q and k RMS-normed over their 64 with
  learned gains; RoPE (half-split pairing) on q, k; causal
  ``softmax(q k^T / 8) v``, each kv head serving ``heads / kv_heads``
  query heads (computed one kv head at a time so that a 4,096 row's
  scores fit); ``Op = concat(heads) W_o``;
- ``FF`` dense (the leading ``num_dense_layers``):
  ``(silu(u W_1) * (u W_3)) W_2``;
- ``FF`` experts: ``s = sigmoid(u W_r)`` over ALL published experts;
  ``sel = top_k(s + b)`` (``b`` selects and never weights);
  ``w = s[sel] / (sum s[sel] + 1e-6) * routed_scaling_factor``;
  ``FF = sum over the HELD experts e in sel of w_e (silu(u W1_e) *
  (u W3_e)) W2_e``: a dense loop over the experts this chip holds, each
  masked to the tokens that chose it. What the absent experts would add
  is left out, as in the program (the configuration's ``deployment``);
- after the last layer one RMSNorm; logits on the embedding's transpose
  (tied), over the held slice of the vocabulary.

``quant="int8"`` / ``"fp8"`` is the CONTROL, never the reference: both
operands of every matmul the configuration states as bfloat16 (all but
the router's) pass through a per-tensor-scaled int8 or float8_e4m3 round
trip first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST


class _Frozen(dict):
    """A hashable view of the configuration for ``static_argnums``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def _sizes(cfg) -> dict:
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    dep = cfg["deployment"]
    return {"d": d, "heads": heads, "hd": d // heads,
            "kv": int(cfg["num_key_value_heads"]),
            "dense": int(cfg["intermediate_size"]),
            "width": int(cfg["moe_intermediate_size"]),
            "kinds": list(cfg["layer_types"]),
            "n_dense": int(cfg["num_dense_layers"]),
            "experts": int(cfg["published"]["num_experts"]),
            "held": int(cfg["num_experts"]),
            "first": int(dep["first_expert"]),
            "top_k": int(cfg["num_experts_per_tok"]),
            "scaling": float(cfg["routed_scaling_factor"]),
            "vocab": int(cfg["vocab_size"]),
            "taps": int(cfg["conv_L_cache"]),
            "eps": float(cfg["norm_eps"]),
            "theta": float(cfg["rope_parameters"]["rope_theta"])}


# -- weights from the seed ---------------------------------------------------

def _uniform(key, shape, scale):
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale)


def init(key_words, cfg: dict) -> dict:
    """Flat ``{"layers/0/conv/in": array, ...}`` in float32."""
    return _init(jnp.asarray(np.asarray(key_words, np.uint32)), _Frozen(cfg))


def _init_impl(key, cfg) -> dict:
    c = _sizes(cfg)
    d = c["d"]
    ekey, *lkeys = jax.random.split(key, 1 + len(c["kinds"]))
    p = {"embed": jax.random.normal(ekey, (c["vocab"], d)) * 0.02}
    s_d = math.sqrt(1.0 / d)
    for i, (kind, lkey) in enumerate(zip(c["kinds"], lkeys)):
        at = f"layers/{i}"
        kop, kff = jax.random.split(lkey)
        p[f"{at}/op_norm/scale"] = jnp.ones((d,))
        p[f"{at}/ff_norm/scale"] = jnp.ones((d,))
        if kind == "conv":
            ki, kc, ko = jax.random.split(kop, 3)
            p[f"{at}/conv/in"] = _uniform(ki, (d, 3 * d), s_d)
            p[f"{at}/conv/conv"] = _uniform(kc, (c["taps"], d),
                                            math.sqrt(1.0 / c["taps"]))
            p[f"{at}/conv/out"] = _uniform(ko, (d, d), s_d)
        else:
            kq, ko = jax.random.split(kop)
            kvd = c["kv"] * c["hd"]
            p[f"{at}/attn/qkv"] = _uniform(kq, (d, d + 2 * kvd), s_d)
            p[f"{at}/attn/out"] = _uniform(ko, (d, d), s_d)
            p[f"{at}/attn/q_norm"] = jnp.ones((c["hd"],))
            p[f"{at}/attn/k_norm"] = jnp.ones((c["hd"],))
        if i < c["n_dense"]:
            k1, k3, k2 = jax.random.split(kff, 3)
            h = c["dense"]
            p[f"{at}/mlp/w1"] = _uniform(k1, (d, h), s_d)
            p[f"{at}/mlp/w3"] = _uniform(k3, (d, h), s_d)
            p[f"{at}/mlp/w2"] = _uniform(k2, (h, d), math.sqrt(1.0 / h))
        else:
            kr, k1, k3, k2 = jax.random.split(kff, 4)
            h, held = c["width"], c["held"]
            p[f"{at}/moe/router"] = _uniform(kr, (d, c["experts"]), s_d)
            p[f"{at}/moe/expert_bias"] = jnp.zeros((c["experts"],))
            p[f"{at}/moe/w1"] = _uniform(k1, (held, d, h), s_d)
            p[f"{at}/moe/w3"] = _uniform(k3, (held, d, h), s_d)
            p[f"{at}/moe/w2"] = _uniform(k2, (held, h, d),
                                         math.sqrt(1.0 / h))
    p["norm_f/scale"] = jnp.ones((d,))
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


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1,
                                      keepdims=True) + eps) * gain


def _rope(x, theta):
    """(seq, heads, hd): the pair ``(x[i], x[i + hd/2])`` turns by
    ``pos * theta^(-2i/hd)``."""
    s, _, hd = x.shape
    freqs = theta ** (-jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _conv_op(p, at, u, c, quant):
    b, cc, x = jnp.split(_mm("sd,de->se", u, p[f"{at}/conv/in"], quant), 3,
                         axis=-1)
    z, taps = b * x, p[f"{at}/conv/conv"]
    n, seq = c["taps"], u.shape[0]
    padded = jnp.pad(z, ((n - 1, 0), (0, 0)))
    conv = sum(taps[j] * padded[j:j + seq] for j in range(n))
    return _mm("sd,de->se", cc * conv, p[f"{at}/conv/out"], quant)


def _attention_op(p, at, u, c, quant):
    seq, d, hd, kv = u.shape[0], c["d"], c["hd"], c["kv"]
    group = c["heads"] // kv
    qkv = _mm("sd,de->se", u, p[f"{at}/attn/qkv"], quant)
    q = qkv[:, :d].reshape(seq, c["heads"], hd)
    k = qkv[:, d:d + kv * hd].reshape(seq, kv, hd)
    v = qkv[:, d + kv * hd:].reshape(seq, kv, hd)
    q = _rope(_rms(q, p[f"{at}/attn/q_norm"], c["eps"]), c["theta"])
    k = _rope(_rms(k, p[f"{at}/attn/k_norm"], c["eps"]), c["theta"])
    mask = jnp.arange(seq)[:, None] >= jnp.arange(seq)[None, :]

    @jax.checkpoint
    def one_kv_head(qkv_g):
        qg, kg, vg = qkv_g            # (seq, group, hd), (seq, hd) x 2
        sc = _mm("qgd,kd->gqk", qg, kg, quant) / math.sqrt(hd)
        w = jax.nn.softmax(jnp.where(mask[None], sc, -jnp.inf), axis=-1)
        return _mm("gqk,kd->qgd", w, vg, quant)

    o = jax.lax.map(one_kv_head, (
        q.reshape(seq, kv, group, hd).transpose(1, 0, 2, 3),
        k.transpose(1, 0, 2), v.transpose(1, 0, 2)))
    o = o.transpose(1, 0, 2, 3).reshape(seq, d)
    return _mm("sd,de->se", o, p[f"{at}/attn/out"], quant)


def _gated(u, w1, w3, w2, quant):
    h = jax.nn.silu(_mm("sd,dh->sh", u, w1, quant)) * _mm("sd,dh->sh", u, w3,
                                                          quant)
    return _mm("sh,hd->sd", h, w2, quant)


def _experts_ff(p, at, u, c, quant):
    scores = jax.nn.sigmoid(jnp.einsum("sd,de->se", u,
                                       p[f"{at}/moe/router"], precision=HI))
    _, sel = jax.lax.top_k(scores + p[f"{at}/moe/expert_bias"], c["top_k"])
    picked = jnp.take_along_axis(scores, sel, axis=-1)
    w = picked / (picked.sum(-1, keepdims=True) + 1e-6) * c["scaling"]
    out = jnp.zeros_like(u)
    for e in range(c["held"]):
        mine = jnp.where(sel == c["first"] + e, w, 0.0).sum(-1)
        out = out + mine[:, None] * _gated(
            u, p[f"{at}/moe/w1"][e], p[f"{at}/moe/w3"][e],
            p[f"{at}/moe/w2"][e], quant)
    return out


def _layer(p, i, x, cfg, quant):
    """One layer over one sequence ``x``: (seq, hidden)."""
    c, at = _sizes(cfg), f"layers/{i}"
    u = _rms(x, p[f"{at}/op_norm/scale"], c["eps"])
    op = _conv_op if c["kinds"][i] == "conv" else _attention_op
    x = x + op(p, at, u, c, quant)
    u = _rms(x, p[f"{at}/ff_norm/scale"], c["eps"])
    if i < c["n_dense"]:
        return x + _gated(u, p[f"{at}/mlp/w1"], p[f"{at}/mlp/w3"],
                          p[f"{at}/mlp/w2"], quant)
    return x + _experts_ff(p, at, u, c, quant)


def _row_logits(p, tokens, cfg, quant):
    x = p["embed"][tokens]
    for i in range(len(cfg["layer_types"])):
        x = jax.checkpoint(_layer, static_argnums=(1, 3, 4))(
            p, i, x, cfg, quant)
    x = _rms(x, p["norm_f/scale"], float(cfg["norm_eps"]))
    return _mm("sd,vd->sv", x, p["embed"], quant)


def logits_fn(p, tokens, cfg, quant=None):
    """``tokens`` (rows, seq) int32 -> logits (rows, seq, vocab) f32."""
    return jnp.stack([_row_logits(p, row, cfg, quant) for row in tokens])


def _nll_sum(p, tokens, targets, cfg, quant):
    logp = jax.nn.log_softmax(logits_fn(p, tokens, cfg, quant))
    return -jnp.sum(jnp.take_along_axis(logp, targets[..., None], -1))


# -- training: loss, gradients, Adam -----------------------------------------

def loss_and_grads(p, tokens, targets, cfg, quant=None):
    """Mean cross entropy over every token of the batch and its gradient,
    accumulated one row at a time so that it fits beside the state."""
    frozen = _Frozen(cfg)
    total, acc = 0.0, None
    for r in range(tokens.shape[0]):
        s, g = _grad_fn(p, tokens[r:r + 1], targets[r:r + 1], frozen, quant)
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
    return {"t": 0, "m": {k: jnp.zeros_like(v) for k, v in p.items()},
            "v": {k: jnp.zeros_like(v) for k, v in p.items()}}


def _adam_leaf_impl(p, g, m, v, c1, c2, lr, b1, b2, eps):
    m = b1 * m + (1 - b1) * g
    v = b2 * v + (1 - b2) * g * g
    return p - lr * (m / c1) / (jnp.sqrt(v / c2) + eps), m, v


# in place: the state is 5.6 GB, and a second copy would not fit beside
# the gradients (the first parameters are re-drawn from the seed at the end)
_adam_leaf = jax.jit(_adam_leaf_impl, donate_argnums=(0, 2, 3))


def adam_step(p, g, state, lr, b1=0.9, b2=0.999, eps=1e-8):
    """Adam (Kingma & Ba 2014), bias-corrected, epsilon outside the root.
    Consumes ``p`` and ``state``."""
    t = state["t"] + 1
    c1, c2 = 1.0 - b1 ** t, 1.0 - b2 ** t
    new_p, new_m, new_v = {}, {}, {}
    for k in list(p):
        new_p[k], new_m[k], new_v[k] = _adam_leaf(
            p.pop(k), g[k], state["m"].pop(k), state["v"].pop(k), c1, c2,
            lr, b1, b2, eps)
    return new_p, {"t": t, "m": new_m, "v": new_v}


_norms = jax.jit(lambda t: {k: jnp.sqrt(jnp.sum(jnp.square(v)))
                            for k, v in t.items()})
_delta_norms = jax.jit(lambda a, b: {k: jnp.sqrt(jnp.sum(jnp.square(
    a[k] - b[k]))) for k in a})


def _floats(tree: dict) -> dict:
    return {k: float(v) for k, v in jax.device_get(tree).items()}


def train_readings(key_words, cfg, batches, lr, quant=None, rows=None,
                   keep_state=False):
    """Follow a trainer's first ``len(batches)`` steps from the seed:
    each step's loss, the first gradient's norm per leaf and the norm of
    the parameters' change per leaf after the last step.

    ``rows`` (a slice) and ``keep_state`` plant the faults the tests read:
    part of the batch left out with the mean over the rest, and a step
    that returns its state unchanged."""
    p = init(key_words, cfg)
    state = adam_init(p)
    losses, grad_norms = [], None
    for i, (tokens, targets) in enumerate(batches):
        if rows is not None:
            tokens, targets = tokens[rows], targets[rows]
        loss, g = loss_and_grads(p, jnp.asarray(tokens),
                                 jnp.asarray(targets), cfg, quant)
        losses.append(float(loss))
        if i == 0:
            grad_norms = _floats(_norms(g))
        if not keep_state:
            p, state = adam_step(p, g, state, lr)
        del g
    del state
    delta = _floats(_delta_norms(p, init(key_words, cfg)))
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
    """Mean log-probability of ``tokens[0, 1:length]`` given the prefix;
    ``tokens`` is (1, bucket), padded past ``length`` (attention and the
    convolution are causal: no real position sees the padding)."""
    return float(_score(p, jnp.asarray(tokens), jnp.int32(length),
                        _Frozen(cfg), quant))
