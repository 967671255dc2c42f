"""Plain reference for the MiniCPM-SALA (``minicpm_sala``) configurations:
straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision.
No kernels, no chunked scan, no bfloat16 arithmetic, no proxy; imports
nothing of the program and takes nothing the program made. Weights come
from the seed by the same key splits, draws and leaf names as the
program's ``models/minicpm_sala.init`` (re-stated here, not imported), and
are ROUNDED to the precision the configuration holds them in
(``precision.params``, bfloat16: the rounded values are the model, as a
served checkpoint's are) before being used in float32.

With ``RMSNorm(x; g) = x / sqrt(mean(x^2) + eps) * g``, ``L`` the PUBLISHED
depth and ``r = scale_depth / sqrt(L)``: ``x0 = scale_emb * E[tokens]``;
every layer ``h = x + r * Mixer(RMSNorm(x))``, ``y = h + r * W_down(
silu(W_gate u) * W_up u)`` with ``u = RMSNorm(h)``; logits =
``RMSNorm(x_last) / (hidden_size / dim_model_base) @ W_head^T`` (untied).

- ``minicpm4``: fused ``[q, k, v] = u W_qkv`` (q ``heads`` x d, k and v
  ``kv_heads`` x d), q and k RMS-normed per head with learned gains, NO
  rotary, causal attention ``A`` at scale 1/sqrt(d),
  ``out = (A * sigmoid(u W_g)) W_o``. Up to ``dense_len`` tokens ``A`` is
  dense softmax attention. Past it, for query ``t`` and kv group ``g``:
  ``kc_j`` = mean of ``k[j*stride : j*stride + kernel_size]``, seen by ``t``
  only if ``j*stride + kernel_size - 1 <= t``; ``p_h = softmax_j(q_{h,t} .
  kc_j / sqrt(d))`` per head; ``s_j`` = the sum of ``p_h`` over the group's
  heads; a block of ``block_size`` keys scores the max of ``s_j`` over the
  windows that overlap it; the first ``init_blocks`` blocks and the
  ``window_size / block_size`` blocks ending at ``t``'s own are forced;
  the ``topk`` blocks of highest score (ties to the lower index), the
  forced ones among them, are the keys ``t`` attends to, causally, by
  softmax over exactly those keys: here an explicit (query x key) mask.
- ``lightning-attn``: ``[q, k, v] = u W_qkv`` (``heads`` x d each), q and k
  RMS-normed per head, rotary (half-split pairing) at ``rope_theta``,
  ``o_t = sum_{s<=t} exp(-lam_h (t-s)) (q_t . k_s / sqrt(d)) v_s`` with
  ``lam_h = 2^(-8 h / heads)``, h = 1..heads: the sum itself, a block of
  queries at a time against every key up to the block's end;
  RMS norm of ``o`` per head; ``out = (o * sigmoid(u W_g)) W_o``.

Queries are taken in blocks (and a long sequence in a few bands, each
against the keys up to its end) so that 32,768 tokens fit a chip.

``quant="int8"`` / ``"fp8"`` is the CONTROL, never the reference: both
operands of every matmul pass through a per-tensor-scaled int8 or
float8_e4m3 round trip first.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
ROWS = 512          # queries worked at once
BANDS = 4           # a long sequence's key ranges
MLP_ROWS = 4096


class _Frozen(dict):
    """A hashable view of the configuration for ``static_argnums``."""

    def __hash__(self):
        return hash(tuple(sorted((k, repr(v)) for k, v in self.items())))


def _sizes(cfg) -> dict:
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    a = cfg["assumed"]
    return {"d": d, "heads": heads, "hd": int(cfg["head_dim"]),
            "kv": int(cfg["num_key_value_heads"]),
            "width": int(cfg["intermediate_size"]),
            "kinds": list(cfg["mixer_types"]),
            "depth": int(cfg["published"]["num_hidden_layers"]),
            "vocab": int(cfg["vocab_size"]),
            "eps": float(cfg["rms_norm_eps"]),
            "theta": float(cfg["rope_theta"]),
            "scale_emb": float(cfg["scale_emb"]),
            "scale_depth": float(cfg["scale_depth"]),
            "base": int(cfg["dim_model_base"]),
            "kernel": int(a["kernel_size"]), "stride": int(a["kernel_stride"]),
            "block": int(a["block_size"]), "init": int(a["init_blocks"]),
            "window": int(a["window_size"]), "topk": int(a["topk"]),
            "dense_len": int(a["dense_len"]),
            "exponent": float(a["decay_exponent"]),
            "held": cfg["precision"]["params"]}


# -- weights from the seed ---------------------------------------------------

def _uniform(key, shape, scale):
    return jax.random.uniform(key, shape, jnp.float32, -scale, scale)


def init(key_words, cfg: dict) -> dict:
    """Flat ``{"layers/0/mixer/qkv": array, ...}`` in float32."""
    return _init(jnp.asarray(np.asarray(key_words, np.uint32)), _Frozen(cfg))


def _init_impl(key, cfg) -> dict:
    c = _sizes(cfg)
    d, hd = c["d"], c["hd"]
    held = ((lambda a: a.astype(jnp.dtype(c["held"])).astype(jnp.float32))
            if c["held"] != "float32" else (lambda a: a))
    ekey, hkey, *lkeys = jax.random.split(key, 2 + len(c["kinds"]))
    p = {"embed": held(jax.random.normal(ekey, (c["vocab"], d)) * 0.02),
         "head": held(jax.random.normal(hkey, (c["vocab"], d)) * 0.5)}
    s_d = math.sqrt(1.0 / d)
    for i, (kind, lkey) in enumerate(zip(c["kinds"], lkeys)):
        at = f"layers/{i}"
        kmix, kff = jax.random.split(lkey)
        kq, ko = jax.random.split(kmix)
        kvd = (c["kv"] if kind == "minicpm4" else c["heads"]) * hd
        p[f"{at}/mixer_norm/scale"] = jnp.ones((d,))
        p[f"{at}/mlp_norm/scale"] = jnp.ones((d,))
        p[f"{at}/mixer/qkv"] = held(_uniform(kq, (d, d + 2 * kvd), s_d))
        p[f"{at}/mixer/out"] = held(_uniform(ko, (d, d), s_d))
        p[f"{at}/mixer/gate"] = held(_uniform(jax.random.fold_in(kmix, 1),
                                              (d, d), s_d))
        p[f"{at}/mixer/q_norm"] = jnp.ones((hd,))
        p[f"{at}/mixer/k_norm"] = jnp.ones((hd,))
        if kind != "minicpm4":
            p[f"{at}/mixer/o_norm"] = jnp.ones((hd,))
            h = jnp.arange(1, c["heads"] + 1, dtype=jnp.float32)
            p[f"{at}/mixer/lam"] = jnp.exp2(-c["exponent"] * h / c["heads"])
        k1, k3, k2 = jax.random.split(kff, 3)
        w = c["width"]
        p[f"{at}/mlp/w1"] = held(_uniform(k1, (d, w), s_d))
        p[f"{at}/mlp/w3"] = held(_uniform(k3, (d, w), s_d))
        p[f"{at}/mlp/w2"] = held(_uniform(k2, (w, d), math.sqrt(1.0 / w)))
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


def _mm(eq, a, b, quant):
    if quant == "int8":
        a, b = _q8(a), _q8(b)
    elif quant == "fp8":
        a, b = _f8(a), _f8(b)
    elif quant is not None:
        raise ValueError(f"unknown control precision {quant!r}")
    return jnp.einsum(eq, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


def _rms(x, gain, eps):
    return x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                             + eps) * gain


def _rope(x, theta):
    """(seq, heads, d): pair ``(x[i], x[i + d/2])`` turns by ``pos *
    theta^(-2i/d)``."""
    s, _, d = x.shape
    freqs = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _by_query_blocks(fn, s):
    """``fn(t0, rows, keys) -> (rows, ...)`` over the whole sequence:
    blocks of :data:`ROWS` queries starting at ``t0`` (traced), each against
    the first ``keys`` (static) positions: a long sequence in
    :data:`BANDS` bands, each with the keys up to its own end."""
    rows = min(ROWS, s)
    bands = BANDS if s >= 4 * BANDS * rows else 1
    per, out = s // bands, []
    for b in range(bands):
        starts = b * per + jnp.arange(per // rows) * rows
        got = jax.lax.map(lambda t0: fn(t0, rows, (b + 1) * per), starts)
        out.append(got.reshape(per, *got.shape[2:]))
    return jnp.concatenate(out, 0)


def _rows(x, t0, rows):
    return jax.lax.dynamic_slice_in_dim(x, t0, rows, 0)


def _softmax_attention(q, k, v, allowed, c, quant):
    """(rows, heads, d) against (keys, kv, d) under ``allowed`` (rows, kv,
    keys): each kv head serves ``heads / kv`` query heads."""
    rows, heads, hd = q.shape
    g = heads // k.shape[1]
    qg = q.reshape(rows, k.shape[1], g, hd)
    sc = _mm("qngd,knd->ngqk", qg, k, quant) / math.sqrt(hd)
    sc = jnp.where(allowed.transpose(1, 0, 2)[:, None], sc, -jnp.inf)
    w = jax.nn.softmax(sc, axis=-1)
    return _mm("ngqk,knd->qngd", w, v, quant).reshape(rows, heads, hd)


def _chosen(q, kc, t, c, n_blocks, quant):
    """The blocks each query of a block chooses, (rows, kv, n_blocks)
    bool. ``q``: (rows, heads, d) at positions ``t``; ``kc``: (windows,
    kv, d)."""
    rows, heads, hd = q.shape
    kv, n_c = kc.shape[1], kc.shape[0]
    qg = q.reshape(rows, kv, heads // kv, hd)
    sc = _mm("qngd,jnd->ngqj", qg, kc, quant) / math.sqrt(hd)
    seen = (jnp.arange(n_c) * c["stride"] + c["kernel"] - 1)[None, :] \
        <= t[:, None]
    p = jax.nn.softmax(jnp.where(seen, sc, -jnp.inf), axis=-1)
    p = jnp.where(seen, p, 0.0)          # a query that sees no window yet
    group = p.sum(1).transpose(1, 0, 2)                 # (rows, kv, n_c)
    # the windows that overlap each block, listed (a handful a block)
    first = np.arange(n_c) * c["stride"]
    blocks = np.arange(n_blocks) * c["block"]
    overlap = ((first[:, None] < blocks[None, :] + c["block"])
               & (first[:, None] + c["kernel"] > blocks[None, :]))
    most = int(overlap.sum(0).max())
    which = np.argsort(~overlap, axis=0, kind="stable")[:most]
    listed = np.take_along_axis(overlap, which, 0)      # (most, n_blocks)
    score = jnp.max(jnp.where(listed, group[..., which], 0.0), axis=-2)
    blk, own = jnp.arange(n_blocks)[None, :], (t // c["block"])[:, None]
    forced = ((blk < c["init"])
              | (blk > own - c["window"] // c["block"])) & (blk <= own)
    score = jnp.where(forced[:, None], jnp.inf,
                      jnp.where((blk <= own)[:, None], score, -1.0))
    top, idx = jax.lax.top_k(score, min(c["topk"], n_blocks))
    onehot = jax.nn.one_hot(idx, n_blocks, dtype=jnp.bool_)
    return jnp.any(onehot & (top >= 0.0)[..., None], axis=-2)


def _minicpm4(p, at, u, c, quant):
    s, d, hd = u.shape[0], c["d"], c["hd"]
    kvd = c["kv"] * hd
    qkv = _mm("sd,de->se", u, p[f"{at}/mixer/qkv"], quant)
    q = _rms(qkv[:, :d].reshape(s, c["heads"], hd),
             p[f"{at}/mixer/q_norm"], c["eps"])
    k = _rms(qkv[:, d:d + kvd].reshape(s, c["kv"], hd),
             p[f"{at}/mixer/k_norm"], c["eps"])
    v = qkv[:, d + kvd:].reshape(s, c["kv"], hd)
    if s <= c["dense_len"]:
        def block(t0, rows, keys):
            t = t0 + jnp.arange(rows)
            allowed = jnp.broadcast_to(
                (jnp.arange(keys)[None, :] <= t[:, None])[:, None],
                (rows, c["kv"], keys))
            return _softmax_attention(_rows(q, t0, rows), k[:keys], v[:keys],
                                      allowed, c, quant)
    else:
        n_c = (s - c["kernel"]) // c["stride"] + 1
        kc = _pooled(k, c, n_c)

        def block(t0, rows, keys):
            t = t0 + jnp.arange(rows)
            qb = _rows(q, t0, rows)
            # windows and blocks that lie wholly inside the first `keys`
            # positions are all a query of this band can see or choose
            chosen = _chosen(qb, kc[:(keys - c["kernel"]) // c["stride"] + 1],
                             t, c, keys // c["block"], quant)
            allowed = (jnp.repeat(chosen, c["block"], axis=-1)
                       & (jnp.arange(keys)[None, :] <= t[:, None])[:, None])
            return _softmax_attention(qb, k[:keys], v[:keys], allowed, c,
                                      quant)
    a = _by_query_blocks(block, s).reshape(s, d)
    gate = jax.nn.sigmoid(_mm("sd,de->se", u, p[f"{at}/mixer/gate"], quant))
    return _mm("sd,de->se", a * gate, p[f"{at}/mixer/out"], quant)


def _pooled(k, c, n_c):
    """``kc_j`` = the mean of ``k[j*stride : j*stride + kernel_size]``."""
    idx = (jnp.arange(n_c) * c["stride"])[:, None] + jnp.arange(c["kernel"])
    return k[idx].mean(1)


def _lightning(p, at, u, c, quant):
    s, d, hd, heads = u.shape[0], c["d"], c["hd"], c["heads"]
    qkv = _mm("sd,de->se", u, p[f"{at}/mixer/qkv"], quant)
    q, k, v = (qkv[:, i * d:(i + 1) * d].reshape(s, heads, hd)
               for i in range(3))
    q = _rope(_rms(q, p[f"{at}/mixer/q_norm"], c["eps"]), c["theta"])
    k = _rope(_rms(k, p[f"{at}/mixer/k_norm"], c["eps"]), c["theta"])
    lam = p[f"{at}/mixer/lam"]

    def block(t0, rows, keys):
        gap = ((t0 + jnp.arange(rows))[:, None]
               - jnp.arange(keys)[None, :]).astype(jnp.float32)
        decay = jnp.where(gap >= 0, jnp.exp(-lam[:, None, None]
                                            * jnp.maximum(gap, 0.0)), 0.0)
        sc = _mm("qhd,khd->hqk", _rows(q, t0, rows), k[:keys], quant) \
            / math.sqrt(hd)
        return _mm("hqk,khd->qhd", sc * decay, v[:keys], quant)

    o = _rms(_by_query_blocks(block, s), p[f"{at}/mixer/o_norm"], c["eps"])
    gate = jax.nn.sigmoid(_mm("sd,de->se", u, p[f"{at}/mixer/gate"], quant))
    return _mm("sd,de->se", o.reshape(s, d) * gate, p[f"{at}/mixer/out"],
               quant)


def _mlp(p, at, u, quant):
    def block(ub):
        g = jax.nn.silu(_mm("sd,dw->sw", ub, p[f"{at}/mlp/w1"], quant))
        return _mm("sw,wd->sd", g * _mm("sd,dw->sw", ub, p[f"{at}/mlp/w3"],
                                        quant), p[f"{at}/mlp/w2"], quant)
    s = u.shape[0]
    if s <= MLP_ROWS:
        return block(u)
    return jax.lax.map(block, u.reshape(s // MLP_ROWS, MLP_ROWS, -1)
                       ).reshape(s, -1)


def logits_fn(p, tokens, cfg, quant=None):
    """``tokens`` (1, seq) int32 -> logits (1, seq, vocab) float32."""
    c = _sizes(cfg)
    r = c["scale_depth"] / math.sqrt(c["depth"])
    x = c["scale_emb"] * p["embed"][tokens[0]]
    for i, kind in enumerate(c["kinds"]):
        at = f"layers/{i}"
        u = _rms(x, p[f"{at}/mixer_norm/scale"], c["eps"])
        mixer = _minicpm4 if kind == "minicpm4" else _lightning
        x = x + r * mixer(p, at, u, c, quant)
        u = _rms(x, p[f"{at}/mlp_norm/scale"], c["eps"])
        x = x + r * _mlp(p, at, u, quant)
    x = _rms(x, p["norm_f/scale"], c["eps"]) / (c["d"] / c["base"])
    return _mm("sd,vd->sv", x, p["head"], quant)[None]


# -- scoring -----------------------------------------------------------------

def _score_impl(p, tokens, length, cfg, quant):
    logp = jax.nn.log_softmax(logits_fn(p, tokens, cfg, quant)[0, :-1])
    got = jnp.take_along_axis(logp, tokens[0, 1:, None], -1)[:, 0]
    live = jnp.arange(1, tokens.shape[1]) < length
    return jnp.sum(jnp.where(live, got, 0.0)) / jnp.sum(live)


_score = jax.jit(_score_impl, static_argnums=(3, 4))


def score(p, tokens, length, cfg, quant=None) -> float:
    """Mean log-probability of ``tokens[0, 1:length]`` given the prefix.
    ``tokens`` is (1, bucket), padded past ``length``: every mixer is
    causal (a compressed window is seen only once it has ended), so no
    real position sees the padding, and one compiled shape serves a whole
    bucket."""
    return float(_score(p, jnp.asarray(tokens), jnp.int32(length),
                        _Frozen(cfg), quant))
