"""Multi-head attention as (init, apply) pairs, plus a dense reference
softmax-attention kernel.

The reference repo ships no attention code (its eval workloads are
mnist/cifar/lstm/resnet/vgg torch images, ``test/mnist/mnist1.yaml:15``);
long-context workloads are first-class in the TPU build, so the workload
zoo grows a transformer family. Design notes (TPU-first):

- ``dot_product_attention`` keeps the score matmuls in bfloat16-friendly
  einsums (MXU) but runs the softmax accumulation in fp32.
- The attention inner function is pluggable (``attn_fn``) so the same
  transformer block runs dense on one chip or ring-parallel over an ``sp``
  mesh axis (:mod:`kubeshare_tpu.parallel.ringattention`) without the
  model knowing.
- All shapes static; masking is ``jnp.where`` with a finite floor, not
  ``-inf`` (NaN-safe under fp32 exp).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

# Finite mask floor: low enough that exp(floor - m) underflows to 0 for any
# realistic running max m, high enough that (floor - m) never overflows.
MASK_VALUE = -1e30


def kv_groups(heads: int, kv_heads: int) -> int:
    """Query heads per k/v head (grouped-query attention). THE
    divisibility check — every GQA entry point funnels through here."""
    if heads % kv_heads:
        raise ValueError(f"heads {heads} not divisible by kv_heads "
                         f"{kv_heads}")
    return heads // kv_heads


def expand_kv(k: jax.Array, v: jax.Array, heads: int):
    """Materialize grouped-query k/v to the full head count — the
    CLARITY implementation for dense paths (the Pallas kernel instead
    maps the group in block index arithmetic and never expands)."""
    hk = k.shape[2]
    if hk == heads:
        return k, v
    g = kv_groups(heads, hk)
    return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)


def dot_product_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                          causal: bool = True,
                          scale: float | None = None,
                          window: int | None = None) -> jax.Array:
    """Dense reference attention.

    ``q``: (batch, q_len, heads, head_dim); ``k``/``v``: (batch, kv_len,
    kv_heads, head_dim); returns (batch, q_len, heads, head_dim) in fp32.
    ``kv_heads`` may divide ``heads`` (grouped-query / multi-query
    attention — each group of heads//kv_heads query heads shares one
    k/v head); this reference expands k/v for clarity, the Pallas
    kernel (:mod:`.flash_attention`) instead reads this same layout as
    it lies (lane blocks of (batch, seq, heads·head_dim), two heads of
    64 a block) and maps the group in its block index arithmetic, so the
    smaller k/v never grows in HBM and nothing is transposed.
    ``window`` = sliding-window (local) attention: with ``causal``,
    query i sees keys in ``(i - window, i]`` — the Mistral-style band.
    The ring implementation is validated against this function.
    """
    if window is not None:
        # validate BEFORE any compute, mirroring the flash kernel's
        # _blocks: window=0 would silently mask everything (uniform
        # softmax over MASK_VALUE rows = garbage output)
        if not causal:
            raise ValueError("window requires causal=True (the band is "
                             "defined looking back from each query)")
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
    d = q.shape[-1]
    k, v = expand_kv(k, v, q.shape[2])
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    scores = jnp.einsum("bqhd,bkhd->bqhk", q.astype(jnp.float32),
                        k.astype(jnp.float32)) * scale
    if causal:
        nq, nk = scores.shape[1], scores.shape[-1]
        # Align the mask to the END of the kv sequence (q_len may be a
        # suffix of kv_len — not used by the models here, but the standard
        # convention).
        qidx = jnp.arange(nq) + (nk - nq)
        mask = qidx[:, None] >= jnp.arange(nk)[None, :]
        if window is not None:
            mask &= (qidx[:, None] - jnp.arange(nk)[None, :]) < window
        scores = jnp.where(mask[None, :, None, :], scores, MASK_VALUE)
    weights = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bqhk,bkhd->bqhd", weights, v.astype(jnp.float32))


def rope(x: jax.Array, positions: jax.Array | None = None,
         base: float = 10000.0) -> jax.Array:
    """Rotary position embedding (RoPE) over the head dimension.

    ``x``: (batch, seq, heads, head_dim), head_dim even. Each feature
    pair ``(x[i], x[i + d/2])`` rotates by ``pos · base^(-2i/d)`` —
    attention scores between rotated q/k then depend only on RELATIVE
    position, the property that lets windows slide and contexts extend
    (no learned position table to outgrow). Parameter-free, so it adds
    nothing to checkpoints; applied to q AND k before any ``attn_fn``,
    it composes unchanged with the flash kernel, GQA, sliding windows,
    ring and ulysses (rotation happens on the global arrays under jit —
    sequence sharding just shards the position iota).
    """
    b, s, h, d = x.shape
    if d % 2:
        raise ValueError(f"rope needs an even head_dim, got {d}")
    if positions is None:
        positions = jnp.arange(s)
    # arange(0, d, 2) is already 2i — dividing by d gives the standard
    # base^(-2i/d) wavelength ladder (Llama/Mistral-compatible)
    freqs = base ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos = jnp.cos(angles)[None, :, None, :]    # (1, s, 1, d/2)
    sin = jnp.sin(angles)[None, :, None, :]
    x1 = x[..., : d // 2].astype(jnp.float32)
    x2 = x[..., d // 2:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin,
                            x1 * sin + x2 * cos], axis=-1).astype(x.dtype)


def mha_init(key, dim: int, heads: int, kv_heads: int | None = None) -> dict:
    """Fused-QKV multi-head attention parameters (heads must divide dim).

    ``kv_heads`` < ``heads`` builds a grouped-query / multi-query block:
    the fused projection shrinks to (dim, dim + 2·kv_heads·head_dim) —
    less weight memory AND a kv cache smaller by heads/kv_heads."""
    if dim % heads:
        raise ValueError(f"dim {dim} not divisible by heads {heads}")
    kv_heads = heads if kv_heads is None else kv_heads
    kv_groups(heads, kv_heads)
    kvd = (dim // heads) * kv_heads
    kq, ko = jax.random.split(key)
    scale = math.sqrt(1.0 / dim)
    return {
        "qkv": jax.random.uniform(kq, (dim, dim + 2 * kvd), jnp.float32,
                                  -scale, scale),
        "out": jax.random.uniform(ko, (dim, dim), jnp.float32,
                                  -scale, scale),
    }


def mha_apply(params: dict, x: jax.Array, heads: int, causal: bool = True,
              attn_fn=None, dtype=None, use_rope: bool = False) -> jax.Array:
    """Multi-head self-attention over ``x``: (batch, seq, dim).

    ``attn_fn(q, k, v)`` defaults to causal :func:`dot_product_attention`;
    the sequence-parallel path passes a ring-attention closure instead.
    ``use_rope`` rotates q/k with :func:`rope` before the attention body.
    The kv head count is read off the ``qkv`` weight's shape, so grouped-
    query blocks (``mha_init(kv_heads=...)``) need no extra argument.
    """
    b, s, dim = x.shape
    hd = dim // heads
    w_qkv, w_out = params["qkv"], params["out"]
    # (dim + 2·kvd) columns → kv_heads = kvd // head_dim
    kvd = (w_qkv.shape[-1] - dim) // 2
    kv_heads = kvd // hd
    if dtype is not None:
        x, w_qkv, w_out = (x.astype(dtype), w_qkv.astype(dtype),
                           w_out.astype(dtype))
    qkv = x @ w_qkv            # (b, s, dim + 2·kvd) — one MXU matmul
    q = qkv[..., :dim].reshape(b, s, heads, hd)
    k = qkv[..., dim:dim + kvd].reshape(b, s, kv_heads, hd)
    v = qkv[..., dim + kvd:].reshape(b, s, kv_heads, hd)
    if use_rope:
        q, k = rope(q), rope(k)
    if attn_fn is None:
        o = dot_product_attention(q, k, v, causal=causal)
    else:
        o = attn_fn(q, k, v)
    o = o.reshape(b, s, dim).astype(w_out.dtype)
    return o @ w_out


# --- grouped-query attention with per-head q/k norm ---------------------------

def gqa_init(key, dim: int, heads: int, kv_heads: int,
             gated: bool = False) -> dict:
    """:func:`mha_init`'s fused projection plus the q/k norm gains;
    ``gated`` adds the (dim, dim) projection of a sigmoid output gate."""
    hd = dim // heads
    params = dict(mha_init(key, dim, heads, kv_heads=kv_heads),
                  q_norm=jnp.ones((hd,)), k_norm=jnp.ones((hd,)))
    if gated:
        scale = math.sqrt(1.0 / dim)
        params["gate"] = jax.random.uniform(
            jax.random.fold_in(key, 1), (dim, dim), jnp.float32, -scale,
            scale)
    return params


def gqa_apply(params: dict, x: jax.Array, heads: int, attn_fn=None,
              dtype=None, rope_base: float = 10000.0,
              eps: float = 1e-5, use_rope: bool = True,
              gated: bool = False) -> jax.Array:
    """Causal self-attention as the LFM2 / Qwen3 style decoders order it:
    project, RMS-norm q and k per head (``q_norm`` / ``k_norm``), THEN
    rotate (:func:`rope` at ``rope_base``), attend, project out. The norm
    sits before the rotation: a rotation keeps a pair's norm, a gain per
    feature after it would not commute with it. kv heads are read off the
    ``qkv`` weight as :func:`mha_apply` does.

    ``use_rope=False`` leaves q and k unrotated (a layer that takes its
    positions from elsewhere in the model); ``gated`` multiplies the
    attention's output by ``sigmoid(x @ params["gate"])`` before the
    output projection."""
    b, s, dim = x.shape
    hd = dim // heads
    w_qkv, w_out = params["qkv"], params["out"]
    kvd = (w_qkv.shape[-1] - dim) // 2
    if dtype is not None:
        x, w_qkv, w_out = (a.astype(dtype) for a in (x, w_qkv, w_out))
    qkv = x @ w_qkv
    q = qkv[..., :dim].reshape(b, s, heads, hd)
    k = qkv[..., dim:dim + kvd].reshape(b, s, kvd // hd, hd)
    v = qkv[..., dim + kvd:].reshape(b, s, kvd // hd, hd)
    from .layers import rmsnorm_apply   # here over each head's features
    turn = (lambda a: rope(a, base=rope_base)) if use_rope else (lambda a: a)
    q = turn(rmsnorm_apply({"scale": params["q_norm"]}, q, eps))
    k = turn(rmsnorm_apply({"scale": params["k_norm"]}, k, eps))
    if attn_fn is None:
        o = dot_product_attention(q, k, v, causal=True)
    else:
        o = attn_fn(q, k, v)
    o = o.reshape(b, s, dim)
    if gated:
        gate = x @ params["gate"].astype(x.dtype)
        o = o * jax.nn.sigmoid(gate.astype(jnp.float32))
    return o.astype(w_out.dtype) @ w_out
