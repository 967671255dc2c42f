"""MiniCPM-SALA-style hybrid decoder LM: block-sparse softmax attention and
decayed linear ("lightning") attention by a per-layer list, a gated SiLU
MLP in every layer, RMSNorm throughout, muP scalings, an output head that
is not the embedding's transpose. Forward only: the scoring path.

With ``r = scale_depth / sqrt(layers_published)`` every layer is
``h = x + r * Mixer(RMSNorm(x))``, ``y = h + r * MLP(RMSNorm(h))``; the
stream starts at ``scale_emb * E[tokens]`` and the logits are
``RMSNorm(x) / (hidden / dim_model_base) @ W_head^T``. ``Mixer`` by
``cfg.mixer_types[i]``:

- ``minicpm4`` (:func:`..ops.attention.gqa_apply` with no rotary and a
  sigmoid output gate): q/k RMS-normed per head, grouped queries, causal.
  A sequence of at most ``cfg.dense_len`` tokens attends densely
  (``attn_fn``, the flash kernel in the benchmark's binding); a longer one
  chooses ``cfg.topk`` blocks of ``cfg.block_size`` keys a (query, kv
  group) (:func:`..ops.sparse_attention.select_blocks`) and attends to
  those alone (:func:`..ops.sparse_attention.chosen_blocks_attention`);
- ``lightning-attn``: q/k RMS-normed per head, rotary at ``rope_theta``,
  ``o_t = sum_{s<=t} exp(-lam_h (t-s)) (q_t . k_s / sqrt(d)) v_s`` as a
  chunked scan (:func:`..ops.linear_attention.lightning_attention`), an
  RMS norm of ``o`` per head, a sigmoid output gate.

The model is configured by ONE frozen :class:`Config` handed to every
function (the pattern of :mod:`.lfm2`): no environment variable, no module
constant. The default is a tiny preset for the CPU; a configuration at
published widths is built by its caller
(``benchmark/models/minicpm_sala.py``).

Matrices, the embedding and the head are HELD in ``cfg.dtype`` (bfloat16:
a served checkpoint's precision); norm gains and decay rates float32.
bfloat16 matmuls and residual stream; float32 norms, softmax, selection
scores, decay and scan state, logits. Static shapes throughout.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from ..ops.attention import dot_product_attention, gqa_apply, gqa_init
from ..ops.layers import (gated_mlp_apply, gated_mlp_init, rmsnorm_apply,
                          rmsnorm_init)
from ..ops.linear_attention import lightning_attention, slopes
from ..ops.sparse_attention import chosen_blocks_attention, select_blocks

MIXERS = ("minicpm4", "lightning-attn")
#: the head's rows are drawn this wide so that random weights give logits
#: the spread a trained model's have (behind the muP divisor, hidden /
#: dim_model_base = 16 at the published width, their standard deviation is
#: ~2): at 0.02 every log-probability is -log(vocab) to three digits, and
#: the mean over a document's 10^4 tokens then differs between precisions
#: only in float32's last bits (PERF.md, PR 34). Chosen, after that
#: reading, FOR the benchmark's comparison to see the arithmetic at all: it
#: is no part of the architecture, and a checkpoint's head replaces it
HEAD_STD = 0.5
#: default names of the scopes the lightning scan, the block selection
#: and the attention over chosen blocks are traced under
SCOPES = ("lin_attn", "sparse_select", "sparse_attn")


@dataclass(frozen=True)
class Config:
    """Sizes of one decoder. ``layers_published`` is the depth the
    residual scaling is taken from (a program that holds a few layers of a
    deeper model keeps the deeper model's ``r``); ``vocab`` is what this
    program holds of the model's vocabulary (ids, logits and scores are
    over it)."""

    hidden: int = 64
    heads: int = 4
    kv_heads: int = 2
    mlp_width: int = 160
    mixer_types: tuple[str, ...] = ("minicpm4", "lightning-attn",
                                    "lightning-attn", "lightning-attn")
    layers_published: int | None = None
    vocab: int = 256
    scale_emb: float = 12.0
    scale_depth: float = 1.4
    dim_model_base: int = 256
    norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    #: lightning attention: slope exponent (``lam_h = 2^(-e h / heads)``)
    #: and the scan's chunk (``None``: the kernel's default)
    decay_exponent: float = 8.0
    chunk: int | None = None
    #: the sparse layers' selection
    kernel_size: int = 8
    kernel_stride: int = 4
    block_size: int = 8
    init_blocks: int = 1
    window_size: int = 16
    topk: int = 4
    dense_len: int = 64
    #: matrices, the residual stream and the matmul operands; ``float32``
    #: is for tests that hold the model to its float32 reference
    dtype: str = "bfloat16"

    def __post_init__(self):
        unknown = set(self.mixer_types) - set(MIXERS)
        if unknown:
            raise ValueError(f"unknown mixer types {sorted(unknown)}; "
                             f"have {MIXERS}")
        if self.hidden % self.heads or self.heads % self.kv_heads:
            raise ValueError("heads must divide hidden, kv_heads heads")
        if (self.kernel_size % self.kernel_stride
                or self.block_size % self.kernel_stride
                or self.window_size % self.block_size):
            raise ValueError("kernel_size and block_size must be whole "
                             "strides, window_size whole blocks")

    @property
    def residual_scale(self) -> float:
        depth = self.layers_published or len(self.mixer_types)
        return self.scale_depth / math.sqrt(depth)


TINY = Config()
BATCH_SIZE, SEQ_LEN = 2, 32


def init(key, cfg: Config = TINY) -> dict:
    """Draws in float32, held in ``cfg.dtype``: what is rounded here IS the
    model (a reference re-draws and rounds the same way)."""
    dtype = jnp.dtype(cfg.dtype)
    held = lambda tree: jax.tree_util.tree_map(lambda a: a.astype(dtype),
                                               tree)
    ekey, hkey, *lkeys = jax.random.split(key, 2 + len(cfg.mixer_types))
    hd = cfg.hidden // cfg.heads
    layers = []
    for kind, lkey in zip(cfg.mixer_types, lkeys):
        kmix, kff = jax.random.split(lkey)
        layer = {"mixer_norm": rmsnorm_init(cfg.hidden),
                 "mlp_norm": rmsnorm_init(cfg.hidden),
                 "mlp": held(gated_mlp_init(kff, cfg.hidden, cfg.mlp_width))}
        kv = cfg.kv_heads if kind == "minicpm4" else cfg.heads
        mixer = gqa_init(kmix, cfg.hidden, cfg.heads, kv, gated=True)
        norms = {n: mixer.pop(n) for n in ("q_norm", "k_norm")}
        layer["mixer"] = dict(held(mixer), **norms)
        if kind == "lightning-attn":
            layer["mixer"]["o_norm"] = jnp.ones((hd,))
            layer["mixer"]["lam"] = slopes(cfg.heads, cfg.decay_exponent)
        layers.append(layer)
    normal = lambda k, std: (jax.random.normal(k, (cfg.vocab, cfg.hidden))
                             * std).astype(dtype)
    return {"embed": normal(ekey, 0.02), "head": normal(hkey, HEAD_STD),
            "layers": layers, "norm_f": rmsnorm_init(cfg.hidden)}


def _sparse_fn(cfg: Config, attn_fn, scopes):
    """What a ``minicpm4`` layer hands :func:`gqa_apply` as ``attn_fn``:
    dense up to ``dense_len`` tokens, the chosen blocks past it."""
    def attend(q, k, v):
        if q.shape[1] <= cfg.dense_len:
            return attn_fn(q, k, v)
        with jax.named_scope(scopes[1]):
            bits = select_blocks(
                q, k, kernel_size=cfg.kernel_size, stride=cfg.kernel_stride,
                block_size=cfg.block_size, init_blocks=cfg.init_blocks,
                window_blocks=cfg.window_size // cfg.block_size,
                topk=cfg.topk)
        with jax.named_scope(scopes[2]):
            return chosen_blocks_attention(q, k, v, bits, cfg.block_size)
    return attend


def _lightning_fn(params: dict, cfg: Config, scopes):
    """``attn_fn`` of a ``lightning-attn`` layer: the scan, then the RMS
    norm of its output per head."""
    def attend(q, k, v):
        with jax.named_scope(scopes[0]):
            o = lightning_attention(q, k, v, params["lam"], chunk=cfg.chunk)
        return rmsnorm_apply({"scale": params["o_norm"]}, o, cfg.norm_eps)
    return attend


def _layer(layer: dict, x: jax.Array, *, cfg: Config, attn_fn, scopes):
    dtype, r = jnp.dtype(cfg.dtype), cfg.residual_scale
    mixer = layer["mixer"]
    u = rmsnorm_apply(layer["mixer_norm"], x, cfg.norm_eps)
    if "lam" in mixer:
        fn, rotate = _lightning_fn(mixer, cfg, scopes), True
    else:
        fn, rotate = _sparse_fn(cfg, attn_fn, scopes), False
    op = gqa_apply(mixer, u, cfg.heads, attn_fn=fn, dtype=dtype,
                   rope_base=cfg.rope_theta, eps=cfg.norm_eps,
                   use_rope=rotate, gated=True)
    x = x + (r * op.astype(jnp.float32)).astype(dtype)
    u = rmsnorm_apply(layer["mlp_norm"], x, cfg.norm_eps)
    ff = gated_mlp_apply(layer["mlp"], u, dtype=dtype)
    return x + (r * ff.astype(jnp.float32)).astype(dtype)


def apply(params: dict, tokens: jax.Array, cfg: Config = TINY, attn_fn=None,
          scopes: tuple[str, str, str] = SCOPES) -> jax.Array:
    """``tokens``: (batch, seq) int32 -> logits (batch, seq, cfg.vocab)
    float32. ``attn_fn(q, k, v)`` overrides the dense causal attention of
    the ``minicpm4`` layers' short path (the flash kernel takes the grouped
    k/v as they are); ``scopes`` names the scopes of the scan, the
    selection and the attention over chosen blocks."""
    dense = attn_fn or dot_product_attention
    dtype = jnp.dtype(cfg.dtype)
    x = (cfg.scale_emb * params["embed"][tokens].astype(jnp.float32)
         ).astype(dtype)
    for lp in params["layers"]:
        x = _layer(lp, x, cfg=cfg, attn_fn=dense, scopes=scopes)
    x = rmsnorm_apply(params["norm_f"], x.astype(jnp.float32), cfg.norm_eps)
    x = (x / (cfg.hidden / cfg.dim_model_base)).astype(dtype)
    return jnp.einsum("bsd,vd->bsv", x, params["head"].astype(dtype),
                      preferred_element_type=jnp.float32)


def score_fn(params: dict, tokens: jax.Array, cfg: Config = TINY,
             **apply_kwargs) -> jax.Array:
    """Mean log-probability of each row's tokens given their prefixes."""
    logp = jax.nn.log_softmax(apply(params, tokens, cfg, **apply_kwargs)
                              [:, :-1])
    return jnp.take_along_axis(logp, tokens[:, 1:, None], -1).mean((1, 2))


def batch_fn(key):
    return jax.random.randint(key, (BATCH_SIZE, SEQ_LEN), 0, TINY.vocab)


if __name__ == "__main__":
    import argparse
    import time

    parser = argparse.ArgumentParser(prog="minicpm_sala")
    parser.add_argument("--steps", type=int, default=3)
    args = parser.parse_args()
    params = init(jax.random.PRNGKey(0))
    fn = jax.jit(score_fn)
    for step in range(args.steps):
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(params, batch_fn(
            jax.random.PRNGKey(step))))
        print(f"step {step}: mean log-prob {float(out.mean()):.4f} "
              f"({1e3 * (time.perf_counter() - t0):.1f} ms)")
