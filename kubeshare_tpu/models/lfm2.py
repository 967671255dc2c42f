"""LFM2-style hybrid decoder LM: gated short convolutions and
grouped-query attention by a per-layer list, dense gated MLPs first and
top-k sigmoid-routed experts after, RMSNorm throughout, tied embeddings.

Every layer is ``h = x + Op(RMSNorm(x))``, ``y = h + FF(RMSNorm(h))`` with

- ``Op`` by ``cfg.layer_types[i]``: ``conv`` (:mod:`..ops.shortconv`) or
  ``full_attention`` (:func:`..ops.attention.gqa_apply`: q/k RMS-normed
  per head, then RoPE at ``cfg.rope_theta``, causal, kv heads shared by
  groups of query heads);
- ``FF`` a dense gated MLP in the first ``cfg.num_dense_layers`` layers
  and :func:`..ops.moe.topk_moe_apply` after: the router scores ALL
  ``cfg.experts``, this program holds ``cfg.experts_held`` of them from
  ``cfg.first_expert`` (one chip's share under expert parallelism; all of
  them by default) and adds their part of the result.

The model is configured by ONE frozen :class:`Config` handed to every
function: no environment variable, no module constant. The default is a
tiny preset for the CPU; a configuration at published widths is built by
its caller (``benchmark/models/lfm2.py`` builds LFM2-24B-A2B's).

TPU-first as :mod:`.transformer`: bfloat16 matmuls and residual stream,
float32 norms, router, softmax and loss, static shapes throughout.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp

from ..ops.attention import gqa_apply, gqa_init
from ..ops.layers import (gated_mlp_apply, gated_mlp_init, rmsnorm_apply,
                          rmsnorm_init)
from ..ops.losses import softmax_cross_entropy
from ..ops.moe import topk_moe_apply, topk_moe_init
from ..ops.shortconv import short_conv_apply, short_conv_init
from .common import main_cli, synthetic_token_batch

OPERATORS = ("conv", "full_attention")


@dataclass(frozen=True)
class Config:
    """Sizes of one decoder. ``experts`` and ``vocab_published`` are the
    model's; ``experts_held`` / ``first_expert`` and ``vocab`` are what
    this program holds of them (``None``: all the experts)."""

    hidden: int = 64
    heads: int = 4
    kv_heads: int = 2
    dense_width: int = 160
    expert_width: int = 48
    layer_types: tuple[str, ...] = ("conv", "full_attention", "conv",
                                    "conv", "conv")
    num_dense_layers: int = 1
    experts: int = 16
    experts_held: int | None = None
    first_expert: int = 0
    experts_per_token: int = 4
    routed_scaling: float = 1.0
    vocab: int = 256
    vocab_published: int | None = None
    conv_kernel: int = 3
    norm_eps: float = 1e-5
    rope_theta: float = 1e6
    #: matmul operands and the residual stream; ``float32`` is for tests
    #: that hold the model to its float32 reference
    dtype: str = "bfloat16"

    def __post_init__(self):
        unknown = set(self.layer_types) - set(OPERATORS)
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}; "
                             f"have {OPERATORS}")
        held = self.held
        if not 0 <= self.first_expert <= self.experts - held:
            raise ValueError(f"experts {self.first_expert}.."
                             f"{self.first_expert + held} are not among "
                             f"the model's {self.experts}")
        if self.experts_per_token > self.experts:
            raise ValueError("more experts per token than experts")

    @property
    def held(self) -> int:
        return self.experts if self.experts_held is None \
            else self.experts_held


TINY = Config()
BATCH_SIZE, SEQ_LEN = 4, 32


def init(key, cfg: Config = TINY) -> dict:
    ekey, *lkeys = jax.random.split(key, 1 + len(cfg.layer_types))
    layers = []
    for i, (kind, lkey) in enumerate(zip(cfg.layer_types, lkeys)):
        kop, kff = jax.random.split(lkey)
        layer = {"op_norm": rmsnorm_init(cfg.hidden),
                 "ff_norm": rmsnorm_init(cfg.hidden)}
        if kind == "conv":
            layer["conv"] = short_conv_init(kop, cfg.hidden, cfg.conv_kernel)
        else:
            layer["attn"] = gqa_init(kop, cfg.hidden, cfg.heads,
                                     cfg.kv_heads)
        if i < cfg.num_dense_layers:
            layer["mlp"] = gated_mlp_init(kff, cfg.hidden, cfg.dense_width)
        else:
            layer["moe"] = topk_moe_init(kff, cfg.hidden, cfg.expert_width,
                                         cfg.experts, cfg.held)
        layers.append(layer)
    return {"embed": jax.random.normal(ekey, (cfg.vocab, cfg.hidden)) * 0.02,
            "layers": layers, "norm_f": rmsnorm_init(cfg.hidden)}


def _layer(layer: dict, x: jax.Array, *, cfg: Config, attn_fn, moe_scopes):
    dtype = jnp.dtype(cfg.dtype)
    u = rmsnorm_apply(layer["op_norm"], x, cfg.norm_eps)
    if "conv" in layer:
        op = short_conv_apply(layer["conv"], u, dtype=dtype)
    else:
        op = gqa_apply(layer["attn"], u, cfg.heads, attn_fn=attn_fn,
                       dtype=dtype, rope_base=cfg.rope_theta,
                       eps=cfg.norm_eps)
    x = x + op.astype(dtype)
    u = rmsnorm_apply(layer["ff_norm"], x, cfg.norm_eps)
    if "mlp" in layer:
        ff = gated_mlp_apply(layer["mlp"], u, dtype=dtype)
    else:
        ff = topk_moe_apply(layer["moe"], u, cfg.experts_per_token,
                            cfg.first_expert, cfg.routed_scaling,
                            dtype=dtype, scopes=moe_scopes)
    return x + ff.astype(dtype)


def apply(params: dict, tokens: jax.Array, cfg: Config = TINY, attn_fn=None,
          moe_scopes: tuple[str, str] = ("moe_route", "moe_experts"),
          remat: bool = False) -> jax.Array:
    """``tokens``: (batch, seq) int32 -> logits (batch, seq, cfg.vocab)
    fp32, on the embedding's transpose. ``attn_fn(q, k, v)`` overrides the
    dense causal attention (the flash kernel takes the grouped k/v as they
    are); ``remat`` rematerialises each layer in the backward pass."""
    layer = partial(_layer, cfg=cfg, attn_fn=attn_fn, moe_scopes=moe_scopes)
    if remat:
        layer = jax.checkpoint(layer)
    dtype = jnp.dtype(cfg.dtype)
    x = params["embed"][tokens].astype(dtype)
    for lp in params["layers"]:
        x = layer(lp, x)
    x = rmsnorm_apply(params["norm_f"], x, cfg.norm_eps)
    # (a float32 result here makes the cotangent float32 and the two
    # matmuls back run in float32 passes: 24 ms of a 176 ms step on a v5e)
    return (x @ params["embed"].astype(dtype).T).astype(jnp.float32)


def loss_fn(params: dict, batch, cfg: Config = TINY, **apply_kwargs
            ) -> jax.Array:
    tokens, targets = batch
    return softmax_cross_entropy(apply(params, tokens, cfg, **apply_kwargs),
                                 targets)


batch_fn = partial(synthetic_token_batch, batch_size=BATCH_SIZE,
                   seq_len=SEQ_LEN, vocab=TINY.vocab)


if __name__ == "__main__":
    main_cli("lfm2", init, loss_fn, batch_fn)
