"""The binding of the LFM2 (``lfm2_moe``) configurations to the program's
own model: ``kubeshare_tpu.models.lfm2`` driven by one configuration
object built here from the configuration's file, with the program's flash
attention under the scope ``bench_attn``, the expert layer's grouped
products under ``bench_moe`` and its routing (scores, top-k, sort, gather,
combine) under ``bench_moe_route``, every layer rematerialised. Named by a
configuration's ``binding``; the only file of the benchmark that reaches
the program's LFM2 model (through the zoo's ``get_model``).

A binding offers three factories of pure functions and no more (see
``models/gpt2.py``): ``init(cfg) -> f(key)``, ``loss(cfg) -> f(params,
(tokens, targets))``, ``logits(cfg) -> f(params, tokens)``. It sets
nothing on the program's modules: two configurations in one process do
not see each other.

Import this only inside a tenant process (it imports jax).
"""

from __future__ import annotations

from functools import partial

import jax

from kubeshare_tpu import models as zoo
from kubeshare_tpu.ops.flash_attention import flash_attention

M = zoo.get_model("lfm2")
MOE_SCOPES = ("bench_moe_route", "bench_moe")


def _config(cfg: dict):
    """The program's configuration object from the file's own key names:
    what is held here (``num_experts``, ``vocab_size``) beside what is
    published (``published``) and where the share starts
    (``deployment``)."""
    return M.Config(
        hidden=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        dense_width=int(cfg["intermediate_size"]),
        expert_width=int(cfg["moe_intermediate_size"]),
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=int(cfg["num_dense_layers"]),
        experts=int(cfg["published"]["num_experts"]),
        experts_held=int(cfg["num_experts"]),
        first_expert=int(cfg["deployment"]["first_expert"]),
        experts_per_token=int(cfg["num_experts_per_tok"]),
        routed_scaling=float(cfg["routed_scaling_factor"]),
        vocab=int(cfg["vocab_size"]),
        vocab_published=int(cfg["published"]["vocab_size"]),
        conv_kernel=int(cfg["conv_L_cache"]),
        norm_eps=float(cfg["norm_eps"]),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]))


def bench_attn(q, k, v):
    """The program's flash kernels under a stable scope; the grouped k, v
    go in as they are (the kernel maps the group in its block index)."""
    with jax.named_scope("bench_attn"):
        return flash_attention(q, k, v, causal=True)


def init(cfg: dict):
    return partial(M.init, cfg=_config(cfg))


def loss(cfg: dict):
    return partial(M.loss_fn, cfg=_config(cfg), attn_fn=bench_attn,
                   moe_scopes=MOE_SCOPES, remat=True)


def logits(cfg: dict):
    return partial(M.apply, cfg=_config(cfg), attn_fn=bench_attn,
                   moe_scopes=MOE_SCOPES)
