"""The binding of the MiniCPM-SALA (``minicpm_sala``) configurations to the
program's own model: ``kubeshare_tpu.models.minicpm_sala`` driven by one
configuration object built here from the configuration's file. The
lightning layers' chunked scan runs under the scope ``bench_lin_attn``,
the sparse layers' block selection (pooling, scores, max-pool, top-k)
under ``bench_sparse_select``, their attention over the chosen blocks
under ``bench_sparse_attn`` and their dense path (a sequence of at most
``dense_len`` tokens: the program's flash kernel) under ``bench_attn``.
Named by a configuration's ``binding``; the only file of the benchmark
that reaches the program's MiniCPM-SALA model (through the zoo's
``get_model``).

A binding offers factories of pure functions and no more (see
``models/gpt2.py``): ``init(cfg) -> f(key)`` and ``logits(cfg) ->
f(params, tokens)``. This family is served forward only (the model has no
backward: ``PERF.md`` section 4 says why a trainer of it does not fit), so
``loss`` names that and raises. It sets nothing on the program's modules.

Import this only inside a tenant process (it imports jax).
"""

from __future__ import annotations

from functools import partial

import jax

from kubeshare_tpu import models as zoo
from kubeshare_tpu.ops.flash_attention import flash_attention

M = zoo.get_model("minicpm_sala")

SCOPES = ("bench_lin_attn", "bench_sparse_select", "bench_sparse_attn")


def _config(cfg: dict):
    """The program's configuration object from the file's own key names:
    what is held here (``vocab_size``, ``mixer_types``) beside what is
    published (``published``) and what the catalog row leaves to the
    family's convention (``assumed``)."""
    a = cfg["assumed"]
    if (int(cfg["lightning_nh"]) != int(cfg["num_attention_heads"])
            or int(cfg["lightning_head_dim"]) != int(cfg["head_dim"])
            or int(cfg["head_dim"]) * int(cfg["num_attention_heads"])
            != int(cfg["hidden_size"])):
        raise SystemExit("the program's layers take heads x head_dim = "
                         "hidden_size, alike in both kinds of mixer")
    return M.Config(
        hidden=int(cfg["hidden_size"]),
        heads=int(cfg["num_attention_heads"]),
        kv_heads=int(cfg["num_key_value_heads"]),
        mlp_width=int(cfg["intermediate_size"]),
        mixer_types=tuple(cfg["mixer_types"]),
        layers_published=int(cfg["published"]["num_hidden_layers"]),
        vocab=int(cfg["vocab_size"]),
        scale_emb=float(cfg["scale_emb"]),
        scale_depth=float(cfg["scale_depth"]),
        dim_model_base=int(cfg["dim_model_base"]),
        norm_eps=float(cfg["rms_norm_eps"]),
        rope_theta=float(cfg["rope_theta"]),
        decay_exponent=float(a["decay_exponent"]),
        chunk=int(a["scan_chunk"]),
        kernel_size=int(a["kernel_size"]),
        kernel_stride=int(a["kernel_stride"]),
        block_size=int(a["block_size"]),
        init_blocks=int(a["init_blocks"]),
        window_size=int(a["window_size"]),
        topk=int(a["topk"]),
        dense_len=int(a["dense_len"]),
        dtype=cfg["precision"]["params"])


def bench_attn(q, k, v):
    """The program's flash kernel under a stable scope; the grouped k, v
    go in as they are (16 query heads a kv head, 128 lanes a head)."""
    with jax.named_scope("bench_attn"):
        return flash_attention(q, k, v, causal=True)


def init(cfg: dict):
    return partial(M.init, cfg=_config(cfg))


def loss(cfg: dict):
    raise SystemExit("the minicpm_sala family is served forward only: no "
                     "cell of it has a trainer (PERF.md section 4)")


def logits(cfg: dict):
    return partial(M.apply, cfg=_config(cfg), attn_fn=bench_attn,
                   scopes=SCOPES)
