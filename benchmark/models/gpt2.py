"""The binding of the GPT-2 configurations to the program's own model:
``kubeshare_tpu.models.transformer`` with the program's flash attention
under the scope ``bench_attn``. Named by a configuration's ``binding``;
the only file of the benchmark that imports the program's model.

A binding offers three factories of pure functions and no more; the roles
(``tenants/<role>.py``) jit them and own everything else:

    init(cfg)   -> f(key)                        the parameters from a
                                                  threefry key (uint32[2])
    loss(cfg)   -> f(params, (tokens, targets))  mean cross entropy
    logits(cfg) -> f(params, tokens)             (rows, seq, vocab) float32

``init``'s function is traced inside other programs too (a trainer draws
its init again inside the program that measures the parameters' change),
so it is handed over un-jitted.

Import this only inside a tenant process (it imports jax).
"""

from __future__ import annotations

from functools import partial

import jax

from kubeshare_tpu.models import transformer as T
from kubeshare_tpu.ops.flash_attention import flash_attention


def _dims(cfg: dict) -> dict:
    """Sizes from the configuration's file. The head count is a module
    constant of the program (PERF.md section 7): set before any trace."""
    T.HEADS = int(cfg["n_head"])
    return {"seq_len": int(cfg["n_positions"]),
            "vocab": int(cfg["vocab_size"]),
            "dim": int(cfg["n_embd"]), "layers": int(cfg["n_layer"])}


def bench_attn(q, k, v):
    """The program's flash kernels under a stable scope, so the trace
    reduction finds attention whatever later implements it."""
    with jax.named_scope("bench_attn"):
        return flash_attention(q, k, v, causal=True)


def init(cfg: dict):
    return partial(T.init, **_dims(cfg))


def loss(cfg: dict):
    _dims(cfg)
    return partial(T.loss_fn, attn_fn=bench_attn)


def logits(cfg: dict):
    _dims(cfg)
    return partial(T.apply, attn_fn=bench_attn)
