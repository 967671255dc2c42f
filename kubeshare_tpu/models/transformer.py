"""Decoder-only transformer LM workload — the long-context model family.

The reference's eval zoo stops at convnets + LSTM (``test/mnist`` etc.);
long-context workloads are first-class in the TPU build, so the zoo grows
a GPT-style causal LM. The attention inner function is pluggable: dense
on one chip, ring attention over an ``sp`` mesh axis for sequence
parallelism (``parallel.ringattention`` — pass ``attn_fn``).

TPU-first notes: pre-norm residual blocks, all matmuls bfloat16 (MXU),
layernorm/softmax accumulate fp32, static shapes throughout.
"""

from __future__ import annotations

import os
from functools import partial

import jax
import jax.numpy as jnp

from ..ops import (dense_apply, dense_init, layernorm_apply, layernorm_init,
                   mha_apply, mha_init, softmax_cross_entropy)
from ..ops.attention import dot_product_attention
from .common import main_cli, synthetic_token_batch

BATCH_SIZE = 8
SEQ_LEN = 256
VOCAB = 4096
DIM = 256
HEADS = 8
LAYERS = 4
MLP_MULT = 4
DTYPE = jnp.bfloat16

if os.environ.get("KUBESHARE_TPU_TRANSFORMER_PRESET", "") == "small":
    # CI / smoke preset: the full config costs minutes of CPU XLA compile
    # per process in the multi-process gang tests. Same code paths,
    # divisibility (sp/tp/heads/dp) preserved.
    BATCH_SIZE, SEQ_LEN, VOCAB, DIM, HEADS, LAYERS = 4, 32, 64, 32, 4, 2

# Modern-LM attention knobs (env-configured like the preset; 0/off =
# the classic full-causal multi-head block):
#   KV_HEADS < HEADS  -> grouped-query / multi-query attention (smaller
#                        fused projection + kv cache; changes the
#                        checkpoint shape, so set it consistently)
#   ROPE              -> rotary positions on q/k (parameter-free)
#   WINDOW > 0        -> sliding-window (local) attention band
KV_HEADS = int(os.environ.get("KUBESHARE_TPU_TRANSFORMER_KV_HEADS", "0")) \
    or None
USE_ROPE = os.environ.get("KUBESHARE_TPU_TRANSFORMER_ROPE", "").lower() in \
    ("1", "true", "yes", "on")
WINDOW = int(os.environ.get("KUBESHARE_TPU_TRANSFORMER_WINDOW", "0")) \
    or None


def init(key, *, seq_len: int = SEQ_LEN, vocab: int = VOCAB, dim: int = DIM,
         layers: int = LAYERS, n_experts: int = 0) -> dict:
    """``n_experts > 0`` swaps every block's dense FFN for a top-1 routed
    mixture of experts (``ops.moe``) — the expert-parallel family; shard
    the expert stacks with :func:`kubeshare_tpu.ops.moe.expert_sharding`.
    """
    from ..ops.moe import moe_init

    ekey, pkey, okey, *bkeys = jax.random.split(key, 3 + layers)
    blocks = []
    for lkey in bkeys:
        k1, k2, k3 = jax.random.split(lkey, 3)
        block = {
            "ln1": layernorm_init(dim),
            "attn": mha_init(k1, dim, HEADS, kv_heads=KV_HEADS),
            "ln2": layernorm_init(dim),
        }
        if n_experts:
            block["moe"] = moe_init(k2, dim, MLP_MULT * dim, n_experts)
        else:
            block["fc"] = dense_init(k2, dim, MLP_MULT * dim)
            block["proj"] = dense_init(k3, MLP_MULT * dim, dim)
        blocks.append(block)
    return {
        "embed": jax.random.normal(ekey, (vocab, dim)) * 0.02,
        "pos": jax.random.normal(pkey, (seq_len, dim)) * 0.02,
        "blocks": blocks,
        "ln_f": layernorm_init(dim),
        "out": dense_init(okey, dim, vocab),
    }


def apply(params: dict, tokens: jax.Array, attn_fn=None,
          return_aux: bool = False):
    """``tokens``: (batch, seq) int32 → logits (batch, seq, vocab) fp32
    (with ``return_aux``: ``(logits, moe_aux_loss)``).

    ``attn_fn(q, k, v)`` overrides the dense causal attention — the
    sequence-parallel path passes a ring-attention closure built on the
    gang's mesh. The rest of the block is pointwise over the sequence, so
    a ``P(dp, sp)`` token sharding flows through untouched; attention is
    the only cross-sequence communication.
    """
    from ..ops.moe import moe_apply

    seq = tokens.shape[1]
    x = params["embed"][tokens]
    if not USE_ROPE:
        # learned absolute positions (and their seq_len cap); RoPE
        # REPLACES them — rotating q/k while also adding this table
        # would forfeit the relative-position property RoPE exists for
        # (the table still lives in the checkpoint for shape stability)
        x = x + params["pos"][:seq]
    x = x.astype(DTYPE)
    if attn_fn is None and WINDOW is not None:
        # the band lives in the LOCAL attention body; the sp strategies
        # own their masking (only the ulysses pair supports a band —
        # see _loss_for_mesh)
        attn_fn = partial(dot_product_attention, causal=True,
                          window=WINDOW)
    aux_total = jnp.zeros((), jnp.float32)
    for blk in params["blocks"]:
        x = x + mha_apply(blk["attn"], layernorm_apply(blk["ln1"], x),
                          HEADS, causal=True, attn_fn=attn_fn,
                          use_rope=USE_ROPE,
                          dtype=DTYPE).astype(DTYPE)
        hin = layernorm_apply(blk["ln2"], x)
        if "moe" in blk:
            ffn, aux = moe_apply(blk["moe"], hin, dtype=DTYPE)
            aux_total = aux_total + aux
        else:
            h = jax.nn.gelu(dense_apply(blk["fc"], hin, dtype=DTYPE))
            ffn = dense_apply(blk["proj"], h, dtype=DTYPE)
        x = x + ffn
    x = layernorm_apply(params["ln_f"], x)
    logits = dense_apply(params["out"], x, dtype=DTYPE).astype(jnp.float32)
    return (logits, aux_total) if return_aux else logits


AUX_COEF = 0.01  # Switch load-balance coefficient


def loss_fn(params: dict, batch, attn_fn=None) -> jax.Array:
    tokens, targets = batch
    logits, aux = apply(params, tokens, attn_fn=attn_fn, return_aux=True)
    return softmax_cross_entropy(logits, targets) + AUX_COEF * aux


batch_fn = partial(synthetic_token_batch, batch_size=BATCH_SIZE,
                   seq_len=SEQ_LEN, vocab=VOCAB)


def _loss_for_mesh(mesh):
    """Sequence-parallel loss when the gang's mesh carries an ``sp``
    axis (e.g. ``KUBESHARE_TPU_MESH="dp=2,sp=2,tp=2"``), dense
    otherwise (None = keep the default). Strategy is selectable via
    ``KUBESHARE_TPU_SP_ATTN``:

    - ``ring`` (default) — any head count, O((seq/sp)²) score memory;
    - ``ring_flash`` — ring with the Pallas flash tile per step: one
      (block_q × block_k) score tile alive regardless of shard length,
      the tile following the shard's shape up to the kernel's target
      (``ops.flash_attention._blocks``; the long-context default on the
      chip);
    - ``ulysses`` — all-to-all head/sequence exchange, two collectives
      total, needs heads divisible by sp;
    - ``ulysses_flash`` — ulysses with the flash kernel as the local
      attention body.
    """
    if "sp" not in mesh.axis_names:
        return None
    kind = os.environ.get("KUBESHARE_TPU_SP_ATTN", "ring").lower()
    if kind not in ("ring", "ring_flash", "ulysses", "ulysses_flash"):
        # a typo must not silently wire in plain ring: on a long-context
        # gang that's an O((seq/sp)²) tile and an OOM with no clue why
        raise ValueError(
            f"KUBESHARE_TPU_SP_ATTN={kind!r}: want ring | ring_flash | "
            "ulysses | ulysses_flash")
    if WINDOW is not None and kind in ("ring", "ring_flash"):
        # the ring's per-step blocks have shifted origins, so the band
        # cannot ride it; ulysses sees the full sequence per device
        raise ValueError(
            f"KUBESHARE_TPU_TRANSFORMER_WINDOW={WINDOW} needs an "
            "ulysses strategy (KUBESHARE_TPU_SP_ATTN=ulysses[_flash], "
            "which in turn needs heads AND kv_heads divisible by sp); "
            f"the {kind} path is full-causal — windowed attention with "
            "kv_heads not divisible by sp is unsupported under "
            "sequence parallelism")
    if kind in ("ulysses", "ulysses_flash"):
        from ..parallel.ulysses import make_ulysses_attention
        if kind == "ulysses_flash":
            from ..ops.flash_attention import flash_attention
            attn = make_ulysses_attention(
                mesh, causal=False,
                attn_fn=partial(flash_attention, causal=True,
                                window=WINDOW))
        elif WINDOW is not None:
            from ..ops.attention import dot_product_attention
            attn = make_ulysses_attention(
                mesh, causal=False,
                attn_fn=partial(dot_product_attention, causal=True,
                                window=WINDOW))
        else:
            attn = make_ulysses_attention(mesh)
    elif kind == "ring_flash":
        from ..parallel.ringattention import make_ring_flash_attention
        attn = make_ring_flash_attention(mesh)
    else:
        from ..parallel.ringattention import make_ring_attention
        attn = make_ring_attention(mesh)
    return partial(loss_fn, attn_fn=attn)


def _token_sharding_hook(mesh):
    from ..parallel.mesh import token_sharding
    return token_sharding(mesh)


MESH_HOOKS = {"loss": _loss_for_mesh,
              "batch_sharding": _token_sharding_hook}


if __name__ == "__main__":
    main_cli("transformer", init, loss_fn, batch_fn,
             mesh_hooks=MESH_HOOKS)
