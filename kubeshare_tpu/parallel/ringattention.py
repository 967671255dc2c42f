"""Ring attention: sequence-parallel exact attention over an ``sp`` mesh axis.

Long-context sequence parallelism is a first-class capability of the TPU
build (the reference delegates all model math to its workload images,
``test/distribute/**``). Each device holds one contiguous block of the
sequence; key/value blocks rotate around the ring with ``lax.ppermute``
(one ICI hop per step) while queries stay put, and the partial softmax is
combined with the online (flash-attention style) running max / running sum
update — so attention over the FULL sequence is exact, but no device ever
materializes more than a (block × block) score tile, and the k/v transfer
for step i+1 overlaps the compute for step i under XLA's async collectives.

Memory per device: O(seq/sp · seq/sp) scores instead of O(seq²) — the
point of the exercise for long contexts.

Layout convention matches :mod:`kubeshare_tpu.ops.attention`:
q/k/v are (batch, seq_shard, heads, head_dim) inside the shard; the global
arrays are (batch, seq, heads, head_dim) sharded P(dp, sp, tp, None).
"""

from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, PartitionSpec as P

from ..ops.attention import MASK_VALUE, expand_kv, kv_groups


def ring_attention_shard(q: jax.Array, k: jax.Array, v: jax.Array,
                         axis_name: str, causal: bool = True,
                         scale: float | None = None) -> jax.Array:
    """Per-shard ring attention body. MUST run inside ``shard_map`` (or
    another SPMD context) where ``axis_name`` maps the sequence axis.

    ``q``/``k``/``v``: (batch, block, heads, head_dim) — this device's
    sequence block. Returns the attention output for the local queries
    against the FULL (global) sequence, (batch, block, heads, head_dim),
    fp32.
    """
    sp = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    b, nq, h, d = q.shape
    if h != k.shape[2]:
        kv_groups(h, k.shape[2])  # validate at trace time, expand per step
    scale = (1.0 / math.sqrt(d)) if scale is None else scale
    qf = q.astype(jnp.float32)

    # Ring: each step, ship our current k/v block one hop forward so after
    # i steps this device holds block (me - i) mod sp. Every link carries
    # one block per step — bandwidth-balanced on a torus ICI.
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def step(i, carry):
        o, m, l, kblk, vblk = carry
        src = jnp.mod(me - i, sp)          # which global block we hold now
        # grouped-query kv expands ONLY at the local einsum — the carry
        # that rides the ring (ppermute below) stays kv-sized, so GQA's
        # ICI-bandwidth saving survives the rotation
        kb, vb = expand_kv(kblk, vblk, h)
        scores = jnp.einsum("bqhd,bkhd->bqhk", qf,
                            kb.astype(jnp.float32)) * scale
        if causal:
            qidx = me * nq + jnp.arange(nq)
            kidx = src * nq + jnp.arange(nq)
            mask = qidx[:, None] >= kidx[None, :]
            scores = jnp.where(mask[None, :, None, :], scores, MASK_VALUE)
        # Online softmax combine. Fully-masked rows keep m at the floor;
        # the explicit where() guards turn their exp(0)=1 into 0.
        m_new = jnp.maximum(m, scores.max(axis=-1))
        alpha = jnp.where(m > MASK_VALUE * 0.5,
                          jnp.exp(m - m_new), 0.0)
        p = jnp.where(scores > MASK_VALUE * 0.5,
                      jnp.exp(scores - m_new[..., None]), 0.0)
        l_new = l * alpha + p.sum(axis=-1)
        o_new = (o * alpha[..., None]
                 + jnp.einsum("bqhk,bkhd->bqhd", p,
                              vb.astype(jnp.float32)))
        kblk, vblk = lax.ppermute((kblk, vblk), axis_name, perm)
        return o_new, m_new, l_new, kblk, vblk

    # Derive the accumulators from qf so they carry the same
    # varying-manual-axes type as the loop outputs (jax ≥0.8 shard_map
    # rejects an unvarying init zipped with varying outputs).
    o = qf * 0.0
    m = qf.max(axis=-1) * 0.0 + MASK_VALUE
    l = qf.sum(axis=-1) * 0.0
    # sp is static at trace time → static trip count.
    o, m, l, _, _ = lax.fori_loop(0, sp, step, (o, m, l, k, v),
                                  unroll=True)
    return o / jnp.where(l > 0.0, l, 1.0)[..., None]


def ring_flash_attention_shard(q: jax.Array, k: jax.Array, v: jax.Array,
                               axis_name: str, causal: bool = True,
                               block_q: int | None = None,
                               block_k: int | None = None,
                               interpret: bool | None = None) -> jax.Array:
    """Ring attention whose per-step tile is the Pallas flash kernel.

    :func:`ring_attention_shard` bounds memory at O(block²) where block
    = seq/sp — still quadratic IN THE SHARD, which at long context is
    the limit (128k over sp=8 → a 16k×16k fp32 score tile per head).
    Here each ring step instead calls
    :func:`~kubeshare_tpu.ops.flash_attention.flash_attention_lse`, so
    the largest live score tile is (block_q × block_k) VMEM-resident
    REGARDLESS of shard length (``None`` = the kernel's own tile rule,
    from the shard's shape); partial outputs merge exactly via the
    returned logsumexp. Two-level flash: the ring blocks the sequence
    over chips (ICI), the kernel blocks the shard over VMEM.

    The causal structure is hoisted OUT of the kernel: ring step i sees
    global k-block (me − i) mod sp, which is entirely past (full
    attention), the diagonal (causal attention), or entirely future
    (skipped) — a 3-way ``lax.switch``, so the kernel never needs
    dynamic position offsets.
    """
    from ..ops.flash_attention import flash_attention_lse

    sp = lax.axis_size(axis_name)
    me = lax.axis_index(axis_name)
    perm = [(j, (j + 1) % sp) for j in range(sp)]

    def tile_full(kblk, vblk):
        return flash_attention_lse(q, kblk, vblk, causal=False,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)

    def tile_diag(kblk, vblk):
        return flash_attention_lse(q, kblk, vblk, causal=True,
                                   block_q=block_q, block_k=block_k,
                                   interpret=interpret)

    def tile_masked(kblk, vblk):
        # derived from q AND kblk/vblk so all switch branches carry the
        # same varying-manual-axes type (plain constants have none)
        zero = (kblk[0, 0, 0, 0].astype(jnp.float32) * 0.0
                + vblk[0, 0, 0, 0].astype(jnp.float32) * 0.0)
        return (q.astype(jnp.float32) * 0.0 + zero,
                q.max(axis=-1).astype(jnp.float32) * 0.0 + zero
                + MASK_VALUE)

    def step(i, carry):
        o, lse, kblk, vblk = carry
        src = jnp.mod(me - i, sp)          # which global block we hold now
        if causal:
            branch = jnp.where(src < me, 0, jnp.where(src == me, 1, 2))
            o_i, lse_i = lax.switch(branch, (tile_full, tile_diag,
                                             tile_masked), kblk, vblk)
        else:
            o_i, lse_i = tile_full(kblk, vblk)
        # exact merge of two normalized partials over disjoint key sets
        lse_new = jnp.logaddexp(lse, lse_i)
        wa = jnp.where(lse > MASK_VALUE * 0.5, jnp.exp(lse - lse_new), 0.0)
        wb = jnp.where(lse_i > MASK_VALUE * 0.5,
                       jnp.exp(lse_i - lse_new), 0.0)
        o_new = o * wa[..., None] + o_i * wb[..., None]
        kblk, vblk = lax.ppermute((kblk, vblk), axis_name, perm)
        return o_new, lse_new, kblk, vblk

    # accumulators derived from q: same varying-manual-axes type as the
    # loop outputs (see ring_attention_shard)
    o0 = q.astype(jnp.float32) * 0.0
    lse0 = q.max(axis=-1).astype(jnp.float32) * 0.0 + MASK_VALUE
    o, _, _, _ = lax.fori_loop(0, sp, step, (o0, lse0, k, v), unroll=True)
    return o


def _seq_shard_spec(mesh: Mesh, axis_name: str) -> P:
    """The sequence-parallel layout both factories share: batch rides
    ``dp`` and heads ride ``tp`` when those axes exist (purely local —
    no collectives on them); sequence rides the ring axis."""
    names = set(mesh.axis_names)
    if axis_name not in names:
        raise ValueError(f"mesh {mesh.axis_names} has no {axis_name!r} axis")
    return P("dp" if "dp" in names else None, axis_name,
             "tp" if "tp" in names else None, None)


def make_ring_attention(mesh: Mesh, causal: bool = True,
                        axis_name: str = "sp"):
    """An ``attn_fn(q, k, v)`` over GLOBAL (batch, seq, heads, head_dim)
    arrays, sequence-sharded over ``axis_name`` via ``shard_map``
    (layout: :func:`_seq_shard_spec`). Plug the result into
    :func:`kubeshare_tpu.ops.attention.mha_apply`.
    """
    spec = _seq_shard_spec(mesh, axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec)
    def attn(q, k, v):
        return ring_attention_shard(q, k, v, axis_name, causal=causal)

    return attn


def make_ring_flash_attention(mesh: Mesh, causal: bool = True,
                              axis_name: str = "sp",
                              block_q: int | None = None,
                              block_k: int | None = None,
                              interpret: bool | None = None):
    """:func:`make_ring_attention` with the Pallas flash kernel as the
    per-step tile (see :func:`ring_flash_attention_shard`) — the
    long-context configuration: O(block_q × block_k) live scores at
    every level of the hierarchy."""
    spec = _seq_shard_spec(mesh, axis_name)

    @partial(jax.shard_map, mesh=mesh, in_specs=(spec, spec, spec),
             out_specs=spec)
    def attn(q, k, v):
        return ring_flash_attention_shard(q, k, v, axis_name, causal=causal,
                                          block_q=block_q, block_k=block_k,
                                          interpret=interpret)

    return attn
