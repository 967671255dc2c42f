"""What the LFM2 (``lfm2_moe``) configurations' algorithms NEED, from
shapes alone: the numerators of ``step_mfu`` and ``attn_roofline``, and
the sizes the traffic is drawn over. Named by a configuration's
``counts``. Plain Python, no jax: the readers run in ``run.py``'s process. Recomputed work (every layer is rematerialised; the
flash backward looks at the scores again) does not count.

The configuration holds a SHARE of each expert layer (``num_experts`` of
``published.num_experts``): what is counted is this chip's work.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kinds = list(cfg["layer_types"])
    dense = int(cfg["num_dense_layers"])
    return {"d": d, "heads": heads, "hd": d // heads,
            "kvd": int(cfg["num_key_value_heads"]) * (d // heads),
            "dense_width": int(cfg["intermediate_size"]),
            "conv": kinds.count("conv"),
            "attention": kinds.count("full_attention"),
            "dense": dense, "expert_layers": len(kinds) - dense,
            "experts": int(cfg["published"]["num_experts"]),
            "vocab": int(cfg["vocab_size"])}


def sizes(cfg: dict) -> dict:
    """The vocabulary AS RUN (the held slice: ids are drawn from it, the
    loss is taken over it) and the longest sequence."""
    return {"vocab": int(cfg["vocab_size"]),
            "positions": int(cfg["max_position_embeddings"])}


def multiplying_params(cfg: dict) -> float:
    """Parameters that SURELY multiply a token's activation: a convolution
    layer's two projections (4 d^2), the attention layer's (fused q/k/v
    and output), the dense gated MLP's three matrices, the router; the
    tied embedding as the output head. Norm gains, the three taps and the
    selection bias are elementwise. The held experts' matrices are LEFT
    OUT: how many (token, expert) pairs a step sends them is the run's
    own (an even router sends ``top_k x held / experts`` = half a pair a
    token, 18.9 M parameters more; at the cell's lr the router sends none
    from about the 20th step on, ``PERF.md`` section 5) and no counter of
    a run reports it, so what is counted is a floor."""
    c = _dims(cfg)
    d = c["d"]
    return (c["conv"] * 4 * d * d
            + c["attention"] * (d * (d + 2 * c["kvd"]) + d * d)
            + c["dense"] * 3 * d * c["dense_width"]
            + c["expert_layers"] * d * c["experts"]
            + d * c["vocab"])


def attention_matmul_flops(cfg: dict, rows: int, seq: int) -> float:
    """One causal attention matmul over ``rows`` sequences of ``seq``, all
    query heads of ONE layer, halved by the causal mask."""
    c = _dims(cfg)
    return rows * 2.0 * seq * seq * c["heads"] * c["hd"] / 2.0


def train_flops(cfg: dict, rows: int, seq: int) -> float:
    """One training step: 6 N per token, plus attention forward (2
    matmuls) and backward (4) in every attention layer; the grouped
    products over the held experts are not in N (``multiplying_params``
    says why)."""
    c = _dims(cfg)
    return (6.0 * multiplying_params(cfg) * rows * seq
            + c["attention"] * 6 * attention_matmul_flops(cfg, rows, seq))


def attention_layers(cfg: dict) -> int:
    """Layers whose attention runs under the binding's ``bench_attn``."""
    return _dims(cfg)["attention"]


def attention_layer(cfg: dict, rows: int, seq: int, backward: bool) -> dict:
    """What one layer's attention needs: flops, and the bytes that must
    cross HBM once as the program's flash kernels declare their arrays:
    bfloat16 q (``heads x 64`` wide) and k, v (``kv_heads x 64`` wide: the
    group shares them) in, float32 o out; backward reads q, k, v, o and
    the float32 do and writes dq, dk, dv in the primal dtype."""
    c = _dims(cfg)
    q, kv = rows * seq * c["d"], rows * seq * c["kvd"]
    flops = 2 * attention_matmul_flops(cfg, rows, seq)
    nbytes = (q + 2 * kv) * 2 + q * 4
    if backward:
        flops += 4 * attention_matmul_flops(cfg, rows, seq)
        nbytes += (q + 2 * kv) * 2 + 2 * q * 4 + (q + 2 * kv) * 2
    return {"flops": flops, "bytes": float(nbytes)}
