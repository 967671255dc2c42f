"""What the MiniCPM-SALA (``minicpm_sala``) configurations' algorithms NEED,
from shapes alone: the numerators of ``score_mfu``, ``lin_attn_roofline``
and ``sparse_attn_roofline``, and the sizes the traffic is drawn over.
Named by a configuration's ``counts``. Plain Python, no jax: the readers
run in ``run.py``'s process. The counts read the same whatever implements
a kernel: work that a kernel does and the algorithm does not need (a
masked tile multiplied whole, the upper half of a chunk's square) is not
counted.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d, heads = int(cfg["hidden_size"]), int(cfg["num_attention_heads"])
    kinds, a = list(cfg["mixer_types"]), cfg["assumed"]
    return {"d": d, "heads": heads, "hd": int(cfg["head_dim"]),
            "kvd": int(cfg["num_key_value_heads"]) * int(cfg["head_dim"]),
            "width": int(cfg["intermediate_size"]),
            "sparse": kinds.count("minicpm4"),
            "linear": kinds.count("lightning-attn"),
            "vocab": int(cfg["vocab_size"]),
            "kernel": int(a["kernel_size"]), "stride": int(a["kernel_stride"]),
            "block": int(a["block_size"]), "topk": int(a["topk"]),
            "dense_len": int(a["dense_len"]),
            "chunk": int(a["scan_chunk"])}


def sizes(cfg: dict) -> dict:
    """The vocabulary AS RUN (the held slice: ids are drawn from it, the
    scores are taken over it) and the longest sequence a cell sends (the
    model declares 524,288 positions and holds no table of them)."""
    return {"vocab": int(cfg["vocab_size"]),
            "positions": int(cfg["deployment"]["positions_as_run"])}


def parameters(cfg: dict) -> int:
    """Every leaf the program holds: the matrices that multiply, the
    embedding, the norm gains and the decay rates."""
    c = _dims(cfg)
    d, hd = c["d"], c["hd"]
    gains = 2 * d + 2 * hd
    return int(multiplying_params(cfg) + c["vocab"] * d
               + c["sparse"] * gains + c["linear"] * (gains + hd + c["heads"])
               + d)


def multiplying_params(cfg: dict) -> float:
    """Parameters that multiply a token's activation: a ``minicpm4``
    layer's fused q/k/v, gate and output projections, a ``lightning-attn``
    layer's (three full-width q/k/v, gate, output), the gated MLP's three
    matrices in every layer, the untied head. The embedding is a lookup;
    norm gains and decay rates are elementwise."""
    c = _dims(cfg)
    d = c["d"]
    mlp = 3 * d * c["width"]
    return (c["sparse"] * (d * (d + 2 * c["kvd"]) + 2 * d * d + mlp)
            + c["linear"] * (5 * d * d + mlp) + d * c["vocab"])


def _linear_flops(c: dict, seq: int) -> float:
    """The chunked form's necessary products of ONE lightning layer over
    ``seq`` positions at the chunk the configuration states: inside a
    chunk the causal triangle of Q K^T and of P V (2 x 2 hd x (C + 1) / 2
    a position and head), across chunks the state's read (q S) and its
    update (k^T v), 2 hd^2 each; not the quadratic sum the reference
    takes."""
    chunk = min(c["chunk"], seq)
    per = 2.0 * c["hd"] * (chunk + 1) + 4.0 * c["hd"] * c["hd"]
    return seq * c["heads"] * per


def linear_attention_layer(cfg: dict, seq: int) -> dict:
    """What one lightning layer's scan needs over a sequence of ``seq``:
    flops as above; bytes that must cross HBM once: q, k, v in and o out,
    all at the two bytes the model holds its activations in (the layer
    consumes ``o`` through a norm and a gate into a bfloat16 matmul: a
    kernel that writes float32 moves bytes the algorithm does not need,
    and reads a lower share for it)."""
    c = _dims(cfg)
    return {"flops": _linear_flops(c, seq),
            "bytes": float(seq * c["d"] * (3 * 2 + 2))}


def linear_attention_layers(cfg: dict) -> int:
    return _dims(cfg)["linear"]


def _chosen_keys(c: dict, seq: int) -> float:
    """Sum over the queries of a sequence of the keys each attends to:
    every earlier key while fewer than ``topk`` blocks exist, else ``topk
    - 1`` whole blocks and its own block up to itself."""
    total = 0.0
    for first in range(0, seq, c["block"]):
        own = first // c["block"]
        rows = min(c["block"], seq - first)
        whole = min(own, c["topk"] - 1) * c["block"]
        total += rows * whole + rows * (rows + 1) / 2.0
    return total


def sparse_attention_layer(cfg: dict, seq: int):
    """What one ``minicpm4`` layer's attention over the chosen blocks needs
    for a sequence PAST ``dense_len`` (``None`` up to it: the dense path is
    another kernel's): Q K^T and P V over ``topk * block_size`` keys a
    query (fewer near the start), all query heads; bytes: q in and o out
    at the model's two bytes, k and v once, the choice's bits."""
    c = _dims(cfg)
    if seq <= c["dense_len"]:
        return None
    keys = _chosen_keys(c, seq)
    words = -(-(seq // c["block"]) // 32)
    kv_heads = c["kvd"] // c["hd"]
    return {"flops": 4.0 * c["heads"] * c["hd"] * keys,
            "bytes": float(seq * (c["d"] * (2 + 2) + 2 * c["kvd"] * 2
                                  + kv_heads * words * 4))}


def sparse_attention_layers(cfg: dict) -> int:
    return _dims(cfg)["sparse"]


def _select_flops(c: dict, seq: int) -> float:
    """The selection's score products: every query head against the
    compressed windows that have ended before the query."""
    windows = 0.0
    for t in range(c["kernel"] - 1, seq, c["stride"]):
        seen = (t - c["kernel"] + 1) // c["stride"] + 1
        windows += seen * min(c["stride"], seq - t)
    return 2.0 * c["heads"] * c["hd"] * windows


def score_flops(cfg: dict, length: int) -> float:
    """One scored sequence of ``length`` real tokens: 2 N a token (N the
    parameters that multiply), the lightning layers' scans, and the
    ``minicpm4`` layers' attention: causal and dense (two matmuls, halved
    by the mask) up to ``dense_len``, the selection's scores and the
    chosen keys past it."""
    c = _dims(cfg)
    flops = 2.0 * multiplying_params(cfg) * length
    flops += c["linear"] * _linear_flops(c, length)
    if length <= c["dense_len"]:
        flops += c["sparse"] * 2.0 * c["heads"] * c["hd"] * length * (
            length + 1)
    else:
        flops += c["sparse"] * (_select_flops(c, length) + 4.0 * c["heads"]
                                * c["hd"] * _chosen_keys(c, length))
    return flops
