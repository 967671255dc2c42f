"""Operations and bytes the algorithms NEED, from shapes alone: the
numerators of every roofline share and of ``step_mfu``. Kept with the
benchmark so that no PR that claims a gain can change them. Recomputed
work (the flash backward's second look at the scores) does not count.
"""

from __future__ import annotations


def dims(cfg: dict) -> dict:
    d, heads = int(cfg["n_embd"]), int(cfg["n_head"])
    return {"d": d, "heads": heads, "hd": d // heads,
            "layers": int(cfg["n_layer"]), "vocab": int(cfg["vocab_size"])}


def multiplying_params(cfg: dict) -> int:
    """Parameters that multiply an activation: the four block matrices
    (12 d^2 a layer) and the output head. Embedding rows are looked up,
    biases and norms are added."""
    c = dims(cfg)
    return c["layers"] * 12 * c["d"] ** 2 + c["d"] * c["vocab"]


def attention_matmul_flops(cfg: dict, rows: int, seq: int) -> float:
    """One causal attention matmul (QK^T, or PV, or one of the backward's
    four) over ``rows`` sequences of ``seq``, all heads of ONE layer:
    2·seq²·d multiply-adds' worth, halved by the causal mask."""
    c = dims(cfg)
    return rows * 2.0 * seq * seq * c["d"] / 2.0


def train_flops(cfg: dict, rows: int, seq: int) -> float:
    """One training step: 6 N per token, plus attention forward (2
    matmuls) and backward (4) in every layer."""
    c = dims(cfg)
    return (6.0 * multiplying_params(cfg) * rows * seq
            + c["layers"] * 6 * attention_matmul_flops(cfg, rows, seq))


def score_flops(cfg: dict, length: int) -> float:
    """One scored sequence of ``length`` real tokens: 2 N per token plus
    the attention forward."""
    c = dims(cfg)
    return (2.0 * multiplying_params(cfg) * length
            + c["layers"] * 2 * attention_matmul_flops(cfg, 1, length))


def attention_layer(cfg: dict, rows: int, seq: int, backward: bool) -> dict:
    """What one layer's attention needs over ``rows`` x ``seq`` tokens:
    flops, and the bytes that must cross HBM once (bf16 q, k, v in and
    float32 o out forward; backward reads q, k, v, o, do and writes dq,
    dk, dv in float32 as the program's kernels declare them)."""
    c = dims(cfg)
    elems = rows * seq * c["d"]
    flops = 2 * attention_matmul_flops(cfg, rows, seq)
    nbytes = 3 * elems * 2 + elems * 4
    if backward:
        flops += 4 * attention_matmul_flops(cfg, rows, seq)
        nbytes += 3 * elems * 2 + 2 * elems * 4 + 3 * elems * 4
    return {"flops": flops, "bytes": float(nbytes)}


def adam(params: int) -> dict:
    """One Adam step over ``params`` float32 parameters: four arrays read
    (p, g, m, v), three written; about a dozen operations a parameter."""
    return {"flops": 12.0 * params, "bytes": 28.0 * params}


def least_seconds(need: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two binds."""
    by_flops = need["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes,
                                                               "memory")
