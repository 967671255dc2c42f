"""What the GPT-2 configurations' algorithms NEED, from shapes alone: the
numerators of ``step_mfu`` and ``attn_roofline``, and the sizes the
traffic is drawn over. Named by a configuration's ``counts``. Plain
Python, no jax: the readers run in ``run.py``'s process. Kept with the
benchmark so that no PR that claims a gain can change them. Recomputed
work (the flash backward's second look at the scores) does not count.

A family's counts offer ``sizes`` and whichever of ``train_flops``,
``score_flops``, ``multiplying_params``, ``attention_layer`` with
``attention_layers`` its layers have; a reader whose count is not offered
says nothing.
"""

from __future__ import annotations


def _dims(cfg: dict) -> dict:
    d, heads = int(cfg["n_embd"]), int(cfg["n_head"])
    return {"d": d, "heads": heads, "hd": d // heads,
            "layers": int(cfg["n_layer"]), "vocab": int(cfg["vocab_size"])}


def sizes(cfg: dict) -> dict:
    """The vocabulary AS RUN (ids are drawn from it, the loss and the
    scores are taken over it) and the longest sequence."""
    return {"vocab": int(cfg["vocab_size"]),
            "positions": int(cfg["n_positions"])}


def multiplying_params(cfg: dict) -> int:
    """Parameters that multiply an activation: the four block matrices
    (12 d^2 a layer) and the output head. Embedding rows are looked up,
    biases and norms are added."""
    c = _dims(cfg)
    return c["layers"] * 12 * c["d"] ** 2 + c["d"] * c["vocab"]


def attention_matmul_flops(cfg: dict, rows: int, seq: int) -> float:
    """One causal attention matmul (QK^T, or PV, or one of the backward's
    four) over ``rows`` sequences of ``seq``, all heads of ONE layer:
    2·seq²·d multiply-adds' worth, halved by the causal mask."""
    c = _dims(cfg)
    return rows * 2.0 * seq * seq * c["d"] / 2.0


def train_flops(cfg: dict, rows: int, seq: int) -> float:
    """One training step: 6 N per token, plus attention forward (2
    matmuls) and backward (4) in every layer."""
    c = _dims(cfg)
    return (6.0 * multiplying_params(cfg) * rows * seq
            + c["layers"] * 6 * attention_matmul_flops(cfg, rows, seq))


def score_flops(cfg: dict, length: int) -> float:
    """One scored sequence of ``length`` real tokens: 2 N per token plus
    the attention forward."""
    c = _dims(cfg)
    return (2.0 * multiplying_params(cfg) * length
            + c["layers"] * 2 * attention_matmul_flops(cfg, 1, length))


def attention_layers(cfg: dict) -> int:
    """Layers whose attention runs under the binding's ``bench_attn``."""
    return _dims(cfg)["layers"]


def attention_layer(cfg: dict, rows: int, seq: int, backward: bool) -> dict:
    """What one layer's attention needs over ``rows`` x ``seq`` tokens:
    flops, and the bytes that must cross HBM once, as the program's flash
    kernels declare their arrays (``tests/test_flops.py`` ties the count
    to them): bfloat16 q, k, v in and float32 o out forward; backward
    reads q, k, v, o and the float32 do and writes dq, dk, dv in the
    primal dtype. The row statistics (``lse``, D: 8 bytes a row and head,
    under 1% of this) are left out."""
    c = _dims(cfg)
    elems = rows * seq * c["d"]
    flops = 2 * attention_matmul_flops(cfg, rows, seq)
    nbytes = 3 * elems * 2 + elems * 4
    if backward:
        flops += 4 * attention_matmul_flops(cfg, rows, seq)
        nbytes += 3 * elems * 2 + 2 * elems * 4 + 3 * elems * 2
    return {"flops": flops, "bytes": float(nbytes)}
