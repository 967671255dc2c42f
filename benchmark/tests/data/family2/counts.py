"""Test data: a second family's counts, copied into a rehearsal tree as
``counts/<family>.py``. It offers the sizes and the whole step's
operations and NO count of one attention layer: ``attn_roofline`` then
says nothing for this family, ``step_mfu`` reads."""

from __future__ import annotations


def sizes(cfg: dict) -> dict:
    return {"vocab": int(cfg["vocab_size"]),
            "positions": int(cfg["max_position_embeddings"])}


def multiplying_params(cfg: dict) -> int:
    d = int(cfg["hidden_size"])
    return (int(cfg["num_hidden_layers"]) * 12 * d * d
            + d * int(cfg["vocab_size"]))


def train_flops(cfg: dict, rows: int, seq: int) -> float:
    attention = (int(cfg["num_hidden_layers"]) * 6 * rows * seq * seq
                 * int(cfg["hidden_size"]))
    return 6.0 * multiplying_params(cfg) * rows * seq + attention
