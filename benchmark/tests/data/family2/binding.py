"""Test data: a second family's binding, copied into a rehearsal tree as
``models/<family>.py``. The same mathematics as GPT-2 under the key names
of another family of published configurations; it stands for a binding
that builds another architecture on the program's code."""

from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "family2_gpt2_binding", Path(__file__).resolve().parent / "gpt2.py")
_gpt2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_gpt2)


def _as_gpt2(cfg: dict) -> dict:
    return {"n_embd": cfg["hidden_size"], "n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_positions": cfg["max_position_embeddings"],
            "vocab_size": cfg["vocab_size"]}


def init(cfg: dict):
    return _gpt2.init(_as_gpt2(cfg))


def loss(cfg: dict):
    return _gpt2.loss(_as_gpt2(cfg))


def logits(cfg: dict):
    return _gpt2.logits(_as_gpt2(cfg))
