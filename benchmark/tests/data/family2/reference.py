"""Test data: a second family's plain reference, copied into a rehearsal
tree as ``reference/<family>.py``: GPT-2's reference under the other
family's key names (what ``checks/<role>.py`` call, and no more)."""

from __future__ import annotations

import importlib.util
from pathlib import Path

_spec = importlib.util.spec_from_file_location(
    "family2_gpt2_reference", Path(__file__).resolve().parent / "gpt2.py")
_gpt2 = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(_gpt2)


def _as_gpt2(cfg: dict) -> dict:
    return {"n_embd": cfg["hidden_size"], "n_layer": cfg["num_hidden_layers"],
            "n_head": cfg["num_attention_heads"],
            "n_positions": cfg["max_position_embeddings"],
            "vocab_size": cfg["vocab_size"]}


def init(key_words, cfg: dict) -> dict:
    return _gpt2.init(key_words, _as_gpt2(cfg))


def train_readings(key_words, cfg, batches, lr, **kw):
    return _gpt2.train_readings(key_words, _as_gpt2(cfg), batches, lr, **kw)


def score(p, tokens, length, cfg, quant=None) -> float:
    return _gpt2.score(p, tokens, length, _as_gpt2(cfg), quant)
