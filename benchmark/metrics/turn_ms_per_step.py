"""The trainer's wall-clock turn-around per training step: from an
execute's reply coming in to the tenant's next execute send, measured in
the tenant's process (``turn_ms_total``): the shim, the tenant's own code
between two calls and its other round trips. ``shim_ms_per_step`` is the
CPU-time part of it that lies in shim and client code. Mean over the
trainers."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "attach", "ms", "program_counter", "train_tokens_per_s"


def read(run: dict):
    gained = R.reader("idle_attach_pct").gained
    vals = []
    for t in R.by_role(run, "train"):
        turn_ms, steps = gained(run, "turn_ms_total", [t]), R.counted_steps(
            run, t)
        if turn_ms is None:
            return None
        if steps > 0:
            vals.append(turn_ms / steps)
    return sum(vals) / len(vals) if vals else None
