"""The attach shim's own cost per training step: CPU time of the
trainer's calling thread inside shim and client code (``shim_ms_total``,
``time.thread_time`` in the tenant's process, so no wait for a reply, and
with it no neighbour's program behind ``_dlock``, is in it; where the
kernel counts CPU time in 10 ms ticks, a sum of ticks). Mean over the
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
        shim_ms, steps = gained(run, "shim_ms_total", [t]), R.counted_steps(
            run, t)
        if shim_ms is None:
            return None
        if steps > 0:
            vals.append(shim_ms / steps)
    return sum(vals) / len(vals) if vals else None
