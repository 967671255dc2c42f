"""Share of the outputs the tenants' programs produced inside the
counters' window that the chip proxy wrote into a buffer the same
``execute`` freed, instead of the runtime allocating them anew
(``out_recycled`` over ``out_count`` in ``usage``'s ``chip.sessions``,
both gained inside the window, over all tenants). A trainer whose step
frees last step's state on its next call reads ~100; a scorer's one-float
outputs are counted and never recycled. Says nothing on a program without
the counters."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "chip proxy", "%", "program_counter", "train_tokens_per_s"


def read(run: dict):
    gained = R.reader("idle_attach_pct").gained
    made, recycled = gained(run, "out_count"), gained(run, "out_recycled")
    if not made or recycled is None:
        return None
    return 100.0 * recycled / made
