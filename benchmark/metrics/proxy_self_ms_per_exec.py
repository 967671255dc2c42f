"""The execute handler's self time per execution, over all tenants:
arrival to reply less the gate wait, the ``_dlock`` wait and the device
time, taken inside the one call on one clock (``self_ms_total``)."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "chip proxy", "ms", "program_counter", "train_tokens_per_s"


def read(run: dict):
    return R.reader("idle_attach_pct").per_exec(run, "self_ms_total")
