"""The wire per execution, over all tenants: the client's execute round
trip less the proxy's handler time for the same call (``wire_ms_total``):
both sockets, the proxy's reader and writer threads and its dispatch
queue."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "attach", "ms", "program_counter", "train_tokens_per_s"


def read(run: dict):
    return R.reader("idle_attach_pct").per_exec(run, "wire_ms_total")
