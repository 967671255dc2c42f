"""Share of the counters' window between the proxy's host stamps (see
``idle_attach_pct``: runtime allocation inside ``ks.device`` excluded) in
which a session was asking for the chip and the token was elsewhere
(``idle_gate_ms_total``): the hand-over, the previous holder's turn-around
before its renew included."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "token gate", "%", "program_counter", "train_tokens_per_s"


def read(run: dict):
    return R.reader("idle_attach_pct").idle_pct(run, "idle_gate_ms_total")
