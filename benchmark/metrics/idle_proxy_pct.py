"""Share of the counters' window between the proxy's host stamps (see
``idle_attach_pct``: runtime allocation inside ``ks.device`` excluded)
after the token was granted and before the program was called
(``idle_proxy_ms_total``): the proxy's own handler work and the wait for
``_dlock``."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "chip proxy", "%", "program_counter", "train_tokens_per_s"


def read(run: dict):
    return R.reader("idle_attach_pct").idle_pct(run, "idle_proxy_ms_total")
