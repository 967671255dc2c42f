"""Requests a call of a remoted function costs, over all tenants: every
request the proxy handled for the tenants' sessions, whatever the op
(``rpc_count``), over their executions (``exec_count``), both gained
inside the counters' window. 1.0 where a call is one ``execute`` and its
reply; 5 to 6 where each host leaf is ``put`` and freed and the result is
read with a ``get``. Says nothing on a program without the counter."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "attach", "requests", "program_counter", "train_tokens_per_s"


def read(run: dict):
    return R.reader("idle_attach_pct").per_exec(run, "rpc_count")
