"""Largest difference, over tenants, between the share by the gate's books
(``exec_ms_total``) and the share by completed steps: what the gate charged
against what the tenant got. Only where every tenant trains."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "token gate", "%", "program_counter", "worst_share_kept_pct"


def read(run: dict):
    tenants = run["tenants"]
    if len(tenants) < 2 or any(t["role"] != "train" for t in tenants):
        return None
    charged = [R.session_delta(run, t["pod"], "exec_ms_total")
               for t in tenants]
    work = [R.counted_steps(run, t) for t in tenants]
    if sum(charged) <= 0 or sum(work) <= 0:
        return None
    return 100.0 * max(abs(c / sum(charged) - w / sum(work))
                       for c, w in zip(charged, work))
