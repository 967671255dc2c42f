"""The least, over tenants, of (share of the work done in the window) /
(share entitled = request / sum of requests), times 100. Every tenant must
run the one training program, so the share of work is the share of
completed steps: the tenants' own counts, not the gate's books."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, BETTER = "end_to_end", "", "%", "host_clock", "higher"


def read(run: dict):
    tenants = run["tenants"]
    if len(tenants) < 2 or any(t["role"] != "train" for t in tenants):
        return None
    work = [R.trained_tokens(run, t) for t in tenants]
    asked = [float(t["entry"]["tpu_request"]) for t in tenants]
    if sum(work) <= 0:
        return None
    return 100.0 * min((w / sum(work)) / (a / sum(asked))
                       for w, a in zip(work, asked))
