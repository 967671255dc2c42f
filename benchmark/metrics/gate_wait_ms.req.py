"""Mean ``kubeshare_token_grant_wait_seconds`` of the scoring tenants'
grants over the window: what a request waits for the chip's token."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "token gate", "ms", "program_counter", "req_p95_ms"


def read(run: dict):
    total, grants = 0.0, 0.0
    for t in R.by_role(run, "score"):
        s, n = R.hist_delta(run, "kubeshare_token_grant_wait_seconds",
                            namespace=t["namespace"])
        total, grants = total + s, grants + n
    return 1e3 * total / grants if grants > 0 else None
