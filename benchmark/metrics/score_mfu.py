"""The scored requests' share of the chip's peak: model operations of the
requests answered inside the window, by the counts of the configuration's
family (``score_flops`` of each request's real length: 2 N a token, the
scans, the attention), over window x peak bf16 FLOP/s (in a traced run:
the part of the window before the tracer starts, which slows the host).
The whole step's share for a cell without a trainer: ``step_mfu`` moves
``train_tokens_per_s``, which such a cell does not report."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "whole step", "%", "host_clock", "req_p95_ms"


def read(run: dict):
    cfg, seconds = run["config"], R.counted(run)[1]
    score_flops = R.count(cfg, "score_flops")
    if score_flops is None:
        return None
    ops = sum(score_flops(cfg, r["length"])
              for t in R.by_role(run, "score") for r in R.requests(run, t)
              if r["done_s"] is not None and r["done_s"] <= seconds)
    if ops <= 0:
        return None
    return 100.0 * ops / (seconds * run["peaks"]["bf16_flops_per_s"])
