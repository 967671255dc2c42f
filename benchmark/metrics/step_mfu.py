"""The whole step's share of the chip's peak: model operations of the
window, by the counts of the configuration's family (for GPT-2: 6 N per
trained token and 2 N per scored token, N the parameters that multiply,
plus attention) over window x peak bf16 FLOP/s (in a traced run: the part
of the window before the tracer starts, which slows the host). A family
that offers no count for a role that ran says nothing."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "whole step", "%", "host_clock", "train_tokens_per_s"


def read(run: dict):
    cfg, ops, seconds = run["config"], 0.0, R.counted(run)[1]
    trainers, scorers = R.by_role(run, "train"), R.by_role(run, "score")
    train_flops = R.count(cfg, "train_flops")
    score_flops = R.count(cfg, "score_flops")
    untold = ((trainers and train_flops is None)
              or (scorers and score_flops is None))
    if untold:
        return None
    for t in trainers:
        e = t["entry"]
        ops += R.counted_steps(run, t) * train_flops(
            cfg, int(e["batch"]), int(e["seq_len"]))
    for t in scorers:
        ops += sum(score_flops(cfg, r["length"])
                   for r in R.requests(run, t)
                   if r["done_s"] is not None
                   and r["done_s"] <= seconds)
    if ops <= 0:
        return None
    return 100.0 * ops / (seconds * run["peaks"]["bf16_flops_per_s"])
