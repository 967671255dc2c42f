"""The tenant side and the wire, per training step, from per-session
sources only: the trainers' wall time per step, minus their grant wait per
step (the histogram is labelled by namespace), minus their charged
``exec_ms_total`` per step, minus ``proxy_host_ms_per_exec`` times
their executions per step. Mean over the trainers."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "attach", "ms", "program_counter", "train_tokens_per_s"


def read(run: dict):
    per_exec = R.reader("proxy_host_ms_per_exec").read(run)
    vals = []
    for t in R.by_role(run, "train"):
        steps = R.counted_steps(run, t)
        if per_exec is None or steps <= 0:
            continue
        wait_s, _ = R.hist_delta(run, "kubeshare_token_grant_wait_seconds",
                                 namespace=t["namespace"])
        vals.append((1e3 * R.counted(run)[1] - 1e3 * wait_s
                     - R.session_delta(run, t["pod"], "exec_ms_total")
                     - per_exec * R.session_delta(run, t["pod"],
                                                  "exec_count")) / steps)
    return sum(vals) / len(vals) if vals else None
