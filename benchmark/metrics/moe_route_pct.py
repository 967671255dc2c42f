"""What dropless routing costs beside the expert products: the device time
of the events under the binding's scope :data:`SCOPE` (router scores, top-k,
the sort of the (token, expert) pairs, the gather into the sorted buffer
and the weighted combine back; summed by ``scopetime.py``, which finds
the scope's name on the line below) over the device's busy time in the
traced window. The buffer has a row for every (token, expert) pair
whatever share of them is held, so this cost is there whether the held
experts are chosen or not."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import scopetime  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "kernels", "%", "device_trace", "train_tokens_per_s"
SCOPE = "bench_moe_route"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace["busy_s"] <= 0:
        return None
    took = scopetime.seconds(run, SCOPE)
    return None if took is None else 100.0 * took / trace["busy_s"]
