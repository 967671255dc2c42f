"""The fused Adam's share of its roofline (memory-bound: 28 bytes a
parameter over peak bytes/s), over the device time of the events under the
scope ``bench_opt`` in the traced window."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "kernels", "%", "device_trace", "train_tokens_per_s"


def read(run: dict):
    trace = run.get("trace")
    took = (trace or {}).get("scopes", {}).get("bench_opt", {}).get(
        "seconds", 0.0)
    if took <= 0:
        return None
    one = R.flops.least_seconds(
        R.flops.adam(int(run["config"]["parameters_as_run"])),
        run["peaks"])[0]
    steps = sum(R.steps_between(t, trace["from_s"], trace["to_s"])
                for t in R.by_role(run, "train"))
    return 100.0 * one * steps / took
