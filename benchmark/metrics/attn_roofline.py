"""Flash attention's share of its roofline: the least time the chip could
take for the attention the traced window did (the larger of needed
operations over peak FLOP/s and needed bytes over peak bytes/s), over the
device time of the events under the scope ``bench_attn``."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "kernels", "%", "device_trace", "train_tokens_per_s"


def need(run: dict, a: float, b: float):
    """Least seconds for the attention done in ``[a, b]``, by the counts
    of the configuration's family; ``None`` where it counts none."""
    cfg, peaks, least = run["config"], run["peaks"], 0.0
    layer = R.count(cfg, "attention_layer")
    count_layers = R.count(cfg, "attention_layers")
    if layer is None or count_layers is None:
        return None
    layers = count_layers(cfg)
    for t in R.by_role(run, "train"):
        e = t["entry"]
        one = R.flops.least_seconds(layer(
            cfg, int(e["batch"]), int(e["seq_len"]), backward=True), peaks)[0]
        least += layers * one * R.steps_between(t, a, b)
    for t in R.by_role(run, "score"):
        for r in R.requests(run, t):
            if r["done_s"] is not None and a <= r["done_s"] <= b:
                least += layers * R.flops.least_seconds(
                    layer(cfg, 1, r["bucket"], backward=False), peaks)[0]
    return least


def read(run: dict):
    trace = run.get("trace")
    took = (trace or {}).get("scopes", {}).get("bench_attn", {}).get(
        "seconds", 0.0)
    if took <= 0:
        return None
    least = need(run, trace["from_s"], trace["to_s"])
    return None if least is None else 100.0 * least / took
