"""The lightning layers' chunked scan's share of its roofline: the least
time the chip could take for the scans of the traced window's programs
(the larger of needed operations over peak FLOP/s and needed bytes over
peak bytes/s, by each request's bucket and the counts of the
configuration's family), over the device time of those same programs'
events under the binding's scope ``bench_lin_attn``. Work and time are of
the same programs: only a program wholly inside the traced window counts,
on both sides (``programtime.py`` says why). Every request runs the scan,
so a traced window always has some."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import programtime  # noqa: E402
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "kernels", "%", "device_trace", "req_p95_ms"
UNDER, COUNT = "bench_lin_attn", "linear_attention_layer"


def share(run: dict, scope: str, count: str, longest: bool = False):
    """100 x least seconds / device seconds under ``scope``, over the
    programs wholly inside the traced window whose bucket the family's
    ``count`` gives work for (with ``longest``, of the longest bucket the
    cell sends alone: the configuration's ``positions_as_run``); ``None``
    where there is none."""
    joined = programtime.of_requests(run)
    if not joined:
        return None
    bucket = R.sizes(run["config"])["positions"] if longest else None
    layer, layers = R.count(run["config"], count), R.count(
        run["config"], count + "s")
    if layer is None or layers is None:
        return None
    least = took = 0.0
    for _tenant, request, program in joined:
        if bucket is not None and request["bucket"] != bucket:
            continue
        need = layer(run["config"], request["bucket"])
        seconds = program["scopes"].get(scope, 0.0)
        if need is None or seconds <= 0:
            continue
        least += layers(run["config"]) * R.flops.least_seconds(
            need, run["peaks"])[0]
        took += seconds
    return 100.0 * least / took if took > 0 else None


def read(run: dict):
    return share(run, UNDER, COUNT)
