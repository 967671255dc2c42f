"""What choosing the blocks costs a document's program, AT ONE STATED
BUCKET (the longest the cell sends, as ``sparse_attn_roofline`` is read):
the device time of the events under the binding's scope
``bench_sparse_select`` (pooled keys, per-head scores over the compressed
windows, their softmax and sum over a group's heads, the max over a
block's windows, top-k, the bit table) over the whole device time of the
SAME programs: those of that bucket wholly inside the traced window
(``programtime.py``). Not over the window's busy time: one document's
selection over whatever else the window held follows the window, not the
selection. A window without such a program says nothing (never 0)."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import programtime  # noqa: E402
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "kernels", "%", "device_trace", "req_p95_ms"
UNDER = "bench_sparse_select"


def read(run: dict):
    joined = programtime.of_requests(run)
    if not joined:
        return None
    bucket = R.sizes(run["config"])["positions"]
    programs = [p for _t, r, p in joined
                if r["bucket"] == bucket and p["scopes"].get(UNDER, 0.0) > 0]
    whole = sum(p["device_s"] for p in programs)
    if whole <= 0:
        return None
    return 100.0 * sum(p["scopes"][UNDER] for p in programs) / whole
