"""The sparse layers' attention over the chosen blocks, as a share of its
roofline, AT ONE STATED BUCKET: the longest the cell sends (the
configuration's ``positions_as_run``). The least time the chip could take
for ``topk * block_size`` keys a query (fewer near a sequence's start) in
the traced window's programs of that bucket, over the device time of those
same programs' events under the binding's scope ``bench_sparse_attn``
(``lin_attn_roofline``'s reader, on another scope and count). The share
differs by bucket (a kernel that visits every causal tile takes n^2 for
work of n), and a ~3 s window holds one or two documents: read over
whatever bucket landed in it the number would follow the draw, not the
kernel. A traced window that holds no whole program of the stated bucket
says nothing (never 0)."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "kernels", "%", "device_trace", "req_p95_ms"
UNDER, COUNT = "bench_sparse_attn", "sparse_attention_layer"


def read(run: dict):
    return R.reader("lin_attn_roofline").share(run, UNDER, COUNT,
                                               longest=True)
