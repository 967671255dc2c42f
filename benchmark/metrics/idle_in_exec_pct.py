"""Share of the traced window that the chip idled INSIDE the proxy's
``ks.device`` brackets: for each program wholly inside the window
(``programtime.py``), its bracket's length less the union of its ops'
intervals, summed, over the trace's ``window_s``. The proxy's dispatch of
the program, the runtime's launch, holes between its ops and the
completion barrier's read after its last op: what ``ks.dispatch`` and
``ks.barrier`` split, and what the three ``idle_*_pct`` shares (between
host stamps, OUTSIDE the brackets) leave out of ``device_idle_pct``."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import programtime  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "device", "%", "device_trace", "train_tokens_per_s"


def read(run: dict):
    trace = run.get("trace")
    if not trace or trace.get("busy_s", 0) <= 0:
        return None     # nothing ran on the chip's plane: no chip here
    got = programtime.read(run)
    if not got or not got["programs"]:
        return None
    idle_s = sum(p["end_mono_s"] - p["start_mono_s"] - p["device_s"]
                 for p in got["programs"])
    return 100.0 * idle_s / trace["window_s"]
