"""95th percentile (nearest rank), over ALL requests due in the window, of
due time -> score on the host. A request that failed or was still
unanswered when the drain ended counts with the whole wait it had by
then."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, BETTER = "end_to_end", "", "ms", "host_clock", "lower"


def latencies_ms(run: dict) -> list[float]:
    out = []
    for t in R.by_role(run, "score"):
        gave_up = run["window_s"] + run["drain_s"]
        for r in R.requests(run, t):
            done = r["done_s"] if r["done_s"] is not None else gave_up
            out.append(1e3 * (done - r["due_s"]))
    return sorted(out)


def read(run: dict):
    lat = latencies_ms(run)
    if not lat:
        return None
    return lat[min(len(lat) - 1, -(-95 * len(lat) // 100) - 1)]
