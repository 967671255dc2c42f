"""All tokens of all training steps inside the window, over the window's
length. Counted by the tenants: a step ends when its loss has reached the
host; the one under way at the window's end counts by the part inside."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, BETTER = "end_to_end", "", "tokens/s", "host_clock", "higher"


def read(run: dict):
    trainers = R.by_role(run, "train")
    if not trainers:
        return None
    return sum(R.trained_tokens(run, t) for t in trainers) / run["window_s"]
