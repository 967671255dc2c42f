"""Share of the holds the token gate's weighted pick kept at a contended
program boundary (a program ended while another tenant waited, and the
holder stayed) that the chip proxy then handed to the waiter because the
holder's grace ran out with no new request, and whose holder did not come
back before the idle timer would have let go (``kept_yielded`` less
``kept_early``, over ``kept_count``, in ``usage``'s ``chip.sessions``, all
gained inside the counters' window, over all tenants). A hand-over its
holder's next request showed too soon counts for nothing here: the share
is what the waiter gained without costing the holder a wait. Says nothing
on a program without the counters, or where no hold was kept in the
window."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "token gate", "%", "program_counter", "train_tokens_per_s"


def read(run: dict):
    gained = R.reader("idle_attach_pct").gained
    kept = gained(run, "kept_count")
    yielded, early = gained(run, "kept_yielded"), gained(run, "kept_early")
    if not kept or yielded is None or early is None:
        return None
    return 100.0 * (yielded - early) / kept
