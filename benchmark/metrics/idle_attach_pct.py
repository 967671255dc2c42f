"""Share of the counters' window that lay between the proxy's host stamps
"last program ended" and "next program called" with nobody asking for the
chip: the tenants' turn-around and the wire. The proxy splits every such
gap where it ends (``idle_attach_ms_total`` in ``usage``, charged to the
session whose program ended the gap); summed over tenants.

Idle between HOST stamps: the three ``idle_*_pct`` shares leave out what
the chip idles after the program is called, while the runtime allocates
its outputs inside ``ks.device`` (about half of ``device_idle_pct`` in
``gpt2s-pair-even``). Only the device trace bounds that part
(``scripts/ks_spans.py``: ``runtime``).

``gained``, ``idle_pct`` and ``per_exec`` are what the other readers of the
proxy's phase counters share.
"""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "attach", "%", "program_counter", "train_tokens_per_s"


def gained(run: dict, key: str, tenants: list | None = None):
    """What the tenants' sessions gained of the phase counter ``key``
    inside the counters' window, summed; ``None`` where the program
    reports no such counter (a parent of the PR that added them)."""
    tenants = run["tenants"] if tenants is None else tenants
    sessions = run["usage"]["begin"]["chip"]["sessions"]
    if not tenants or any(key not in sessions.get(t["pod"], {})
                          for t in tenants):
        return None
    return sum(R.session_delta(run, t["pod"], key) for t in tenants)


def idle_pct(run: dict, key: str):
    idle_ms, window_s = gained(run, key), R.counted(run)[1]
    if idle_ms is None or window_s <= 0:
        return None
    return 100.0 * idle_ms / (1e3 * window_s)


def per_exec(run: dict, key: str):
    """Counter ``key`` gained per execution, over all tenants."""
    total, execs = gained(run, key), gained(run, "exec_count")
    if total is None or not execs:
        return None
    return total / execs


def read(run: dict):
    return idle_pct(run, "idle_attach_ms_total")
