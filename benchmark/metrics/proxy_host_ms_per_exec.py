"""The proxy's own host work around a program: over all sessions, summed
``execute`` handling time minus summed grant wait minus summed charged
``exec_ms``, per execution (the RPC histogram is labelled by op only)."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "chip proxy", "ms", "program_counter", "train_tokens_per_s"


def read(run: dict):
    rpc_s, execs = R.hist_delta(run, "kubeshare_proxy_rpc_latency_seconds",
                                op="execute")
    if execs <= 0:
        return None
    wait_s, _ = R.hist_delta(run, "kubeshare_token_grant_wait_seconds")
    charged_ms = sum(R.session_delta(run, t["pod"], "exec_ms_total")
                     for t in run["tenants"])
    return (1e3 * (rpc_s - wait_s) - charged_ms) / execs
