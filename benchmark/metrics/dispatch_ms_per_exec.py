"""The proxy's call of a program's executable per execution, over all
tenants: ``device_start`` until that call returns (``dispatch_ms_total``,
the first part of ``exec_ms_total``; the rest, until the completion
barrier's host read returns, is ``barrier_ms_total``). The runtime's launch
and, for a program that is not recycled, its allocation of the outputs are
in it; the chip idles through the part of it before the first op."""
from __future__ import annotations

import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import readlib as R  # noqa: E402

KIND, LAYER, UNIT, SOURCE, MOVES = "per_layer", "chip proxy", "ms", "program_counter", "train_tokens_per_s"


def read(run: dict):
    return R.reader("idle_attach_pct").per_exec(run, "dispatch_ms_total")
