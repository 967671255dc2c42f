#!/usr/bin/env python
"""Whose the chip's idle time is, from one traced run's ``.xplane.pb``.

The proxy writes the boundaries of every execution into the profiler's
trace as ``ks.*`` events (``obs.trace.phase``; doc/observability.md). This
reads them beside the device's ``XLA Ops`` line: every idle gap of the
chip longer than 0.1 ms is put down to the session whose program ended it
(the ``ks.device`` event open when the chip started again) and split, each
bound clamped into the gap, into

- ``attach``: up to the start of that execute's ``ks.rpc`` (nobody asking),
- ``gate``: from there to the end of its last ``ks.gate_wait``,
- ``proxy``: from there to the start of ``ks.device``,
- ``runtime``: from there to the program's first op: INSIDE ``ks.device``,
  where no host stamp of the proxy sees it (the runtime allocating the
  program's outputs); the ``idle_*_ms_total`` counters leave it out,

plus ``in_program`` (holes between two ops of one program) and
``unattributed`` (no ``ks.device`` open when the chip started again: the
trace ended first, or a ``put``/``get`` transfer ended the gap). Also
reported: how constant ``mono_us * 1000 - start_ns`` is over the events
(the CLOCK_MONOTONIC offset of the trace's axis), and the device time of
the four Pallas kernels by their names.

Usage::

    python scripts/ks_spans.py <trace.xplane.pb | trace.xplane.pb.gz>
    python scripts/ks_spans.py --run <workload> [--seed N] [--seconds S]

``--run`` makes one ``--trace 1`` run of ``benchmark/run.py`` as it is, in
this process's checkout, keeps the ``.xplane.pb`` that ``run.py`` deletes
with its run directory (as ``chiprun_out/ks_spans/<workload>.xplane.pb.gz``)
and analyses it in a child held to the CPU, after the chip is free again.
"""

from __future__ import annotations

import argparse
import collections
import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
PALLAS = ("flash_fwd", "flash_dq", "flash_dkv", "fused_adam")
MIN_GAP_NS = 100_000        # shorter: the sequencer between two ops
#: the device plane's clock runs a little ahead of the host's: an op can
#: start up to ~0.5 ms "before" the ks.device event it belongs to
SKEW_NS = 1_000_000


def load(path: Path):
    """``(ks events, ops)``: every ``ks.*`` event of the host planes as a
    dict (name, lo, hi in ns, and its stats), every op of a TPU plane's
    ``XLA Ops`` line as ``(lo, hi, name)``."""
    from jax.profiler import ProfileData

    raw = path.read_bytes()
    if path.suffix == ".gz":
        raw = gzip.decompress(raw)
    ks, ops = [], []
    for plane in ProfileData.from_serialized_xspace(raw).planes:
        on_chip = plane.name.startswith("/device:TPU:")
        for line in plane.lines:
            if on_chip and line.name == "XLA Ops":
                ops.extend((ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name) for ev in line.events)
            elif not plane.name.startswith("/device:"):
                for ev in line.events:
                    if ev.name.startswith("ks."):
                        ks.append({"name": ev.name, "lo": ev.start_ns,
                                   "hi": ev.start_ns + ev.duration_ns,
                                   **{k: v for k, v in ev.stats}})
    return ks, sorted(ops)


def clamp(x, lo, hi):
    return min(max(x, lo), hi)


def split(ks: list, ops: list) -> dict:
    merged: list = []       # the union of the ops' intervals
    for lo, hi, _ in ops:
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    busy = sum(hi - lo for lo, hi in merged)
    span = merged[-1][1] - merged[0][0] if merged else 0
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)

    def of(name):
        return [e for e in ks if e["name"] == name]

    dev, gate = of("ks.device"), of("ks.gate_wait")
    rpc = [e for e in of("ks.rpc") if e.get("op") == "execute"]
    by_phase: dict = collections.defaultdict(float)
    longest = []
    for length, lo, hi in gaps:
        if length < MIN_GAP_NS:
            break
        owner = next((e for e in dev
                      if e["lo"] - SKEW_NS <= hi <= e["hi"]), None)
        if owner is None:
            by_phase["unattributed", ""] += length
            continue
        who = owner["session"]
        if lo >= owner["lo"]:
            by_phase["in_program", who] += length
            continue
        call = next((r for r in rpc if r["session"] == who
                     and r["lo"] <= owner["lo"] and owner["hi"] <= r["hi"]),
                    None)
        arrived = call["lo"] if call else owner["lo"]
        granted = max((g["hi"] for g in gate if g["session"] == who
                       and arrived <= g["lo"] and g["hi"] <= owner["lo"]),
                      default=arrived)
        a = clamp(arrived, lo, hi)
        g = clamp(granted, a, hi)
        d = clamp(owner["lo"], g, hi)
        parts = {"attach": a - lo, "gate": g - a, "proxy": d - g,
                 "runtime": hi - d}
        for phase, ns in parts.items():
            by_phase[phase, who] += ns
        if len(longest) < 12:
            longest.append({"gap_ms": length / 1e6, "session": who,
                            **{k + "_ms": v / 1e6
                               for k, v in parts.items()}})
    share = {}              # phase -> % of the traced span, and by session
    for (phase, who), ns in sorted(by_phase.items()):
        share.setdefault(phase, {"pct": 0.0, "by_session_pct": {}})
        share[phase]["pct"] += 100.0 * ns / span
        share[phase]["by_session_pct"][who] = 100.0 * ns / span
    offsets = sorted(int(e["mono_us"]) * 1000 - e["lo"]
                     for e in ks if "mono_us" in e)
    pallas = {}
    for lo, hi, name in ops:
        kernel = next((k for k in PALLAS if f"%{k}." in name), None)
        if kernel:
            seen = pallas.setdefault(kernel, {"events": 0, "seconds": 0.0,
                                              "example": name[:60]})
            seen["events"] += 1
            seen["seconds"] += (hi - lo) / 1e9
    return {
        "span_s": span / 1e9, "busy_s": busy / 1e9,
        "idle_pct": 100.0 * (span - busy) / span if span else None,
        "idle_by_phase": share, "longest_gaps": longest,
        "ks_events": dict(collections.Counter(e["name"] for e in ks)),
        "sessions": sorted({str(e.get("session")) for e in ks}),
        "mono_offset_spread_us": ((offsets[-1] - offsets[0]) / 1e3
                                  if offsets else None),
        "pallas": pallas,
        "branch_0_fun_events": sum(
            1 for _, _, n in ops if n.lstrip("%").startswith("branch_0_fun")),
    }


class _KeepTrace:
    """Stands in for ``run.py``'s ``shutil``: before a run's directory
    goes, its ``.xplane.pb`` is copied to ``self.kept``."""

    def __init__(self, kept: Path):
        self.kept = kept

    def __getattr__(self, name):
        return getattr(shutil, name)

    def rmtree(self, path, **kwargs):
        found = sorted(Path(path).rglob("*.xplane.pb"))
        if found:
            self.kept.parent.mkdir(parents=True, exist_ok=True)
            with open(found[-1], "rb") as src, \
                    gzip.open(self.kept, "wb", compresslevel=6) as dst:
                shutil.copyfileobj(src, dst)
        shutil.rmtree(path, **kwargs)


def traced_run(workload: str, seed: int, seconds: float) -> Path:
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "bench_run", REPO / "benchmark" / "run.py")
    run = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = run
    spec.loader.exec_module(run)
    kept = REPO / "chiprun_out" / "ks_spans" / f"{workload}.xplane.pb.gz"
    run.shutil = _KeepTrace(kept)
    os.chdir(REPO)
    rc = run.main(["--workload", workload, "--seed", str(seed),
                   "--seconds", str(seconds), "--trace", "1"])
    if rc or not kept.exists():
        sys.exit(f"traced run failed (rc={rc}) or left no .xplane.pb")
    return kept


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("trace", nargs="?", type=Path)
    ap.add_argument("--run", metavar="WORKLOAD")
    ap.add_argument("--seed", type=int, default=2_147_484_001)
    ap.add_argument("--seconds", type=float, default=40.0)
    args = ap.parse_args(argv)
    if args.run:
        kept = traced_run(args.run, args.seed, args.seconds)
        # this process never touched jax; the child must not take the chip
        return subprocess.run(
            [sys.executable, __file__, str(kept)],
            env=dict(os.environ, JAX_PLATFORMS="cpu")).returncode
    if args.trace is None:
        ap.error("a trace file or --run is needed")
    result = split(*load(args.trace))
    args.trace.with_name(args.trace.name.split(".")[0]
                         + ".ks_spans.json").write_text(
        json.dumps(result, indent=1))
    print(json.dumps(result, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
