#!/usr/bin/env python
"""Whose the chip's idle time is, from one traced run's ``.xplane.pb``.

The proxy writes the boundaries of every execution and transfer into the
profiler's trace as ``ks.*`` events (``obs.trace.phase``;
doc/observability.md). This reads them beside the device's ``XLA Ops``
line. First the device's axis is put on the host's, by an **offset**
measured from the trace (:func:`clock_offset`; reported with the lowest
offset that would still hold every op, and each program's first op behind
its bracket's start, ``first_op_us``). Then every idle gap of the chip
(between the union of the ops' intervals) is cut at the brackets' bounds
and each piece named.

Inside a program's ``ks.device`` bracket (the program's session):

- ``dispatch``: before its first op, while the proxy's call of the
  executable (``ks.dispatch``) had not returned,
- ``launch``: before its first op, after that call returned,
- ``in_program``: between two of its ops,
- ``barrier``: after its last op, until the host read returned.

Between brackets, charged to the program whose bracket ends the piece, as
the proxy's ``idle_*_ms_total`` counters are:

- ``attach``: up to the start of that execute's ``ks.rpc`` (nobody asking),
- ``gate``: from there to the end of its last ``ks.gate_wait``,
- ``proxy``: from there to the bracket's start.

A piece that no bracket ends is named by what ended the gap: ``xfer`` (a
``ks.xfer``, a ``put`` / ``get`` holding the device lock, was open when
the chip started again), ``trace_edge`` (it started again before the first
bracket or after the last, or that execute's ``ks.rpc`` began before the
trace did: the profiler keeps only events that began and ended while it
ran), else ``unattributed``. The phases sum to the chip's idle time.

Also reported: programs a session ran inside the traced span, how constant
``mono_us * 1000 - start_ns`` is over the events (the CLOCK_MONOTONIC
offset of the trace's axis), and the device time of the Pallas kernels
named in :data:`PALLAS`.

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
import bisect
import collections
import gzip
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np

REPO = Path(__file__).resolve().parent.parent
PALLAS = ("flash_fwd", "flash_dq", "flash_dkv", "fused_adam", "moe_rows")
PHASES = ("attach", "gate", "proxy", "dispatch", "launch", "in_program",
          "barrier", "xfer", "trace_edge", "unattributed")
#: how far the device's axis may lie behind the host's (one traced run
#: read -116 ms), and ops closer than this are one run, when the offset
#: between them is sought
SEARCH_NS = 1_000_000_000
JOIN_NS = 20_000


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


def clock_offset(brackets: list, ops: list) -> float:
    """The device's axis less the host's, in ns: the least, over the
    programs, of a program's first op less its ``ks.device`` bracket's
    start, for the shift of the ``XLA Ops`` line that leaves the most
    runs of ops (ops closer than ``JOIN_NS`` joined) wholly inside a
    bracket. Tried: each run that starts within ``SEARCH_NS`` before a
    bracket and is no longer than it, less that bracket's start. A steady
    program's runs fit as well a whole period away, give or take the one
    the trace's edge cut: the shifts that hold within one run of the most
    fall in groups (less than half the shortest bracket between two), and
    of the group nearest 0, the largest shift that holds its most."""
    runs: list = []
    for lo, hi, _ in ops:
        if runs and lo <= runs[-1][1] + JOIN_NS:
            runs[-1][1] = max(runs[-1][1], hi)
        else:
            runs.append([lo, hi])
    if not runs or not brackets:
        return 0.0
    lo, hi = np.array(runs, dtype=np.float64).T
    los = np.array([b["lo"] for b in brackets], dtype=np.float64)
    his = np.array([b["hi"] for b in brackets], dtype=np.float64)
    candidates = set()
    for b_lo, b_hi in zip(los, his):
        fits = ((lo >= b_lo - SEARCH_NS) & (lo <= b_hi)
                & (hi - lo <= b_hi - b_lo))
        candidates.update((lo[fits] - b_lo).tolist())
    if not candidates:
        return 0.0

    def held(offset):
        at = np.searchsorted(los, lo - offset, side="right") - 1
        return int(np.sum((at >= 0)
                          & (hi - offset <= his[np.maximum(at, 0)])))

    tried = sorted((c, held(c)) for c in candidates)
    best = max(h for _, h in tried)
    apart = float(np.min(his - los)) / 2
    groups: list = []
    for c, h in tried:
        if h >= best - 1:
            if groups and c - groups[-1][-1][0] < apart:
                groups[-1].append((c, h))
            else:
                groups.append([(c, h)])
    near = min(groups, key=lambda g: min(abs(c) for c, _ in g))
    most = max(h for _, h in near)
    return max(c for c, h in near if h == most)


def split(ks: list, ops: list) -> dict:
    def of(name):
        return [e for e in ks if e["name"] == name]

    brackets = sorted(of("ks.device"), key=lambda e: e["lo"])
    offset = clock_offset(brackets, ops)
    # the device's ops on the host's axis, and the union of their intervals
    ops = [(lo - offset, hi - offset, name) for lo, hi, name in ops]
    merged: list = []
    for lo, hi, _ in ops:
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    busy = sum(hi - lo for lo, hi in merged)
    span = merged[-1][1] - merged[0][0] if merged else 0

    # each bracket: its first op's start and last op's end, where the
    # execute's call to the executable returned, when it was asked for and
    # granted the token (None: its ks.rpc began before the trace did)
    los = [b["lo"] for b in brackets]
    first, last = [None] * len(brackets), [None] * len(brackets)
    for lo, hi, _ in ops:
        i = bisect.bisect_right(los, lo) - 1
        if i >= 0 and lo <= brackets[i]["hi"]:
            first[i] = lo if first[i] is None else first[i]
            last[i] = hi if last[i] is None else max(last[i], hi)
    rpc = [e for e in of("ks.rpc") if e.get("op") == "execute"]
    gate, dispatch = of("ks.gate_wait"), of("ks.dispatch")
    asked, dispatched = [], []
    for b in brackets:
        who = b["session"]
        call = next((r for r in rpc if r["session"] == who
                     and r["lo"] <= b["lo"] and b["hi"] <= r["hi"]), None)
        granted = call and max(
            (g["hi"] for g in gate if g["session"] == who
             and call["lo"] <= g["lo"] and g["hi"] <= b["lo"]),
            default=call["lo"])
        asked.append(call and (call["lo"], granted))
        dispatched.append(max((d["hi"] for d in dispatch
                               if d["session"] == who
                               and b["lo"] <= d["lo"] <= b["hi"]),
                              default=b["lo"]))
    xfers = of("ks.xfer")

    def pieces(lo, hi):
        """``[(phase, session, ns)]`` of the idle interval ``[lo, hi]``."""
        out, t = [], lo
        i = bisect.bisect_right(los, t) - 1
        if i < 0 or brackets[i]["hi"] <= t:
            i += 1
        while t < hi:
            if i < len(brackets) and brackets[i]["lo"] <= t:
                b = brackets[i]
                e, who = min(hi, b["hi"]), b["session"]
                if first[i] is not None and t >= last[i]:
                    out.append(("barrier", who, e - t))
                elif first[i] is not None and t >= first[i]:
                    out.append(("in_program", who, e - t))
                else:
                    d = clamp(dispatched[i], t, e)
                    out += [("dispatch", who, d - t), ("launch", who, e - d)]
                t, i = e, i + 1
                continue
            nxt = brackets[i]["lo"] if i < len(brackets) else float("inf")
            e = min(hi, nxt)
            if e == nxt and asked[i]:
                who = brackets[i]["session"]
                a = clamp(asked[i][0], t, e)
                g = clamp(asked[i][1], a, e)
                out += [("attach", who, a - t), ("gate", who, g - a),
                        ("proxy", who, e - g)]
            elif e == nxt or not brackets or hi < los[0] \
                    or hi > brackets[-1]["hi"]:
                out.append(("trace_edge", "", e - t))
            else:
                moving = next((x for x in xfers
                               if x["lo"] <= hi <= x["hi"]), None)
                out.append(("xfer", moving["session"], e - t) if moving
                           else ("unattributed", "", e - t))
            t = e
        return [p for p in out if p[2] > 0]

    by_phase: dict = collections.defaultdict(float)
    gaps = sorted(((b[0] - a[1], a[1], b[0])
                   for a, b in zip(merged, merged[1:])), reverse=True)
    longest = []
    for length, lo, hi in gaps:
        parts = pieces(lo, hi)
        for phase, who, ns in parts:
            by_phase[phase, who] += ns
        if len(longest) < 12:
            named = collections.defaultdict(float)
            for phase, _, ns in parts:
                named[phase + "_ms"] += ns / 1e6
            longest.append({"gap_ms": length / 1e6,
                            "sessions": sorted({w for _, w, _ in parts}),
                            **named})
    share = {p: {"pct": 0.0, "ms": 0.0, "by_session_pct": {}}
             for p in PHASES}   # % of the traced span, and by session
    for (phase, who), ns in sorted(by_phase.items()):
        share[phase]["pct"] += 100.0 * ns / span
        share[phase]["ms"] += ns / 1e6
        share[phase]["by_session_pct"][who] = 100.0 * ns / span
    inside = [b for b in brackets if merged and merged[0][0] <= b["lo"]
              and b["hi"] <= merged[-1][1]]
    behind = sorted(f - b["lo"] for f, b in zip(first, brackets)
                    if f is not None)
    # the least a program's last op ends before its bracket: the offset
    # could be that much lower and still hold every op
    slack = min((b["hi"] - e for e, b in zip(last, brackets)
                 if e is not None), default=0.0)
    mono = sorted(int(e["mono_us"]) * 1000 - e["lo"]
                  for e in ks if "mono_us" in e)
    pallas = {}
    for lo, hi, name in ops:
        # the op's own name, not an op that reads its result or calls it
        own = name.split(" = ")[0]
        kernel = next((k for k in PALLAS if own.startswith(f"%{k}.")), None)
        if kernel:
            seen = pallas.setdefault(kernel, {"events": 0, "seconds": 0.0,
                                              "example": name[:60]})
            seen["events"] += 1
            seen["seconds"] += (hi - lo) / 1e9

    def pick(q):
        return behind[min(int(q * len(behind)), len(behind) - 1)] / 1e3

    return {
        "span_s": span / 1e9, "busy_s": busy / 1e9,
        "idle_pct": 100.0 * (span - busy) / span if span else None,
        "clock": {"offset_us": offset / 1e3,
                  "lowest_offset_us": (offset - slack) / 1e3,
                  "programs": len(behind),
                  "first_op_us": {"p10": pick(0.1), "p50": pick(0.5),
                                  "p90": pick(0.9), "max": pick(1.0)}
                  if behind else None},
        "idle_by_phase": share, "longest_gaps": longest,
        "programs_by_session": dict(collections.Counter(
            str(b["session"]) for b in inside)),
        "ks_events": dict(collections.Counter(e["name"] for e in ks)),
        "sessions": sorted({str(e.get("session")) for e in ks}),
        "mono_offset_spread_us": ((mono[-1] - mono[0]) / 1e3
                                  if mono else None),
        "pallas": pallas,
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
