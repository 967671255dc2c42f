"""Device time under named scopes, PROGRAM BY PROGRAM, from a run's
``.xplane.pb``: for the readers whose work and time must be of the same
programs.

    python benchmark/programtime.py <trace dir or .xplane.pb> <out.json>

A traced window is ~3 s and one program of a long request most of a second:
a window's edge cuts a program, and its device events inside the window
are then time without the work beside it (or, counted by requests that
FINISHED inside, work without all of its time). The proxy brackets every
program it runs by a ``ks.device`` event on the host's plane (its stats:
``session``, whose program; ``mono_us``, CLOCK_MONOTONIC at entry), and the
profiler records such an event only if it began AND ended while tracing.
So the programs listed here are exactly those wholly inside the traced
window: each with the device time of the ops that ran inside its bracket,
under every scope whose name begins ``bench_`` (a binding's scopes: this
file holds no scope's name). A reader joins a program to the request that
it answered by session and time (:func:`of_requests`) and sets the
request's needed work against that program's own time: a program the
window cuts is left out on both sides, which costs a reading some of its
sample and never its balance. A scope's time over the WHOLE window is
``scopetime.seconds``'s to give, not this file's.

The trace is parsed once, in a child process under ``JAX_PLATFORMS=cpu``
(``run.py`` never imports jax), and kept beside the trace.
"""

from __future__ import annotations

import json
import os
import re
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

CACHE = "programtime.json"
BRACKET = "ks.device"
SCOPE = re.compile(r"(?<![\w.-])(bench_\w+)(?![\w.-])")
#: the device plane's clock runs a little ahead of the host's
#: (``scripts/ks_spans.py``): an op may start this long before its bracket
SKEW_NS = 1_000_000
#: a request's answer reaches its tenant this long after its program's
#: bracket closed, at the most, for the two to be joined
REPLY_S = 0.25


def by_program(xplane: str) -> dict:
    import reduce_trace as rt
    from jax.profiler import ProfileData

    tables = rt.event_scopes(xplane)
    brackets, ops = [], []
    for plane in ProfileData.from_file(xplane).planes:
        if plane.name.startswith(rt.DEVICE_PREFIX):
            table, cache = tables.get(plane.name, {}), {}
            for line in plane.lines:
                if line.name != rt.OPS_LINE:
                    continue
                for ev in line.events:
                    if ev.name not in cache:
                        cache[ev.name] = sorted(set(
                            SCOPE.findall(table.get(ev.name, ""))
                            + SCOPE.findall(ev.name)))
                    ops.append((ev.start_ns, ev.duration_ns, cache[ev.name]))
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name == BRACKET:
                        stats = {k: v for k, v in ev.stats}
                        if "mono_us" in stats:
                            brackets.append({
                                "session": str(stats.get("session", "")),
                                "lo": ev.start_ns,
                                "hi": ev.start_ns + ev.duration_ns,
                                "mono_s": int(stats["mono_us"]) / 1e6})
    return {"programs": assign(brackets, ops)}


def assign(brackets: list[dict], ops: list[tuple]) -> list[dict]:
    """The programs of ``brackets`` (``session``, ``lo`` / ``hi`` in the
    trace's ns, ``mono_s``), each with the device time of the ``ops``
    (``(start_ns, duration_ns, scopes)``) that began inside it. The ops
    line nests (a ``while`` holds its body's ops): time is the UNION of
    the intervals, of a program's ops and of each scope's, as
    ``reduce_trace``'s ``busy_s`` is."""
    brackets = sorted(brackets, key=lambda b: b["lo"])
    programs = [{"session": b["session"], "start_mono_s": b["mono_s"],
                 "end_mono_s": b["mono_s"] + (b["hi"] - b["lo"]) / 1e9,
                 "device_s": 0.0, "scopes": {}} for b in brackets]
    covered = [{} for _ in brackets]
    at = 0
    for lo, dur, scopes in sorted(ops):
        while at < len(brackets) and brackets[at]["hi"] < lo:
            at += 1
        if at < len(brackets) and brackets[at]["lo"] - SKEW_NS <= lo:
            prog, upto = programs[at], covered[at]
            for scope in ("", *scopes):
                new = max(0, lo + dur - max(lo, upto.get(scope, 0))) / 1e9
                upto[scope] = max(upto.get(scope, 0), lo + dur)
                if scope:
                    prog["scopes"][scope] = prog["scopes"].get(scope,
                                                               0.0) + new
                else:
                    prog["device_s"] += new
    return programs


def read(run: dict) -> dict | None:
    """``{"programs": [...]}`` of the run's traced window, or ``None``: an
    untraced run, a trace that is gone or cannot be read."""
    where = ((run.get("proxy") or {}).get("trace") or {}).get("dir")
    if not run.get("trace") or not where or not os.path.isdir(where):
        return None
    cache = Path(where) / CACHE
    if not cache.exists():
        done = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), where,
             str(cache)], env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=240.0)
        if done.returncode != 0 or not cache.exists():
            print(f"programtime: no reading of {where}: "
                  f"{(done.stderr or done.stdout)[-400:]}", file=sys.stderr)
            return None
    return json.loads(cache.read_text())


def of_requests(run: dict):
    """``[(tenant, request, program)]``: each serving tenant's request
    whose program lies wholly inside the traced window (a
    ``readlib.requests`` row), with that program: its ``device_s`` and its
    device time by scope, ``scopes``. ``None`` where the trace cannot be
    read."""
    import readlib as R

    got = read(run)
    if got is None:
        return None
    # CLOCK_MONOTONIC at the window's start: the tracer's own start is
    # ``from_s`` into the window
    t0 = run["proxy"]["trace"]["start"] - run["trace"]["from_s"]
    out = []
    for t in R.by_role(run, "score"):
        done = sorted((r for r in R.requests(run, t)
                       if r["done_s"] is not None),
                      key=lambda r: r["done_s"])
        at = 0
        for prog in got["programs"]:
            if prog["session"] != t["pod"]:
                continue
            end = prog["end_mono_s"] - t0
            while at < len(done) and done[at]["done_s"] < end:
                at += 1
            if at < len(done) and done[at]["done_s"] - end <= REPLY_S:
                out.append((t, done[at], prog))
                at += 1
    return out


def main(argv) -> None:
    if len(argv) != 3:
        raise SystemExit(__doc__)
    import reduce_trace as rt

    out = by_program(rt.find_xplane(argv[1]))
    tmp = argv[2] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, argv[2])


if __name__ == "__main__":
    main(sys.argv)
