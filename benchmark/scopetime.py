"""Device time under named scopes that ``reduce_trace.py`` does not keep,
summed from a run's ``.xplane.pb`` for the metric readers that need one.

    python benchmark/scopetime.py <trace dir or .xplane.pb> <out.json> <scope> [<scope> ...]

``reduce_trace.SCOPES`` is a fixed pair. A reader of another scope says
which in its own file (a line ``SCOPE = "<name>"``) and calls
:func:`seconds` with the run's record: this file holds no scope's name.
The trace is still on disk when the readers run
(``run["proxy"]["trace"]["dir"]``); it is parsed ONCE, in a child process
under ``JAX_PLATFORMS=cpu`` as ``run.py`` runs the reducer (``run.py``
never imports jax), for every scope that a reader under ``metrics/``
declares, and the sums are kept beside the trace for the next reader. An
event belongs to a scope whose name stands whole in its metadata's path
(``bench_moe`` does not claim ``jvp(bench_moe_route)``). Time is averaged
over the device planes, as ``reduce_trace``'s ``busy_s`` is.
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

CACHE = "scopetime.json"


def declared() -> tuple[str, ...]:
    """Every scope that a reader under ``metrics/`` declares."""
    found = set()
    for path in (HERE / "metrics").glob("*.py"):
        found.update(re.findall(r'^SCOPE = "([\w.-]+)"', path.read_text(),
                                re.M))
    return tuple(sorted(found))


def by_scope(xplane: str, scopes) -> dict:
    """``{scope: {"seconds", "events"}}`` over the ``XLA Ops`` lines of the
    device planes; a scope with no event is left out."""
    import reduce_trace as rt
    from jax.profiler import ProfileData

    whole = {s: re.compile(rf"(?<![\w.-]){re.escape(s)}(?![\w.-])")
             for s in scopes}
    tables = rt.event_scopes(xplane)
    planes = [p for p in ProfileData.from_file(xplane).planes
              if p.name.startswith(rt.DEVICE_PREFIX)]
    out: dict = {}
    for plane in planes:
        table, cache = tables.get(plane.name, {}), {}
        for line in plane.lines:
            if line.name != rt.OPS_LINE:
                continue
            for ev in line.events:
                if ev.name not in cache:
                    text = table.get(ev.name, "")
                    cache[ev.name] = [s for s, pat in whole.items()
                                      if pat.search(text)
                                      or pat.search(ev.name)]
                for scope in cache[ev.name]:
                    got = out.setdefault(scope, {"seconds": 0.0,
                                                 "events": 0})
                    got["seconds"] += ev.duration_ns / 1e9
                    got["events"] += 1
    for got in out.values():
        got["seconds"] /= max(1, len(planes))
    return out


def seconds(run: dict, scope: str) -> float | None:
    """Device seconds under ``scope`` in the run's traced window, or
    ``None``: an untraced run, a trace with no such event (a program that
    has no such scope), a trace that is gone or cannot be read."""
    where = ((run.get("proxy") or {}).get("trace") or {}).get("dir")
    if not run.get("trace") or not where or not os.path.isdir(where):
        return None
    cache = Path(where) / CACHE
    kept = json.loads(cache.read_text()) if cache.exists() else {}
    if scope not in kept.get("asked", ()):
        asked = sorted({scope, *declared()})
        done = subprocess.run(
            [sys.executable, str(Path(__file__).resolve()), where,
             str(cache), *asked], env=dict(os.environ, JAX_PLATFORMS="cpu"),
            capture_output=True, text=True, timeout=240.0)
        if done.returncode != 0 or not cache.exists():
            print(f"scopetime: no reading of {where}: "
                  f"{(done.stderr or done.stdout)[-400:]}", file=sys.stderr)
            return None
        kept = json.loads(cache.read_text())
    took = kept["by_scope"].get(scope, {}).get("seconds", 0.0)
    return took if took > 0 else None


def main(argv) -> None:
    if len(argv) < 4:
        raise SystemExit(__doc__)
    import reduce_trace as rt

    asked = list(argv[3:])
    out = {"asked": asked,
           "by_scope": by_scope(rt.find_xplane(argv[1]), asked)}
    tmp = argv[2] + ".tmp"
    with open(tmp, "w") as f:
        json.dump(out, f)
    os.replace(tmp, argv[2])


if __name__ == "__main__":
    main(sys.argv)
