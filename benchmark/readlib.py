"""What the metric readers share: differences of the proxy registry's
counters between the window's two ends, per-tenant work counts, and the
way to the files a configuration names. No jax: the readers run in
``run.py``'s process."""

from __future__ import annotations

import importlib.util
import re
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
if str(HERE) not in sys.path:
    sys.path.insert(0, str(HERE))

import flops  # noqa: E402,F401  (readers import it from here)
import traffic  # noqa: E402


def _load(path: Path):
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", str(path.relative_to(HERE))), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """Another metric's reader, by its name."""
    return _load(HERE / "metrics" / f"{name}.py")


def named(config: dict, key: str):
    """The module that a configuration's file names under ``key`` by its
    path from the checkout's root: its ``reference``, its ``binding``
    (``models/<family>.py``) or its ``counts`` (``counts/<family>.py``)."""
    if not config.get(key):
        raise KeyError(f"the configuration names no {key!r}")
    path = (REPO / config[key]).resolve()
    if HERE not in path.parents or not path.is_file():
        raise FileNotFoundError(f"the configuration's {key} "
                                f"{config[key]!r} is no file of the "
                                "benchmark")
    return _load(path)


def sizes(config: dict) -> dict:
    """``{"vocab", "positions"}`` of the configuration as run, by its
    family's counts."""
    return named(config, "counts").sizes(config)


def count(config: dict, name: str):
    """One function of the family's counts, or ``None`` where the family
    offers no such count: its reader then says nothing."""
    return getattr(named(config, "counts"), name, None)


def counted(run: dict) -> tuple[str, float]:
    """Where the counters' window ends and how long it is: the whole
    window, or in a traced run the part up to half a second before the
    tracer starts (it slows the proxy's host code, which these counters
    are there to measure, and starting it stalls that code)."""
    if run.get("trace") and "mid" in run["proxy"]:
        return "mid", run["trace"]["counted_s"]
    return "end", run["window_s"]


def hist_delta(run: dict, family: str, **labels) -> tuple[float, float]:
    """``(sum, count)`` a histogram family gained inside the counters'
    window, over the series whose labels match ``labels``."""
    def total(snap, suffix):
        return sum(v for name, lab, v in snap["samples"]
                   if name == family + suffix
                   and all(lab.get(k) == want for k, want in labels.items()))
    begin, end = run["proxy"]["begin"], run["proxy"][counted(run)[0]]
    return (total(end, "_sum") - total(begin, "_sum"),
            total(end, "_count") - total(begin, "_count"))


def session_delta(run: dict, pod: str, key: str) -> float:
    """What a session's ``usage`` counter gained inside the counters'
    window."""
    def at(which):
        return run["usage"][which]["chip"]["sessions"].get(pod, {}).get(
            key, 0.0)
    return at(counted(run)[0]) - at("begin")


def counted_steps(run: dict, t: dict) -> float:
    """A trainer's steps inside the counters' window."""
    return steps_between(t, 0.0, counted(run)[1])


def by_role(run: dict, role: str) -> list[dict]:
    return [t for t in run["tenants"] if t["role"] == role]


def trained_tokens(run: dict, t: dict) -> float:
    """Tokens of the training steps inside the window. The step that was
    under way when the window closed counts by the part of it inside (the
    tenant finishes it): a whole step is half a per cent of a window, and
    a count that jumps by that much hides every smaller loss."""
    return (steps_between(t, 0.0, run["window_s"])
            * t["done"]["tokens_per_step"])


def steps_between(t: dict, a: float, b: float) -> float:
    """Training steps of tenant ``t`` inside ``[a, b]`` (seconds from the
    window's start), a step that straddles an end counted by the part of
    it inside."""
    total, prev = 0.0, 0.0
    for done in t["done"]["done_at_s"]:
        lo, hi = max(prev, a), min(done, b)
        if done > prev and hi > lo:
            total += (hi - lo) / (done - prev)
        prev = done
    return total


def requests(run: dict, t: dict) -> list[dict]:
    """A serving tenant's schedule joined with what it recorded:
    ``due_s``, ``done_s`` (None where unanswered), ``length``, ``bucket``."""
    entry = t["entry"]
    schedule = traffic.request_schedule(
        run["seed"], t["index"], entry["arrivals"], entry["lengths"],
        [int(b) for b in entry["buckets"]], run["seconds"])
    done = {int(r[0]): r[2] for r in t["done"]["rows"] if r[3] is not None}
    return [dict(r, done_s=done.get(r["idx"])) for r in schedule]
