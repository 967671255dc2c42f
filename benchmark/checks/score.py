"""What decides ``correct`` for a ``score`` tenant.

Answers that can be checked one by one: once the window has closed, a
sample of the finished requests drawn from the seed (the longest always in
it) is scored again by the plain reference, and the widest gap between a
served score and the reference's is compared. An answer that came late is
late, not wrong (its wait is in the latency); one that never came counts.
"""

from __future__ import annotations

import math


def _schedule(spec: dict, t: dict) -> list[dict]:
    import traffic

    entry = t["entry"]
    return traffic.request_schedule(
        int(spec["seed"]), t["index"], entry["arrivals"], entry["lengths"],
        [int(b) for b in entry["buckets"]], float(spec["seconds"]))


def reference(ref, spec: dict, t: dict) -> dict:
    """In the check's process: reference scores of the sampled requests."""
    import readlib
    import traffic

    cfg, seed, idx = spec["config"], int(spec["seed"]), t["index"]
    vocab = readlib.sizes(cfg)["vocab"]
    schedule = {r["idx"]: r for r in _schedule(spec, t)}
    finished = {int(r[0]) for r in t["done"]["rows"] if r[3] is not None}
    sample = traffic.sample_requests(seed, idx, list(schedule.values()),
                                     finished, int(spec["sample_requests"]))
    params = ref.init(traffic.key_words(seed, idx), cfg)
    scores = {}
    for i in sample:
        r = schedule[i]
        toks = traffic.request_tokens(seed, idx, i, r["length"], r["bucket"],
                                      vocab)
        scores[str(i)] = ref.score(params, toks, r["length"], cfg)
    return {"scores": scores,
            "tokens": sum(schedule[i]["length"] for i in sample)}


def numbers(t: dict, ref: dict) -> dict:
    served = {int(r[0]): r[3] for r in t["done"]["rows"]}
    gap = 0.0 if ref["scores"] else None
    for i, want in ref["scores"].items():
        got = served.get(int(i))
        if got is None or not math.isfinite(got):
            gap = None
            break
        gap = max(gap, abs(got - want) / abs(want))
    return {"score_gap": gap, "unanswered": float(counts(t)["failed"])}


def counts(t: dict) -> dict:
    due = int(t["done"]["requests_due"])
    answered = sum(1 for r in t["done"]["rows"] if r[3] is not None)
    return {"attempted": due, "failed": due - answered}


def control(ref, spec: dict, t: dict, quant: str) -> dict:
    """In a process that owns the chip: what the comparison reads when the
    reference, computed in the precision below the configuration's, answers
    the sampled requests in the program's place; and when an answer is
    altered where it is produced (the last real token left out of it)."""
    import readlib
    import traffic

    cfg, seed, idx = spec["config"], int(spec["seed"]), t["index"]
    vocab = readlib.sizes(cfg)["vocab"]
    schedule = _schedule(spec, t)
    sample = traffic.sample_requests(seed, idx, schedule,
                                     {r["idx"] for r in schedule},
                                     int(spec["sample_requests"]))
    by_idx = {r["idx"]: r for r in schedule}
    params = ref.init(traffic.key_words(seed, idx), cfg)
    truth, low, cut = {}, [], []
    for i in sample:
        r = by_idx[i]
        toks = traffic.request_tokens(seed, idx, i, r["length"], r["bucket"],
                                      vocab)
        truth[str(i)] = ref.score(params, toks, r["length"], cfg)
        low.append([i, r["due_s"], r["due_s"],
                    ref.score(params, toks, r["length"], cfg, quant)])
        cut.append([i, r["due_s"], r["due_s"],
                    ref.score(params, toks, r["length"] - 1, cfg)])

    def as_program(rows):
        return {"done": {"rows": rows, "requests_due": len(rows)}}

    return {"control": numbers(as_program(low), {"scores": truth}),
            "faults": {"answer_altered": numbers(as_program(cut),
                                                 {"scores": truth})}}


def summary(t: dict, run: dict) -> str:
    rows = [r for r in t["done"]["rows"] if r[3] is not None]
    lat = sorted(1e3 * (r[2] - r[1]) for r in rows)
    if not lat:
        return "no request answered"
    half = len(rows) // 2
    mean = lambda xs: sum(xs) / max(1, len(xs))
    first = mean([1e3 * (r[2] - r[1]) for r in rows[:half]])
    second = mean([1e3 * (r[2] - r[1]) for r in rows[half:]])
    return (f"{len(rows)} of {t['done']['requests_due']} answered; latency "
            f"ms p50 {lat[len(lat) // 2]:.1f} p95 "
            f"{lat[min(len(lat) - 1, int(0.95 * len(lat)))]:.1f} max "
            f"{lat[-1]:.1f}; mean of first half {first:.1f}, of second "
            f"{second:.1f} (a backlog that grows shows here); last answer "
            f"{rows[-1][2] - run['window_s']:+.2f}s from the window's end; "
            f"generator late ms max "
            f"{t['done']['generator_late_ms_max']:.3f} mean "
            f"{t['done']['generator_late_ms_mean']:.3f}")
