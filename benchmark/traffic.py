"""The one general traffic generator: every input of a run from ``--seed``.

A mix's file holds parameters only; this module turns them into token
batches for training tenants and into an open-loop request schedule for
serving tenants. Pure numpy (no jax): the tenants, the reference check and
the tests all call it and get the same numbers for the same seed.

Every seed gets the SAME multiset of request lengths and inter-arrival
gaps, in another order (quantiles of the stated distributions, permuted by
the seed): the seed changes which request meets which moment, never how
much work a run offers.
"""

from __future__ import annotations

import math

import numpy as np

ARRIVALS = ("closed", "poisson")
LENGTHS = ("fixed", "lognormal")


def _rng(seed: int, *stream: int) -> np.random.Generator:
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    return np.random.default_rng([seed & 0xFFFFFFFF, seed >> 32,
                                  *[int(s) for s in stream]])


def key_words(seed: int, stream: int) -> np.ndarray:
    """A raw threefry key (uint32[2]) for ``jax.random``: one per
    (run seed, tenant stream). Seeds past 2**31 are fine."""
    return _rng(seed, 7, stream).integers(0, 2**32, size=2,
                                          dtype=np.uint64).astype(np.uint32)


def token_batch(seed: int, tenant: int, step: int, batch: int, seq: int,
                vocab: int):
    """``(tokens, targets)``, each ``(batch, seq)`` int32: step ``step`` of
    tenant ``tenant``. Every row of every step differs."""
    draw = _rng(seed, 11, tenant, step).integers(
        0, vocab, size=(batch, seq + 1), dtype=np.int64).astype(np.int32)
    return draw[:, :-1], draw[:, 1:]


def _bucket(length: int, buckets) -> int:
    for b in sorted(buckets):
        if length <= b:
            return int(b)
    raise ValueError(f"length {length} exceeds the largest bucket "
                     f"{max(buckets)}")


def _normal_quantiles(n: int) -> np.ndarray:
    """Standard-normal quantiles at (i + 0.5) / n, by bisection on erf —
    numpy has no inverse CDF and scipy is not a dependency."""
    p = (np.arange(n) + 0.5) / n
    lo, hi = np.full(n, -8.0), np.full(n, 8.0)
    erf = np.vectorize(math.erf)
    for _ in range(60):
        mid = (lo + hi) / 2
        below = 0.5 * (1 + erf(mid / math.sqrt(2))) < p
        lo, hi = np.where(below, mid, lo), np.where(below, hi, mid)
    return (lo + hi) / 2


def request_lengths(n: int, lengths: dict) -> np.ndarray:
    """The multiset of ``n`` request lengths a mix offers (unpermuted)."""
    kind = lengths.get("kind")
    if kind == "fixed":
        return np.full(n, int(lengths["tokens"]), dtype=np.int64)
    if kind == "lognormal":
        z = _normal_quantiles(n)
        raw = float(lengths["median"]) * np.exp(float(lengths["sigma"]) * z)
        return np.clip(np.rint(raw), int(lengths["min"]),
                       int(lengths["max"])).astype(np.int64)
    raise ValueError(f"unknown length distribution {kind!r} "
                     f"(known: {LENGTHS})")


def request_schedule(seed: int, tenant: int, arrivals: dict, lengths: dict,
                     buckets, seconds: float) -> list[dict]:
    """Open-loop schedule of one serving tenant over ``seconds``: a list of
    ``{"idx", "due_s", "length", "bucket"}`` sorted by due time.

    Poisson arrivals at ``arrivals["rate_per_s"]``: the gaps are the
    exponential distribution's quantiles (so their sum is the window to
    within a per cent and their count is exactly rate x seconds),
    permuted by the seed."""
    kind = arrivals.get("process")
    if kind != "poisson":
        raise ValueError(f"unknown open-loop arrival process {kind!r} "
                         f"(known: {ARRIVALS})")
    rate = float(arrivals["rate_per_s"])
    n = max(1, int(round(rate * float(seconds))))
    gaps = -np.log1p(-(np.arange(n) + 0.5) / n) / rate
    gaps *= float(seconds) / (gaps.sum() + gaps.mean())   # last due < window
    rng = _rng(seed, 13, tenant)
    due = np.cumsum(rng.permutation(gaps))
    lens = rng.permutation(request_lengths(n, lengths))
    return [{"idx": i, "due_s": float(due[i]), "length": int(lens[i]),
             "bucket": _bucket(int(lens[i]), buckets)} for i in range(n)]


def request_tokens(seed: int, tenant: int, idx: int, length: int,
                   bucket: int, vocab: int) -> np.ndarray:
    """``(1, bucket)`` int32: request ``idx``'s tokens, zero-padded past
    ``length``."""
    out = np.zeros((1, bucket), np.int32)
    out[0, :length] = _rng(seed, 17, tenant, idx).integers(
        0, vocab, size=length, dtype=np.int64)
    return out


def sample_requests(seed: int, tenant: int, schedule: list[dict],
                    finished: set, k: int) -> list[int]:
    """The requests the check compares: ``k`` of the finished ones drawn
    from the seed, the longest always among them."""
    done = [r for r in schedule if r["idx"] in finished]
    if not done:
        return []
    longest = max(done, key=lambda r: (r["length"], -r["idx"]))["idx"]
    rest = [r["idx"] for r in done if r["idx"] != longest]
    pick = _rng(seed, 19, tenant).permutation(len(rest))[:max(0, k - 1)]
    return sorted([longest] + [rest[i] for i in pick])
