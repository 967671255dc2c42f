"""Role ``score``: an open-loop scoring tenant.

Forward only, on the logits of the configuration's binding (names no
model): one sequence a request, padded to a bucket, answered with
one float (the sequence's mean log-probability under the model: what a
reranker or a perplexity filter returns). Requests arrive on a schedule
fixed by the seed; one worker serves them first come, first served, and
each is timed from when it was DUE.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

import tenantlib as L  # noqa: E402
import traffic  # noqa: E402


def make_score(logits_fn):
    def score_fn(params, tokens, length):
        """Mean log-probability of ``tokens[0, 1:length]`` given their
        prefixes; the padding past ``length`` is masked out (and,
        attention being causal, never seen by a real position)."""
        logits = logits_fn(params, tokens)
        logp = jax.nn.log_softmax(logits[0, :-1].astype(jnp.float32))
        got = jnp.take_along_axis(logp, tokens[0, 1:, None], axis=-1)[:, 0]
        live = jnp.arange(1, tokens.shape[1]) < length
        return jnp.sum(jnp.where(live, got, 0.0)) / jnp.maximum(
            jnp.sum(live), 1).astype(jnp.float32)
    return score_fn


def main(argv) -> None:
    spec = L.load_spec(argv)
    t_start = time.monotonic()
    tenant, seed, idx = spec["tenant"], int(spec["seed"]), int(spec["index"])
    config = spec["config"]
    buckets = [int(b) for b in tenant["buckets"]]
    binding, sizes = L.model(config, max(buckets))
    schedule = traffic.request_schedule(
        seed, idx, tenant["arrivals"], tenant["lengths"], buckets,
        float(spec["seconds"]))
    # the weights made on the device from the seed, in one jitted call
    params = jax.jit(binding.init(config))(traffic.key_words(seed, idx))
    score = jax.jit(make_score(binding.logits(config)))

    def serve(req) -> float:
        toks = traffic.request_tokens(seed, idx, req["idx"], req["length"],
                                      req["bucket"], sizes["vocab"])
        return float(score(params, toks, np.int32(req["length"])))

    # warm only the shapes this schedule uses
    for b in sorted({r["bucket"] for r in schedule}):
        serve({"idx": 10**9 + b, "length": b, "bucket": b})
    L.say("WARM", {"setup_s": time.monotonic() - t_start,
                   "requests": len(schedule)})

    go = L.wait_go(spec["rundir"])
    t0, t_end = float(go["t0"]), float(go["t_end"])
    drain_end = t_end + float(go["drain_s"])
    rows, late = [], []
    for req in schedule:
        due = t0 + req["due_s"]
        now = time.monotonic()
        if now >= drain_end:
            break               # the rest stay unanswered: they count failed
        if now < due:
            L.sleep_until(due)
            late.append(time.monotonic() - due)
        try:
            value = serve(req)
        except Exception as exc:      # an operation failed: say which
            print(f"request {req['idx']} failed: {exc!r}", file=sys.stderr,
                  flush=True)
            rows.append([req["idx"], req["due_s"], None, None])
            continue
        rows.append([req["idx"], req["due_s"], time.monotonic() - t0, value])
    L.say("DONE", {"role": "score", "requests_due": len(schedule),
                   "rows": rows,
                   "generator_late_ms_max": 1e3 * max(late, default=0.0),
                   "generator_late_ms_mean":
                       1e3 * (sum(late) / len(late) if late else 0.0)})
    del params


if __name__ == "__main__":
    main(sys.argv)
