"""What decides ``correct`` for a ``train`` tenant.

The reference follows the trainer's first steps from the seed. Compared:
each step's loss; the norm of the first gradient as the optimizer got it;
the norm of the parameters' change over those steps. The two norms are
taken by the worst leaf: the gap between the program's norm and the
reference's, against the reference's norm of that leaf or of the median
leaf, whichever is larger. Leaves whose reference gradient is under a
thousandth of the median leaf's (a key's bias under softmax, say) move
under Adam by round-off alone and are left out of the change.
"""

from __future__ import annotations

import math
import statistics

NEGLIGIBLE_GRADIENT = 1e-3      # of the median leaf's norm


def reference(ref, spec: dict, t: dict) -> dict:
    """In the check's process: the reference's readings for tenant ``t``."""
    import readlib
    import traffic

    cfg, entry, seed = spec["config"], t["entry"], int(spec["seed"])
    vocab = readlib.sizes(cfg)["vocab"]
    batches = [traffic.token_batch(seed, t["index"], i, int(entry["batch"]),
                                   int(entry["seq_len"]), vocab)
               for i in range(len(t["warm"]["losses"]))]
    return ref.train_readings(traffic.key_words(seed, t["index"]), cfg,
                              batches, float(entry["lr"]))


def _finite(x):
    return x if x is not None and math.isfinite(x) else None


def _worst_leaf_gap(prog: dict, ref: dict, skip=()) -> float | None:
    median = statistics.median(ref.values())
    worst = 0.0
    for name, r in ref.items():
        if name in skip:
            continue
        p = prog.get(name)
        if p is None or not math.isfinite(p):
            return None
        worst = max(worst, abs(p - r) / max(r, median, 1e-30))
    return worst


def numbers(t: dict, ref: dict) -> dict:
    """Pure arithmetic on what the tenant recorded and the reference read."""
    warm = t["warm"]
    names = warm["leaves"]
    if sorted(names) != sorted(ref["grad_norms"]):
        return {"loss_gap": None, "grad_norm_gap": None,
                "update_norm_gap": None}
    loss_gap = max((abs(a - b) / abs(b) if math.isfinite(a) else math.inf)
                   for a, b in zip(warm["losses"], ref["losses"]))
    grads = dict(zip(names, warm["grad_norms"]))
    deltas = dict(zip(names, warm["delta_norms"]))
    floor = NEGLIGIBLE_GRADIENT * statistics.median(
        ref["grad_norms"].values())
    still = {n for n, g in ref["grad_norms"].items() if g < floor}
    return {"loss_gap": _finite(loss_gap),
            "grad_norm_gap": _finite(_worst_leaf_gap(grads,
                                                     ref["grad_norms"])),
            "update_norm_gap": _finite(_worst_leaf_gap(
                deltas, ref["delta_norms"], skip=still))}


def counts(t: dict) -> dict:
    return {"attempted": int(t["done"]["steps_started"]),
            "failed": int(t["done"]["nonfinite"])}


def control(ref, spec: dict, t: dict, quant: str) -> dict:
    """In a process that owns the chip: what the comparison reads when the
    reference stands in the program's place (a) computed in the precision
    below the configuration's and (b) with each fault a training cell can
    have planted in it. Sets the upper readings of the limits."""
    import readlib
    import traffic

    cfg, entry, seed = spec["config"], t["entry"], int(spec["seed"])
    vocab = readlib.sizes(cfg)["vocab"]
    batches = [traffic.token_batch(seed, t["index"], i, int(entry["batch"]),
                                   int(entry["seq_len"]), vocab)
               for i in range(3)]
    key, lr = traffic.key_words(seed, t["index"]), float(entry["lr"])
    truth = ref.train_readings(key, cfg, batches, lr)

    def as_program(readings):
        names = list(readings["grad_norms"])
        return {"warm": {"leaves": names, "losses": readings["losses"],
                         "grad_norms": [readings["grad_norms"][n]
                                        for n in names],
                         "delta_norms": [readings["delta_norms"][n]
                                         for n in names]}}

    half = slice(0, max(1, int(entry["batch"]) // 2))
    return {
        "control": numbers(as_program(ref.train_readings(
            key, cfg, batches, lr, quant=quant)), truth),
        "faults": {
            "half_batch": numbers(as_program(ref.train_readings(
                key, cfg, batches, lr, rows=half)), truth),
            "state_unchanged": numbers(as_program(ref.train_readings(
                key, cfg, batches[:1], lr, keep_state=True)),
                dict(truth, losses=truth["losses"][:1])),
        }}


def summary(t: dict, run: dict) -> str:
    done = t["done"]["done_at_s"]
    steps = t["done"]["steps_in_window"]
    per = 1e3 * run["window_s"] / steps if steps else float("nan")
    return (f"{steps} steps in the window ({per:.1f} ms of wall a step), "
            f"{len(done)} started, last loss {t['done']['last_loss']}")
