"""Operations and bytes that no model family owns, and the roofline: the
fused Adam's need and the least time a need takes on a chip. What depends
on the model's family is ``counts/<family>.py``, named by the
configuration's ``counts``. Kept with the benchmark so that no PR that
claims a gain can change them.
"""

from __future__ import annotations


def adam(params: int) -> dict:
    """One Adam step over ``params`` float32 parameters: four arrays read
    (p, g, m, v), three written; about a dozen operations a parameter."""
    return {"flops": 12.0 * params, "bytes": 28.0 * params}


def least_seconds(need: dict, peaks: dict) -> tuple[float, str]:
    """The roofline: the larger of operations over peak FLOP/s and bytes
    over peak bytes/s, and which of the two binds."""
    by_flops = need["flops"] / peaks["bf16_flops_per_s"]
    by_bytes = need["bytes"] / peaks["hbm_bytes_per_s"]
    return (by_flops, "compute") if by_flops >= by_bytes else (by_bytes,
                                                               "memory")
