"""Role ``train``: a closed-loop training tenant.

Plain JAX: the loss of the configuration's binding (its kernels under the
binding's own scopes) and the program's fused Adam under ``bench_opt``,
one ``jax.jit``-ed step. Set-up builds ONE step object with its state,
drives it through its first three steps (the ones the reference follows)
and hands the same object to the window. Names no model.
"""

from __future__ import annotations

import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

import jax  # noqa: E402
import numpy as np  # noqa: E402
import optax  # noqa: E402

import tenantlib as L  # noqa: E402
import traffic  # noqa: E402
from kubeshare_tpu.ops.fused_adam import fused_adam  # noqa: E402

CHECK_STEPS = 3


def make_step(loss_fn, optimizer):
    def step(params, opt_state, tokens, targets):
        loss, grads = jax.value_and_grad(loss_fn)(params, (tokens, targets))
        with jax.named_scope("bench_opt"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss
    return jax.jit(step)


def main(argv) -> None:
    spec = L.load_spec(argv)
    t_start = time.monotonic()
    tenant, seed, idx = spec["tenant"], int(spec["seed"]), int(spec["index"])
    config = spec["config"]
    batch, seq = int(tenant["batch"]), int(tenant["seq_len"])
    binding, sizes = L.model(config, seq)
    init = binding.init(config)
    key = traffic.key_words(seed, idx)
    optimizer = fused_adam(float(tenant["lr"]))

    # the weights made on the device from the seed, in one jitted call
    params = jax.jit(init)(key)
    opt_state = jax.jit(optimizer.init)(params)
    step = make_step(binding.loss(config), optimizer)
    names = L.leaf_names(params)

    def feed(i):
        return traffic.token_batch(seed, idx, i, batch, seq, sizes["vocab"])

    # the first steps, through the window's own call and feed; what the
    # reference is compared with is read from the state they leave
    losses, grad_norms = [], None
    for i in range(CHECK_STEPS):
        params, opt_state, loss = step(params, opt_state, *feed(i))
        losses.append(float(loss))
        if i == 0:
            # the first gradient as the optimizer got it: mu_1 = (1-b1) g_1
            mu = np.asarray(jax.jit(L.leaf_norms)(opt_state["mu"]))
            grad_norms = (mu / (1.0 - L.ADAM_B1)).tolist()
    # the parameters' change over those steps, against the same init
    # regenerated inside the program (no second copy is kept resident)
    delta = jax.jit(lambda p, k: L.leaf_norms(jax.tree_util.tree_map(
        lambda a, b: a - b, p, init(k))))(params, key)
    delta_norms = np.asarray(delta).tolist()
    L.say("WARM", {"setup_s": time.monotonic() - t_start,
                   "leaves": names, "losses": losses,
                   "grad_norms": grad_norms, "delta_norms": delta_norms})

    go = L.wait_go(spec["rundir"])
    t0, t_end = float(go["t0"]), float(go["t_end"])
    L.sleep_until(t0)
    done_at, window_losses = [], []
    i = CHECK_STEPS
    while time.monotonic() < t_end:
        params, opt_state, loss = step(params, opt_state, *feed(i))
        window_losses.append(float(loss))   # the host read ends the step
        done_at.append(time.monotonic() - t0)
        i += 1
    in_window = [t for t in done_at if 0.0 <= t <= t_end - t0]
    bad = sum(1 for x in window_losses if not np.isfinite(x))
    L.say("DONE", {"role": "train", "steps_started": len(done_at),
                   "steps_in_window": len(in_window),
                   "tokens_per_step": batch * seq,
                   "done_at_s": done_at, "nonfinite": bad,
                   "last_loss": window_losses[-1] if window_losses else None})
    del params, opt_state


if __name__ == "__main__":
    main(sys.argv)
