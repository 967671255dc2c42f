#!/usr/bin/env python
"""North-star benchmark: fractional sharing overhead on one chip.

Measures the target stated in BASELINE.md (derived from the reference's
eval workloads, ``test/mnist/mnist1.yaml:15``):

1. **exclusive** — the mnist train step run directly on the chip
   (isolated baseline, no framework in the path);
2. **co-located** — two clients, each ``tpu_request=0.5``, running the
   same training loop concurrently *through* the isolation runtime
   (:class:`~kubeshare_tpu.isolation.proxy.ChipProxy` + token scheduler
   with Gemini-parity quota/window, ``launcher.py:75-80``).

Prints ONE JSON line::

    {"metric": "colocated_2x0.5_aggregate_ratio", "value": <aggregate
     co-located steps/s ÷ exclusive steps/s>, "unit": "fraction",
     "vs_baseline": <value ÷ 0.90 target>, ...detail keys...}

North star: value ≥ 0.90 and per-client device-time share within 5% of
the 0.5 request. The co-located phase must span ≥ 3 accounting windows
(WINDOW_MS = 10 s) for the shares to converge; shares are read from the
proxy's token-gated device-time accounting (``exec_ms_total``), which
excludes token wait and compile time.

Runs in ONE process, which owns the chip for the whole run (a chip
belongs to one process at a time): the exclusive phases, then the proxy
and its two client threads. There is no fallback: ``main`` exits
non-zero, saying why, when ``jax.devices()[0]`` is not a TPU — a CPU
number is never printed under this metric's name. (``run_bench`` itself
stays callable on CPU at toy size for ``tests/test_bench.py``.)
"""

from __future__ import annotations

import argparse
import json
import sys
import threading
import time

import numpy as np


def _mark(msg: str) -> None:
    """Phase marker on stderr: says which phase a run died in."""
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", file=sys.stderr,
          flush=True)


def _model(name: str):
    from kubeshare_tpu.models import get_model
    return get_model({"tiny": "tinymlp"}.get(name, name))


def _exclusive_steps_per_sec(duration: float,
                             fused_chunk: int = 0,
                             model: str = "mnist") -> float:
    """Isolated baseline: timed steps directly on the default device.

    ``fused_chunk=0`` is the naive per-step loop a user writes;
    ``fused_chunk=N`` fuses N steps per dispatch exactly like the proxy's
    hot path — the STRONGER baseline the co-located ratio is judged
    against (judging only the naive loop would let the framework's own
    dispatch amortization inflate the ratio past what sharing earns).
    """
    import jax
    import optax

    from kubeshare_tpu.models.common import make_train_step

    mod = _model(model)
    key = jax.random.PRNGKey(0)
    pkey, bkey = jax.random.split(key)
    params = mod.init(pkey)
    optimizer = optax.adam(1e-3)
    opt_state = optimizer.init(params)
    step = make_train_step(mod.loss_fn, optimizer)
    batch = mod.batch_fn(bkey)

    if fused_chunk:
        def chunk(params, opt_state, batch):
            def body(_, c):
                p, o, _l = c
                return step(p, o, batch)
            return jax.lax.fori_loop(0, fused_chunk, body,
                                     step(params, opt_state, batch))
        run = jax.jit(chunk)
        per_call = fused_chunk
    else:
        run = step
        per_call = 1

    for _ in range(3):  # absorb compile
        params, opt_state, loss = run(params, opt_state, batch)
    float(loss)

    steps = 0
    start = time.perf_counter()
    deadline = start + duration
    while time.perf_counter() < deadline:
        params, opt_state, loss = run(params, opt_state, batch)
        # float(loss) is a host read: a completion barrier, so the loop
        # counts finished steps, not dispatched ones
        float(loss)
        steps += per_call
    return steps / (time.perf_counter() - start)


def _proxied_trainer(proxy_port: int, name: str, request: float, limit: float,
                     barrier: threading.Barrier, duration: float,
                     chunk: int, results: dict, settle: float = 0.0,
                     model: str = "mnist") -> None:
    """One co-located client: training through the proxy's fused-loop
    path (``chunk`` steps per dispatch = one token-gated XLA burst)."""
    import jax
    import optax

    from kubeshare_tpu.isolation.client import ProxyClient

    mod = _model(model)
    optimizer = optax.adam(1e-3)

    def train_chunk(carry, batch):
        params, opt_state = carry
        loss, grads = jax.value_and_grad(mod.loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return (params, opt_state), loss

    # Build the initial state ENTIRELY on the host backend: client threads
    # stand for tenant processes, which never touch the chip — only the
    # proxy drives it. Ops run where their operands live, so the PRNGKey
    # itself must be created under the cpu default_device too.
    with jax.default_device(jax.local_devices(backend="cpu")[0]):
        key = jax.random.PRNGKey(hash(name) % (1 << 31))
        pkey, bkey = jax.random.split(key)
        host_params = mod.init(pkey)
        host_opt = optimizer.init(host_params)
        host_batch = mod.batch_fn(bkey)

    with ProxyClient("127.0.0.1", proxy_port, name, request, limit) as c:
        carry = (c.put_tree(jax.tree_util.tree_map(np.asarray, host_params)),
                 c.put_tree(jax.tree_util.tree_map(np.asarray, host_opt)))
        batch = c.put_tree(tuple(np.asarray(b) for b in host_batch))
        loop = c.compile_loop(train_chunk, carry, batch)

        # Absorb the proxy-side compile AND seed the burst cost model: the
        # first dispatch is clamped to 1 step by design, the second is a
        # 2-step probe, the third runs a converged time-capped burst.
        for _ in range(3):
            carry, loss = loop(chunk, carry, batch)
            c.free(loss)

        barrier.wait()
        # Settle phase: run unmeasured until the token alternation reaches
        # steady state (the first grants after the barrier are a transient —
        # whoever wins the initial race runs a full quota head start).
        settle_deadline = time.perf_counter() + settle
        while time.perf_counter() < settle_deadline:
            carry, loss = loop.chain(chunk, carry, batch)
            c.free(loss)

        used0 = c.usage()["exec_ms_total"]
        steps = 0
        start = time.perf_counter()
        deadline = start + duration
        while time.perf_counter() < deadline:
            # server-side burst chaining: the proxy re-feeds the carry
            # across token-gated bursts, so the client round trip (chip
            # idle time whenever the co-tenant is token-blocked) is paid
            # once per CHAIN, not once per burst
            carry, loss = loop.chain(chunk * 8, carry, batch)
            c.free(loss)
            steps += loop.last_n  # the proxy reports real steps run
        elapsed = time.perf_counter() - start
        results[name] = {
            "steps": steps,
            "steps_per_sec": steps / elapsed,
            "elapsed_s": elapsed,
            # token-gated device time (excludes wait + compile) — the same
            # quantity the scheduler's share accounting is fed with
            "exec_ms": c.usage()["exec_ms_total"] - used0,
            # the burst controller's converged clamp — steady-state
            # evidence for the latency-aware sizing (_cap_repeat)
            "last_burst": loop.last_burst,
        }


def run_bench(exclusive_s: float, colocated_s: float, chunk: int = 100,
              settle_s: float | None = None,
              exclusive_fused: bool | None = None,
              window_ms: float | None = None,
              model: str = "mnist") -> dict:
    import jax

    from kubeshare_tpu.utils.compilecache import enable_compile_cache
    enable_compile_cache()

    from kubeshare_tpu.constants import BASE_QUOTA_MS, MIN_QUOTA_MS, WINDOW_MS
    from kubeshare_tpu.isolation.proxy import ChipProxy
    from kubeshare_tpu.isolation.tokensched import TokenScheduler

    # The accounting window defaults to Gemini parity (10 s).
    if window_ms is None:
        window_ms = WINDOW_MS
    _mark("initializing backend")
    platform = jax.devices()[0].platform
    _mark(f"backend up: {platform}; exclusive plain phase")
    exclusive_plain = _exclusive_steps_per_sec(exclusive_s, model=model)
    _mark(f"exclusive plain: {exclusive_plain:.2f} steps/s")
    # The fused baseline costs an extra XLA compile (tens of seconds on
    # the CPU test backend) — auto-skipped only for toy-duration runs;
    # any run whose ratio is REPORTED must pay it, or the co-located
    # side's dispatch amortization inflates the ratio.
    if exclusive_fused is None:
        exclusive_fused = exclusive_s >= 2.0
    exclusive_fused_sps = (_exclusive_steps_per_sec(exclusive_s,
                                                    fused_chunk=chunk,
                                                    model=model)
                           if exclusive_fused else 0.0)
    _mark(f"exclusive fused: {exclusive_fused_sps:.2f} steps/s")
    exclusive_sps = max(exclusive_plain, exclusive_fused_sps)
    if settle_s is None:
        # Skip the startup transient, but never settle longer than we
        # measure (toy-duration test runs).
        settle_s = min(window_ms / 1000.0, colocated_s / 3.0)

    proxy = ChipProxy(scheduler=TokenScheduler(window_ms, BASE_QUOTA_MS,
                                               MIN_QUOTA_MS))
    proxy.serve()
    _mark(f"proxy serving on {proxy.port}; starting co-located clients")
    try:
        barrier = threading.Barrier(2)
        results: dict = {}
        threads = [
            threading.Thread(
                target=_proxied_trainer,
                args=(proxy.port, name, 0.5, 1.0, barrier, colocated_s,
                      chunk, results, settle_s, model),
                name=f"bench-{name}")
            for name in ("client-a", "client-b")
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        _mark("co-located clients joined")
    finally:
        proxy.close()

    if len(results) != 2:
        raise RuntimeError(f"co-located clients failed: {sorted(results)}")

    a, b = (results[n] for n in ("client-a", "client-b"))
    aggregate_sps = a["steps_per_sec"] + b["steps_per_sec"]
    ratio = aggregate_sps / exclusive_sps if exclusive_sps else 0.0
    total_exec = a["exec_ms"] + b["exec_ms"]
    share_a = a["exec_ms"] / total_exec if total_exec else 0.0
    share_error_pct = abs(share_a - 0.5) / 0.5 * 100.0

    result = {
        "metric": "colocated_2x0.5_aggregate_ratio",
        "value": round(ratio, 4),
        "unit": "fraction",
        "vs_baseline": round(ratio / 0.90, 4),
        "exclusive_steps_per_sec": round(exclusive_sps, 2),
        "exclusive_plain_steps_per_sec": round(exclusive_plain, 2),
        "exclusive_fused_steps_per_sec": round(exclusive_fused_sps, 2),
        "colocated_aggregate_steps_per_sec": round(aggregate_sps, 2),
        "client_steps_per_sec": [round(a["steps_per_sec"], 2),
                                 round(b["steps_per_sec"], 2)],
        "share_error_pct": round(share_error_pct, 2),
        "colocated_seconds": round(colocated_s, 1),
        "window_ms": round(window_ms, 0),
        "windows_measured": round(colocated_s * 1000.0 / window_ms, 1),
        "steady_state_burst": [a["last_burst"], b["last_burst"]],
        "model": model,
        "platform": platform,
        "device_kind": jax.devices()[0].device_kind,
        "device_count": len(jax.devices()),
    }
    return result


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="bench.py", description=__doc__)
    parser.add_argument("--exclusive-seconds", type=float, default=5.0)
    # ≥ 3 accounting windows (WINDOW_MS = 10 s): shares cannot converge in
    # less — the round-2 default of 8 s was shorter than ONE window.
    parser.add_argument("--colocated-seconds", type=float, default=35.0)
    # On the chip an mnist step is sub-microsecond (the MXU eats the tiny
    # model), so a burst must fuse tens of thousands of steps before the
    # ~0.3 ms dispatch+gate cost stops dominating; device time per burst
    # stays a few ms — far under the 300 ms quantum, so preemption
    # granularity is unaffected. CPU tests pass a small chunk explicitly.
    parser.add_argument("--chunk", type=int, default=20000,
                        help="train steps fused per dispatch (one token burst)")
    parser.add_argument("--model", choices=("mnist", "tiny"), default="mnist",
                        help="workload model; 'tiny' is a microsecond-step "
                             "MLP that drives the burst controller hard")
    args = parser.parse_args(argv)

    import jax
    platform = jax.devices()[0].platform
    if platform != "tpu":
        print(f"bench.py: jax.devices()[0] is {jax.devices()[0]} (platform "
              f"{platform!r}), not a TPU — nothing is measured off the chip "
              "and there is no CPU fallback", file=sys.stderr)
        return 1
    result = run_bench(args.exclusive_seconds, args.colocated_seconds,
                       args.chunk, model=args.model)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
