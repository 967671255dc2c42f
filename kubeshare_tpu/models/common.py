"""Shared training machinery for the workload models.

The reference's eval workloads are external torch images driven by pod
manifests (``test/mnist/mnist1.yaml:15`` etc.); here each model module
exposes a functional ``(init, loss_fn)`` pair and this module turns it into
a jitted SGD/Adam train step plus a timed loop. The loop takes an optional
``gate`` callable — the isolation runtime's client-side execution gate
(≙ the reference's libgemhook token round-trip before each kernel burst)
plugs in there without the model knowing.
"""

from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Callable

import jax
import jax.numpy as jnp
import optax

from ..utils.logger import get_logger

log = get_logger("models")


@dataclass
class TrainResult:
    steps: int
    seconds: float
    final_loss: float

    @property
    def steps_per_sec(self) -> float:
        return self.steps / self.seconds if self.seconds > 0 else 0.0


def make_train_step(loss_fn: Callable, optimizer: optax.GradientTransformation,
                    constrain_params: Callable | None = None,
                    constrain_batch: Callable | None = None):
    """``loss_fn(params, batch) -> scalar`` → jitted
    ``step(params, opt_state, batch) -> (params, opt_state, loss)``.

    The optional ``constrain_*`` hooks apply sharding constraints on the way
    in and out — the multi-chip path (``parallel.mesh``) plugs its mesh
    layouts in here so single-chip and sharded benchmarks share one step
    body.
    """

    @jax.jit
    def step(params, opt_state, batch):
        if constrain_params is not None:
            params = constrain_params(params)
        if constrain_batch is not None:
            batch = constrain_batch(batch)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        updates, opt_state = optimizer.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        if constrain_params is not None:
            params = constrain_params(params)
        return params, opt_state, loss

    return step


def synthetic_image_batch(key, batch_size: int, hw: int, channels: int,
                          classes: int) -> tuple[jax.Array, jax.Array]:
    xkey, ykey = jax.random.split(key)
    x = jax.random.normal(xkey, (batch_size, hw, hw, channels), jnp.float32)
    y = jax.random.randint(ykey, (batch_size,), 0, classes)
    return x, y


def synthetic_token_batch(key, batch_size: int, seq_len: int,
                          vocab: int) -> tuple[jax.Array, jax.Array]:
    tokens = jax.random.randint(key, (batch_size, seq_len + 1), 0, vocab)
    return tokens[:, :-1], tokens[:, 1:]


def run_training(init_fn: Callable, loss_fn: Callable, batch_fn: Callable,
                 steps: int, learning_rate: float = 1e-3, seed: int = 0,
                 warmup: int = 2, gate: Callable | None = None,
                 optimizer: optax.GradientTransformation | None = None,
                 checkpoint: str = "",
                 checkpoint_every: int = 0,
                 profile_dir: str = "",
                 mesh=None, mesh_hooks: dict | None = None) -> TrainResult:
    """Train for ``steps`` timed steps on one fixed synthetic batch.

    ``warmup`` untimed steps absorb compile time; each timed step blocks on
    device completion so steps/sec reflects real chip time. ``gate()`` (if
    given) runs before every step — the isolation client's token round-trip.

    ``checkpoint`` (a directory path) enables crash-resume: an existing
    checkpoint there is restored before training (its step count reduces
    the remaining work) and state is saved every ``checkpoint_every``
    steps (default: once at the end). A restarted pod with the same args
    continues the same trajectory — the restartable-filler-work premise
    of the opportunistic tier.
    """
    if mesh is None and jax.process_count() > 1:
        # Gang member (the attach shim already joined jax.distributed):
        # train over the WHOLE gang's chips, not just the local ones.
        from ..parallel.runner import gang_mesh
        mesh = gang_mesh()

    if checkpoint and jax.process_count() > 1:
        # Orbax multihost: every member writes its shards into the SAME
        # directory and the commit is barrier'd. Verify the path really
        # is shared BEFORE touching it — a pod-local path would produce
        # an unrestorable checkpoint (or a restore deadlock when only
        # some ranks find the directory).
        from .checkpoint import verify_shared_path
        verify_shared_path(checkpoint)

    key = jax.random.PRNGKey(seed)
    pkey, bkey = jax.random.split(key)
    params = init_fn(pkey)
    optimizer = optimizer or optax.adam(learning_rate)
    batch = batch_fn(bkey)
    if mesh is not None:
        from ..parallel.mesh import (data_sharding, make_sharded_train_step,
                                     param_sharding)
        # Model-provided mesh hooks (``mesh_hooks``): "loss" swaps in a
        # mesh-aware loss (e.g. the transformer's ring attention over an
        # sp axis) and "batch_sharding" the batch layout (token batches
        # split their sequence axis too). Defaults serve every model.
        hooks = mesh_hooks or {}
        if "loss" in hooks:
            loss_fn = hooks["loss"](mesh) or loss_fn
        batch_sharding = (hooks.get("batch_sharding") or data_sharding)(mesh)
        step = make_sharded_train_step(loss_fn, optimizer, mesh,
                                       batch_sharding=batch_sharding)
        params = jax.device_put(params, param_sharding(mesh, params))
        batch = jax.device_put(batch, batch_sharding)
    else:
        step = make_train_step(loss_fn, optimizer)
    opt_state = optimizer.init(params)
    if mesh is not None:
        # Explicit mesh placement for the optimizer state too: adam's
        # scalars (count) are otherwise born uncommitted on one device,
        # and a gang checkpoint restore would pin them there — colliding
        # with the mesh-placed params inside the jitted step.
        opt_state = jax.device_put(opt_state,
                                   param_sharding(mesh, opt_state))

    done = 0
    if checkpoint:
        from .checkpoint import load_checkpoint, save_checkpoint
        try:
            params, opt_state, done = load_checkpoint(checkpoint, params,
                                                      opt_state)
        except FileNotFoundError:
            pass
        if done:
            # Resume continues the SAME trajectory: warmup steps would
            # apply real optimizer updates beyond the recorded step (and
            # a nothing-to-do restart would silently drift the model).
            # The first timed step absorbs the compile instead.
            warmup = 0

    loss = jnp.zeros(())
    for _ in range(warmup):
        params, opt_state, loss = step(params, opt_state, batch)
    float(loss)

    import contextlib
    # Profile ONLY the timed loop: init/compile/warmup/checkpoint events
    # would otherwise dwarf the steady-state steps in the trace.
    trace_ctx = (jax.profiler.trace(profile_dir) if profile_dir
                 else contextlib.nullcontext())
    # In-loop saves overlap IO with training (AsyncCheckpointWriter):
    # the step stall shrinks to the state snapshot, the write flushes
    # while the next steps run, and close() below guarantees the final
    # state is committed before run_training returns.
    writer_ctx = contextlib.nullcontext()
    if checkpoint and checkpoint_every:
        from .checkpoint import AsyncCheckpointWriter
        writer_ctx = AsyncCheckpointWriter()
    remaining = max(0, steps - done)
    start = time.perf_counter()
    with trace_ctx, writer_ctx as writer:
        for i in range(1, remaining + 1):
            if gate is not None:
                gate()
            params, opt_state, loss = step(params, opt_state, batch)
            # Host read of the loss: a completion barrier, so the loop
            # times the step and not its dispatch.
            float(loss)
            if (checkpoint and checkpoint_every
                    and i % checkpoint_every == 0):
                writer.save(checkpoint, params, opt_state, done + i)
    # the with-block exit closed the writer: the last in-flight save is
    # flushed AND promoted before elapsed is read
    elapsed = time.perf_counter() - start
    if checkpoint and remaining and not (
            checkpoint_every and remaining % checkpoint_every == 0):
        # Final save only when the loop's last in-loop save didn't already
        # cover this exact step — a duplicate save is a full barrier'd
        # checkpoint rewrite in a gang. remaining == 0 saves nothing: the
        # on-disk state already IS this state.
        save_checkpoint(checkpoint, params, opt_state, done + remaining)
    return TrainResult(steps=remaining, seconds=elapsed,
                       final_loss=float(loss))


def main_cli(model_name: str, init_fn, loss_fn, batch_fn, argv=None,
             mesh_hooks: dict | None = None) -> TrainResult:
    """Shared ``python -m kubeshare_tpu.models.<name> --steps N`` entry."""
    import argparse

    parser = argparse.ArgumentParser(prog=f"kubeshare_tpu.models.{model_name}")
    parser.add_argument("--steps", type=int, default=50)
    parser.add_argument("--lr", type=float, default=1e-3)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--checkpoint", default="",
                        help="checkpoint dir: resume from it if present, "
                             "save into it while training")
    parser.add_argument("--checkpoint-every", type=int, default=0)
    parser.add_argument("--platform", default="",
                        help="force a JAX platform (e.g. 'cpu' for an "
                             "off-chip rehearsal); same effect as "
                             "JAX_PLATFORMS")
    parser.add_argument("--profile", default="",
                        help="capture an XLA/TPU profiler trace of the "
                             "timed loop into this directory (view with "
                             "tensorboard / xprof)")
    args = parser.parse_args(argv)

    if args.platform:
        jax.config.update("jax_platforms", args.platform)
    from ..utils.compilecache import enable_compile_cache
    enable_compile_cache()

    result = run_training(init_fn, loss_fn, batch_fn, args.steps,
                          learning_rate=args.lr, seed=args.seed,
                          checkpoint=args.checkpoint,
                          checkpoint_every=args.checkpoint_every,
                          profile_dir=args.profile,
                          mesh_hooks=mesh_hooks)
    # every result names the device it ran on (under proxy attach that is
    # this process's CPU backend: the chip is the proxy's, which reports it)
    dev = jax.devices()[0]
    log.info("ran on platform=%s kind=%r count=%d", dev.platform,
             dev.device_kind, len(jax.devices()))
    print(f"{model_name}: {result.steps} steps in {result.seconds:.2f}s "
          f"= {result.steps_per_sec:.2f} steps/s, final loss {result.final_loss:.4f}")
    return result
