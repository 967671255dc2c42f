"""ElasticTrainer: an SPMD training loop that survives mesh resizes.

The workload half of the elastic plane (doc/elastic.md): owns the live
``(params, opt_state, step)`` and the current mesh, and exposes
:meth:`resize` — called while the gang is drain-paused — which re-lays
the state onto the new device set (``elastic/restate.py``) and rebuilds
the jitted train step for the new mesh. Steps are never dropped: the
step counter is monotonic across resizes and the loss sequence equals
an unresized run's modulo the batch schedule (asserted in
``tests/test_elastic.py``, not eyeballed).

:meth:`restater` adapts the trainer to the orchestrator's restate
callback, so an in-process gang (sim, tests) wires the data plane in
one line::

    orch.register_restater(gang_id, trainer.restater(device_bank))
"""

from __future__ import annotations

import jax

from ..parallel.mesh import (data_sharding, make_mesh, make_sharded_train_step,
                             param_sharding)
from .restate import restate_state

__all__ = ["ElasticTrainer"]


class ElasticTrainer:
    """One per training job. ``devices`` picks the initial sub-mesh
    (default: every visible device)."""

    def __init__(self, loss_fn, optimizer, init_params, devices=None):
        self.loss_fn = loss_fn
        self.optimizer = optimizer
        self.mesh = make_mesh(devices)
        self.params = jax.device_put(
            init_params, param_sharding(self.mesh, init_params))
        opt_state = optimizer.init(self.params)
        self.opt_state = jax.device_put(
            opt_state, param_sharding(self.mesh, opt_state))
        self.step_fn = make_sharded_train_step(loss_fn, optimizer,
                                               self.mesh)
        self.step = 0
        self.losses: list[float] = []
        #: [{"step", "chips", "stats"}] — one entry per resize
        self.resizes: list[dict] = []

    @property
    def num_devices(self) -> int:
        return self.mesh.devices.size

    def train_step(self, batch) -> float:
        batch = jax.device_put(batch, data_sharding(self.mesh))
        self.params, self.opt_state, loss = self.step_fn(
            self.params, self.opt_state, batch)
        self.step += 1
        loss = float(loss)
        self.losses.append(loss)
        return loss

    def resize(self, devices) -> dict:
        """Move the live state onto a mesh over *devices* — the restate
        step of an elastic resize. The state is bit-for-bit the same
        training state, re-laid; the next :meth:`train_step` runs on
        the new mesh at the same step counter."""
        devices = list(devices)
        new_mesh = make_mesh(devices)
        self.params, self.opt_state, stats = restate_state(
            self.params, self.opt_state, new_mesh)
        self.mesh = new_mesh
        self.step_fn = make_sharded_train_step(self.loss_fn,
                                               self.optimizer, new_mesh)
        rec = {"step": self.step, "chips": len(devices), "stats": stats}
        self.resizes.append(rec)
        return rec

    def restater(self, device_bank):
        """Adapt to the orchestrator's restate callback:
        ``device_bank`` maps a planned chip count to the device list to
        use (in-process stand-in for the launcher re-rendering
        ``KUBESHARE_TPU_VISIBLE_CHIPS``). Raising propagates — the orchestrator
        aborts the resize back to the old mesh."""

        def _restate(plan: dict) -> None:
            self.resize(device_bank(len(plan["to_chips"])))

        return _restate
