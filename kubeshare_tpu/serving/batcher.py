"""Continuous batcher: many tenants' requests, one shared execution.

The batcher repeatedly asks the front door for a batch and ships it as
a single ``execute`` on one servable — typically a fractionally-held
proxy session (ParvaGPU's premise: inference under sharing pays for
itself only when requests coalesce).  Two knobs bound the tradeoff:

- ``max_batch`` — rows per shared execution (capped by the servable's
  compiled batch size; shorter batches are zero-padded);
- ``max_wait_s`` — a lone request still ships within this bound, so
  tail latency is ``queue wait + max_wait + execute``, never "until
  the batch happens to fill".

``step(now)`` is explicitly clocked and synchronous — the sim drives
it in virtual time, tests drive it with a manual clock, and
``serve_loop()`` wraps it in a wall-clock pump thread for live
serving (scripts/bench_serving.py).
"""

from __future__ import annotations

import threading
import time
from typing import Callable, List, Optional

import numpy as np

from ..obs import flight as obs_flight
from ..obs import trace as obs_trace
from .frontdoor import FrontDoor, ServeRequest


class LocalServable:
    """In-process servable: ``fn(x[batch, ...]) -> y[batch, ...]``."""

    def __init__(self, fn: Callable[[np.ndarray], np.ndarray],
                 batch_size: int = 8):
        self.fn = fn
        self.batch_size = int(batch_size)

    def execute(self, x: np.ndarray) -> np.ndarray:
        return np.asarray(self.fn(x))

    def close(self) -> None:
        pass


class ProxyServable:
    """The tinymlp model served through a fractional proxy session.

    Parameters are staged once as remote buffers; every batch is one
    ``execute`` on the compiled program — so the serving plane rides
    the full isolation stack (token grants, HBM charging, resume
    tokens) for free.  The padded input shape is fixed at compile
    time; :class:`ContinuousBatcher` pads rows up to ``batch_size``.
    """

    def __init__(self, client, seed: int = 0):
        import jax
        from ..models import tinymlp
        self.client = client
        self.batch_size = tinymlp.BATCH_SIZE
        self.features = tinymlp.FEATURES
        params = tinymlp.init(jax.random.PRNGKey(seed))
        self._params = client.put_tree(params)
        example_x = np.zeros((self.batch_size, self.features),
                             dtype=np.float32)
        self._exe = client.compile(tinymlp.apply, self._params, example_x)

    def execute(self, x: np.ndarray) -> np.ndarray:
        out = self._exe(self._params, np.asarray(x, dtype=np.float32))
        y = np.asarray(self.client.get(out))
        # outputs are HBM-charged device buffers: the free rides on the
        # next batch's execute (or goes out at close)
        self.client.free_later(out)
        return y

    def close(self) -> None:
        try:
            self.client.close()
        except Exception:
            pass


class ContinuousBatcher:
    """Pulls compatible requests from a FrontDoor into shared executes."""

    def __init__(self, frontdoor: FrontDoor, servable,
                 max_batch: Optional[int] = None,
                 max_wait_s: float = 0.005,
                 clock: Optional[Callable[[], float]] = None,
                 recorder=None):
        self.frontdoor = frontdoor
        self.servable = servable
        cap = getattr(servable, "batch_size", max_batch or 8)
        self.max_batch = min(int(max_batch), cap) if max_batch else cap
        self.max_wait_s = float(max_wait_s)
        self.clock = clock or frontdoor.clock
        self.recorder = (recorder if recorder is not None
                         else obs_flight.default_recorder())
        self.executions = 0
        self.rows_served = 0
        frontdoor.batcher = self

    # ---------------------------------------------------------- stepping

    def ready(self, now: Optional[float] = None) -> bool:
        """Ship now? — batch full, or the oldest request aged out."""
        if now is None:
            now = self.clock()
        if self.frontdoor.queued_rows() >= self.max_batch:
            return True
        oldest = self.frontdoor.oldest_submitted_at()
        # Same expression as next_deadline() — `now - oldest >= wait`
        # disagrees with it under float rounding and a virtual-time
        # driver waking exactly at the deadline would spin forever.
        return (oldest is not None
                and now >= oldest + self.max_wait_s)

    def next_deadline(self) -> Optional[float]:
        """When the oldest queued request's max-wait expires (sim hook)."""
        oldest = self.frontdoor.oldest_submitted_at()
        if oldest is None:
            return None
        return oldest + self.max_wait_s

    def step(self, now: Optional[float] = None,
             force: bool = False) -> int:
        """Ship one batch if due; returns requests completed."""
        if now is None:
            now = self.clock()
        if not force and not self.ready(now):
            return 0
        batch = self.frontdoor.pop_batch(self.max_batch)
        if not batch:
            return 0
        return self._execute(batch, now)

    def flush(self, now: Optional[float] = None) -> int:
        """Drain everything queued, ignoring max-wait (shutdown path)."""
        done = 0
        while True:
            n = self.step(now, force=True)
            if not n:
                return done
            done += n

    # --------------------------------------------------------- execution

    def _execute(self, batch: List[ServeRequest], now: float) -> int:
        fd = self.frontdoor
        rows = sum(r.rows for r in batch)
        x = np.concatenate([r.x for r in batch], axis=0)
        pad = self.servable.batch_size - x.shape[0]
        if pad > 0:
            x = np.concatenate(
                [x, np.zeros((pad,) + x.shape[1:], dtype=x.dtype)], axis=0)
        trace_id = batch[0].trace_id or obs_trace.new_trace_id()
        tracer = obs_trace.get_tracer()
        try:
            with tracer.span("serve-batch", trace_id, rows=rows,
                             requests=len(batch),
                             tenants=len({r.tenant for r in batch})):
                y = self.servable.execute(x)
        except Exception as exc:
            # No admitted request is ever silently dropped: a failed
            # execution fails every rider loudly and is accounted.
            for r in batch:
                r._fail(exc)
                fd.note_delivered(r, failed=True)
                fd.accounting.note_failed(r.tenant, r.tpu_class)
            self.recorder.note("serving", "batch-failed",
                               requests=len(batch), error=repr(exc))
            return len(batch)
        self.executions += 1
        self.rows_served += rows
        fd.accounting.note_batch(rows)
        off = 0
        for r in batch:
            out = np.asarray(y[off:off + r.rows])
            off += r.rows
            r._complete(out, now)
            fd.note_delivered(r)
            latency = max(0.0, now - r.submitted_at)
            fd.accounting.note_completed(
                r.tenant, r.tpu_class, latency, r.rows,
                int(r.x.nbytes), int(out.nbytes), trace_id=r.trace_id)
            if fd.slo is not None:
                fd.slo.record(r.tenant, "serve", value_s=latency,
                              now=now, trace_id=r.trace_id)
                fd.slo.record(r.tenant, "serve-availability", ok=True,
                              now=now, trace_id=r.trace_id)
        return len(batch)

    # --------------------------------------------------------- live pump

    def serve_loop(self, stop: threading.Event,
                   idle_wait_s: float = 0.001,
                   drain_on_stop: bool = True) -> None:
        """Wall-clock pump: run in a thread for live serving.

        On ``stop`` the loop drains by default: every request already
        admitted is shipped (ignoring max-wait) before the pump exits,
        so a shutdown never strands riders whose futures would
        otherwise hang — the serving half of graceful drain
        (doc/serving.md; chaos scenarios that bounce the process
        depend on it).
        """
        fd = self.frontdoor
        while not stop.is_set():
            if self.step():
                continue
            with fd.wakeup:  # wakeup wraps fd.lock — inspect inline
                queued = any(t.queue for t in fd._tenants.values())
                if not queued:
                    fd.wakeup.wait(timeout=0.05)
                    continue
            deadline = self.next_deadline()
            delay = idle_wait_s
            if deadline is not None:
                delay = min(max(deadline - time.monotonic(), 0.0),
                            0.05) or idle_wait_s
            stop.wait(delay)
        if drain_on_stop:
            self.flush()

    def describe(self) -> dict:
        return {
            "max_batch": self.max_batch,
            "max_wait_s": self.max_wait_s,
            "executions": self.executions,
            "rows_served": self.rows_served,
        }
