"""Program-boundary slicing: yield a preempted hold *between*
executes, never mid-program (doc/isolation-wire.md).

The isolation proxy already brackets every execute with
``execute_begin``/``execute_end`` (the ledger's ``granted-active``
hooks). :class:`BoundarySlicer` rides those brackets to guarantee the
safety property the bench asserts: ``should_yield`` answers True only
when the session is *not* inside an execute, so a multi-step hold (a
token spans several executes) slices at program boundaries. The yield
itself is the proxy's existing ``renew`` — an atomic release +
re-request that keeps stride shares intact — so the wire stays
byte-for-byte for peers that never negotiated the ``preempt`` feature.

``stats()["mid_execute_yields"]`` counts yields recorded while an
execute was in flight. It is zero by construction; the preempt bench
asserts it stays zero.
"""

from __future__ import annotations

import threading


class BoundarySlicer:
    """Per-process yield bookkeeping over a scheduler facade that may
    expose ``preempted(name) -> bool`` (absent = slicing disabled)."""

    def __init__(self, scheduler=None):
        self.scheduler = scheduler
        self._lock = threading.Lock()
        self._in_execute: dict[str, int] = {}
        self._stats = {"checks": 0, "yields": 0, "mid_execute_yields": 0}

    # -- execute brackets (mirror the proxy's ledger hooks) -----------

    def execute_begin(self, name: str) -> None:
        with self._lock:
            self._in_execute[name] = self._in_execute.get(name, 0) + 1

    def execute_end(self, name: str) -> None:
        with self._lock:
            n = self._in_execute.get(name, 0) - 1
            if n > 0:
                self._in_execute[name] = n
            else:
                self._in_execute.pop(name, None)

    # -- the boundary check -------------------------------------------

    def should_yield(self, name: str) -> bool:
        """True when *name* is marked preempted AND no execute is in
        flight — the only moment a slice is allowed."""
        preempted = getattr(self.scheduler, "preempted", None)
        if preempted is None:
            return False
        with self._lock:
            self._stats["checks"] += 1
            if self._in_execute.get(name, 0) > 0:
                return False
        try:
            return bool(preempted(name))
        except Exception:
            return False

    def note_yield(self, name: str) -> None:
        """Record that the proxy yielded *name*'s token. A yield while
        an execute is in flight is a protocol violation and is counted
        so the bench can assert it never happens."""
        with self._lock:
            self._stats["yields"] += 1
            if self._in_execute.get(name, 0) > 0:
                self._stats["mid_execute_yields"] += 1

    def stats(self) -> dict:
        with self._lock:
            return dict(self._stats)
