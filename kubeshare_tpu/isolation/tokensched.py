"""Token scheduler: time-slices one chip between fractional clients.

Re-design of the reference's per-GPU gem-schd (native C++, CLI
``-q 300 -m 20 -w 10000`` — ``docker/kubeshare-gemini-scheduler/
launcher.py:75-80``). One exclusive *token* circulates per chip; a grant
carries a quota (ms of device time), the holder reports actual usage on
release. Scheduling = stride scheduling weighted by ``tpu_request`` with a
sliding-window ``tpu_limit`` cap (see ``native/tokensched.cpp`` header for
the algorithm statement).

Two interchangeable cores — the native C++ library (default) and a pure
Python :class:`PyTokenCore` (fallback + executable spec, cross-checked by
``tests/test_tokensched.py``) — and a blocking façade
:class:`TokenScheduler` plus a TCP server (:func:`serve`) speaking the
framed-JSON protocol that pod managers use.
"""

from __future__ import annotations

import ctypes
import threading
import time
from collections import deque
from dataclasses import dataclass, field

from ..constants import BASE_QUOTA_MS, MIN_QUOTA_MS, WINDOW_MS
from ..obs import metrics as obs_metrics
from ..obs import prof as obs_prof
from ..obs import slo as obs_slo
from ..obs.flight import default_recorder as flight_default_recorder
from ..obs.trace import phase
from ..utils.logger import get_logger
from . import protocol
from .native import load_library

log = get_logger("tokensched")

_INF = float("inf")

_OBS = obs_metrics.default_registry()
_GRANT_WAIT = _OBS.histogram(
    "kubeshare_token_grant_wait_seconds",
    "Time a client blocked between requesting the chip token and the "
    "grant, by tenant namespace and workload class.",
    labels=("chip", "namespace", "tpu_class"))
_HOLD = _OBS.histogram(
    "kubeshare_token_hold_seconds",
    "Wall time a client held the chip token before releasing it.",
    labels=("chip",))
_UTIL = _OBS.gauge(
    "kubeshare_token_utilization_ratio",
    "Per-client share of the sliding window actually consumed "
    "(window_usage / window_ms), updated at each release.",
    labels=("chip", "client"))


# --------------------------------------------------------------------------
# Pure-Python core (executable spec / fallback)
# --------------------------------------------------------------------------

@dataclass
class _PyClient:
    name: str
    request: float
    limit: float
    vtime: float = 0.0
    waiting: bool = False
    usage: list = field(default_factory=list)  # [(start_ms, end_ms)]

    def window_usage(self, now_ms: float, window_ms: float) -> float:
        lo = now_ms - window_ms
        self.usage = [(s, e) for s, e in self.usage if e > lo]
        return sum(e - max(s, lo) for s, e in self.usage)

    def eligible_at(self, now_ms: float, window_ms: float, target_ms: float) -> float:
        if self.window_usage(now_ms, window_ms) <= target_ms:
            return now_ms
        lo, hi = now_ms, now_ms + window_ms
        for _ in range(48):
            mid = (lo + hi) / 2
            wlo = mid - window_ms
            total = sum(e - max(s, wlo) for s, e in self.usage if e > wlo)
            if total <= target_ms:
                hi = mid
            else:
                lo = mid
        return hi


class PyTokenCore:
    """Same state machine as the native core, in Python."""

    def __init__(self, window_ms: float = WINDOW_MS,
                 base_quota_ms: float = BASE_QUOTA_MS,
                 min_quota_ms: float = MIN_QUOTA_MS):
        self.window_ms = window_ms
        self.base_quota_ms = base_quota_ms
        self.min_quota_ms = min_quota_ms
        self._clients: dict[str, _PyClient] = {}
        self._holder: str | None = None
        self._vnow = 0.0    # the scheduler's virtual time (see poll)
        self._closed = False

    def add_client(self, name: str, request: float, limit: float) -> None:
        if self._closed:
            raise RuntimeError("token scheduler closed")
        if request <= 0 or limit <= 0 or limit > 1 or request > limit:
            raise ValueError(f"bad request/limit: {request}/{limit}")
        if name in self._clients:
            raise ValueError(f"duplicate client {name}")
        vmin = min((c.vtime for c in self._clients.values()), default=0.0)
        self._clients[name] = _PyClient(name, request, limit, vtime=vmin)

    def remove_client(self, name: str) -> None:
        self._clients.pop(name, None)
        if self._holder == name:
            self._holder = None

    def request_token(self, name: str) -> None:
        self._clients[name].waiting = True

    def cancel_request(self, name: str) -> None:
        client = self._clients.get(name)
        if client is not None:
            client.waiting = False

    def poll(self, now_ms: float) -> tuple[str, float] | float:
        """Grant ``(name, quota_ms)`` or return the next wake time (ms,
        may be inf)."""
        if self._closed:
            # Same contract as the native core's freed-handle guard: a
            # waiter woken by close() must error out, not sleep forever.
            raise RuntimeError("token scheduler closed")
        if self._holder is not None:
            return _INF
        best: _PyClient | None = None
        best_remaining = 0.0
        next_wake = _INF
        for c in self._clients.values():
            if not c.waiting:
                continue
            # a client that comes back is owed one quantum of device time
            # at most, however long it stayed away or was capped
            c.vtime = max(c.vtime,
                          self._vnow - self.base_quota_ms / c.request)
            cap = c.limit * self.window_ms
            remaining = cap - c.window_usage(now_ms, self.window_ms)
            if remaining < self.min_quota_ms:
                next_wake = min(next_wake, c.eligible_at(
                    now_ms, self.window_ms, cap - self.min_quota_ms))
                continue
            if (best is None or c.vtime < best.vtime
                    or (c.vtime == best.vtime and c.name < best.name)):
                best, best_remaining = c, remaining
        if best is None:
            return next_wake
        quota = max(self.min_quota_ms, min(self.base_quota_ms, best_remaining))
        best.waiting = False
        self._holder = best.name
        self._vnow = max(self._vnow, best.vtime)
        return best.name, quota

    def release_token(self, name: str, used_ms: float, now_ms: float) -> None:
        if self._holder != name:
            raise ValueError(f"{name} does not hold the token")
        c = self._clients[name]
        if used_ms > 0:
            c.usage.append((now_ms - used_ms, now_ms))
            c.vtime += used_ms / c.request
        self._holder = None

    def set_effective(self, name: str, request: float, limit: float) -> None:
        """Adjust a client's effective share in place (elastic burst
        credit, doc/autopilot.md): same validation as add_client, takes
        hold at the next grant decision — usage history and vtime are
        untouched, so revoking is symmetric and instant."""
        if request <= 0 or limit <= 0 or limit > 1 or request > limit:
            raise ValueError(f"bad request/limit: {request}/{limit}")
        c = self._clients.get(name)
        if c is None:
            raise KeyError(name)
        c.request = request
        c.limit = limit

    def window_usage(self, name: str, now_ms: float) -> float:
        return self._clients[name].window_usage(now_ms, self.window_ms)

    def holder(self) -> str | None:
        return self._holder

    def client_count(self) -> int:
        return len(self._clients)

    def close(self) -> None:
        self._closed = True
        self._clients.clear()
        self._holder = None


# --------------------------------------------------------------------------
# Native core (ctypes over native/tokensched.cpp)
# --------------------------------------------------------------------------

class NativeTokenCore:
    """ctypes wrapper over ``libtokensched.so`` with PyTokenCore's interface."""

    def __init__(self, window_ms: float = WINDOW_MS,
                 base_quota_ms: float = BASE_QUOTA_MS,
                 min_quota_ms: float = MIN_QUOTA_MS, _lib=None):
        lib = _lib if _lib is not None else load_library("tokensched")
        if lib is None:
            raise RuntimeError("native tokensched unavailable")
        self._lib = lib
        lib.ts_create.restype = ctypes.c_void_p
        lib.ts_create.argtypes = [ctypes.c_double] * 3
        lib.ts_destroy.argtypes = [ctypes.c_void_p]
        lib.ts_add_client.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                      ctypes.c_double, ctypes.c_double]
        lib.ts_remove_client.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ts_request_token.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ts_cancel_request.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.ts_poll.argtypes = [ctypes.c_void_p, ctypes.c_double,
                                ctypes.c_char_p, ctypes.c_int,
                                ctypes.POINTER(ctypes.c_double),
                                ctypes.POINTER(ctypes.c_double)]
        lib.ts_release_token.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                         ctypes.c_double, ctypes.c_double]
        lib.ts_window_usage.restype = ctypes.c_double
        lib.ts_window_usage.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                        ctypes.c_double]
        lib.ts_client_count.argtypes = [ctypes.c_void_p]
        lib.ts_holder.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_int]
        self._h = lib.ts_create(window_ms, base_quota_ms, min_quota_ms)
        self.window_ms = window_ms
        self.base_quota_ms = base_quota_ms
        self.min_quota_ms = min_quota_ms

    def _handle(self):
        # Guard every native call: after close() the C++ scheduler is
        # freed, and a stale handle would be a use-after-free (a waiter
        # woken by close would otherwise segfault the whole proxy).
        h = self._h
        if not h:
            raise RuntimeError("token scheduler closed")
        return h

    def add_client(self, name: str, request: float, limit: float) -> None:
        rc = self._lib.ts_add_client(self._handle(), name.encode(), request, limit)
        if rc == -1:
            raise ValueError(f"bad request/limit: {request}/{limit}")
        if rc == -2:
            raise ValueError(f"duplicate client {name}")

    def remove_client(self, name: str) -> None:
        self._lib.ts_remove_client(self._handle(), name.encode())

    def request_token(self, name: str) -> None:
        if self._lib.ts_request_token(self._handle(), name.encode()) != 0:
            raise KeyError(name)

    def cancel_request(self, name: str) -> None:
        self._lib.ts_cancel_request(self._handle(), name.encode())

    def poll(self, now_ms: float):
        buf = ctypes.create_string_buffer(256)
        quota = ctypes.c_double()
        wake = ctypes.c_double()
        rc = self._lib.ts_poll(self._handle(), now_ms, buf, len(buf),
                               ctypes.byref(quota), ctypes.byref(wake))
        if rc == 1:
            return buf.value.decode(), quota.value
        return wake.value

    def release_token(self, name: str, used_ms: float, now_ms: float) -> None:
        if self._lib.ts_release_token(self._handle(), name.encode(), used_ms, now_ms) != 0:
            raise ValueError(f"{name} does not hold the token")

    def set_effective(self, name: str, request: float, limit: float) -> None:
        try:
            fn = self._lib.ts_set_effective
        except AttributeError:
            # a libtokensched.so built before the autopilot plane —
            # surface it as unavailable, never silently drop the credit
            raise RuntimeError(
                "native tokensched predates ts_set_effective; "
                "rebuild with `make native`") from None
        fn.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                       ctypes.c_double, ctypes.c_double]
        rc = fn(self._handle(), name.encode(), request, limit)
        if rc == -1:
            raise ValueError(f"bad request/limit: {request}/{limit}")
        if rc == -2:
            raise KeyError(name)

    def window_usage(self, name: str, now_ms: float) -> float:
        u = self._lib.ts_window_usage(self._handle(), name.encode(), now_ms)
        if u < 0:
            raise KeyError(name)
        return u

    def holder(self) -> str | None:
        buf = ctypes.create_string_buffer(256)
        if self._lib.ts_holder(self._handle(), buf, len(buf)):
            return buf.value.decode()
        return None

    def client_count(self) -> int:
        return self._lib.ts_client_count(self._handle())

    def close(self) -> None:
        if self._h:
            self._lib.ts_destroy(self._h)
            self._h = None

    def __del__(self):  # pragma: no cover
        try:
            self.close()
        except Exception:
            pass


def make_core(window_ms: float = WINDOW_MS, base_quota_ms: float = BASE_QUOTA_MS,
              min_quota_ms: float = MIN_QUOTA_MS, native: bool | None = None):
    """Build the native core when available (or demanded), else Python."""
    if native is not False:
        try:
            return NativeTokenCore(window_ms, base_quota_ms, min_quota_ms)
        except RuntimeError:
            if native:
                raise
    return PyTokenCore(window_ms, base_quota_ms, min_quota_ms)


# --------------------------------------------------------------------------
# Blocking façade + TCP server
# --------------------------------------------------------------------------

def _now_ms() -> float:
    return time.monotonic() * 1000.0


class TokenScheduler:
    """Thread-safe blocking façade over a core: ``acquire`` blocks until the
    token is granted, ``release`` reports usage and wakes the next waiter."""

    def __init__(self, window_ms: float = WINDOW_MS,
                 base_quota_ms: float = BASE_QUOTA_MS,
                 min_quota_ms: float = MIN_QUOTA_MS, native: bool | None = None,
                 clock=None, chip: str = "", ledger=None, blame=None,
                 ledger_clock=None, preempt=None):
        self._core = make_core(window_ms, base_quota_ms, min_quota_ms, native)
        # tracked (doc/observability.md): the Py façade's grant/
        # release lock (the native core reports its own counters)
        self._cond = obs_prof.TrackedCondition("tokensched")
        self._grants: dict[str, float] = {}  # name -> granted quota_ms
        # name -> FIFO of waiter tickets. A client is ONE token stream in
        # the core, but a pipelined connection dispatches gated ops
        # concurrently — multiple façade-level waiters per name must
        # queue, in arrival order, for that single stream (head-of-queue
        # consumes each grant; the rest re-arm the core's request).
        self._waiting: dict[str, deque] = {}
        self._held_since: dict[str, float] = {}  # name -> grant wall time
        self._clock = clock or _now_ms
        self.window_ms = window_ms
        self.chip = chip or "chip"           # metric label for this token
        self._shares: dict[str, tuple[float, float]] = {}   # base
        self._effective: dict[str, tuple[float, float]] = {}
        #: workload class per client (sharedtpu/class) — the grant-wait
        #: histogram's per-tenant attribution (ROADMAP item 1 surface)
        self._classes: dict[str, str] = {}
        #: chip-time ledger + blame graph (doc/observability.md,
        #: contention attribution). ``ledger_clock`` returns SECONDS and
        #: is deliberately separate from ``clock``: the core clock is
        #: milliseconds live but the chaos plane injects its
        #: virtual-seconds clock there — the ledger timebase must not
        #: inherit that ambiguity.
        self._ledger = ledger
        self._blame = blame
        self._ledger_clock = ledger_clock or time.monotonic
        #: demand hook (elastic quota, doc/autopilot.md): called as
        #: ``on_demand(name)`` under the lock the moment a client asks
        #: for the token, BEFORE the grant decision — a lender whose
        #: demand returns gets its credit revoked within that same
        #: token cycle. Exceptions are swallowed: quota policy must
        #: never break the data path.
        self.on_demand = None
        #: preemption plane (kubeshare_tpu.preempt, ROADMAP item 1).
        #: ``preempt`` is a PreemptionPolicy or None; with None AND an
        #: empty boost queue the grant path is exactly the core's poll
        #: — bit-identical to the pre-preemption scheduler.
        self.preempt = preempt
        self._preempt_flags: set[str] = set()     # holders marked
        self._preempt_marked_at: dict[str, float] = {}
        #: directed-grant queue: (name, kind) granted next regardless
        #: of FIFO/stride order — the beneficiary, then the preempted
        #: holder's anti-starvation credit
        self._boost: deque = deque()
        self._hold_quota: dict[str, float] = {}   # name -> granted quota

    @property
    def core(self):
        return self._core

    def add_client(self, name: str, request: float, limit: float,
                   tpu_class: str = "best-effort") -> None:
        with self._cond:
            self._core.add_client(name, request, limit)
            self._shares[name] = (request, limit)
            self._effective[name] = (request, limit)
            self._classes[name] = tpu_class or "best-effort"

    def remove_client(self, name: str) -> None:
        with self._cond:
            self._core.remove_client(name)
            self._grants.pop(name, None)
            was_holding = self._held_since.pop(name, None) is not None
            if was_holding and self._ledger is not None:
                # an evicted/unregistered holder never calls release —
                # close its ledger hold here or the interval leaks open
                self._ledger.release(self.chip, now=self._ledger_clock())
            self._shares.pop(name, None)
            self._effective.pop(name, None)
            self._classes.pop(name, None)
            self._preempt_flags.discard(name)
            self._preempt_marked_at.pop(name, None)
            self._hold_quota.pop(name, None)
            self._cond.notify_all()

    def set_effective(self, name: str, request: float, limit: float) -> bool:
        """Push an adjusted effective share into the core (burst credit
        grant or revocation). Returns False when the native core predates
        the call — the caller must treat the credit as never granted."""
        with self._cond:
            try:
                self._core.set_effective(name, request, limit)
            except RuntimeError:
                return False
            self._effective[name] = (request, limit)
            self._cond.notify_all()   # a raised limit may unblock a waiter
            return True

    def shares(self) -> dict[str, tuple[float, float]]:
        """Base (guaranteed) ``{name: (request, limit)}`` as registered —
        never mutated by burst credit."""
        with self._cond:
            return dict(self._shares)

    def effective(self, name: str) -> tuple[float, float]:
        with self._cond:
            return self._effective[name]

    def waiting(self) -> list[str]:
        """Names with at least one façade-level waiter queued right now."""
        with self._cond:
            return [n for n, q in self._waiting.items() if q]

    def accounting(self) -> dict:
        """One consistent snapshot of the share ledger — the chaos
        plane's token-shares invariant input (doc/chaos.md): per client
        base and effective (request, limit), plus the effective-request
        sum that must stay <= 1.0 even under elastic lending."""
        with self._cond:
            clients = {
                name: {
                    "request": base[0], "limit": base[1],
                    "effective_request": self._effective[name][0],
                    "effective_limit": self._effective[name][1],
                    "class": self._classes.get(name, "best-effort"),
                    "holding": name in self._held_since,
                }
                for name, base in self._shares.items()
            }
            return {
                "chip": self.chip,
                "clients": clients,
                "share_sum": sum(c["effective_request"]
                                 for c in clients.values()),
                "waiting": [n for n, q in self._waiting.items() if q],
                "preempted": sorted(self._preempt_flags),
            }

    def now_ms(self) -> float:
        """This scheduler's clock (injectable in tests) — the timebase
        window_usage is measured on."""
        return self._clock()

    def _note_demand(self, name: str) -> None:
        # caller holds self._cond, right after request_token
        if self.on_demand is None:
            return
        try:
            self.on_demand(name)
        except Exception:
            log.exception("on_demand hook failed for %s", name)

    def acquire(self, name: str, timeout: float | None = None,
                trace_id: str = "") -> float:
        """Block until *name* is granted the token; returns quota_ms."""
        deadline = None if timeout is None else time.monotonic() + timeout
        with phase("gate_wait", name, trace_id, chip=self.chip), self._cond:
            self._core.request_token(name)
            self._note_demand(name)
            t0 = time.monotonic()
            try:
                quota = self._wait_for_grant(name, deadline)
            except TimeoutError:
                self._note_timeout(name, time.monotonic() - t0, trace_id)
                raise
            self._note_grant(name, time.monotonic() - t0, trace_id)
            return quota

    def renew(self, name: str, used_ms: float, timeout: float | None = None,
              trace_id: str = "") -> float:
        """Atomically release + re-request + wait for the next grant.

        This is the steady-state client call (≙ the hook re-requesting when
        its quota runs out while kernels keep coming): the release and the
        re-request happen under one lock acquisition, so this client is
        *waiting* when the freed token is handed out and stride weighting
        decides the order — a release-then-acquire pair instead would hand
        the token to whoever else happened to be waiting in the gap,
        collapsing shares to round-robin.
        """
        deadline = None if timeout is None else time.monotonic() + timeout
        with phase("gate_wait", name, trace_id, chip=self.chip), self._cond:
            self._core.release_token(name, used_ms, self._clock())
            self._note_release(name, used_ms)
            self._core.request_token(name)
            self._note_demand(name)
            self._cond.notify_all()
            t0 = time.monotonic()
            try:
                quota = self._wait_for_grant(name, deadline)
            except TimeoutError:
                self._note_timeout(name, time.monotonic() - t0, trace_id)
                raise
            self._note_grant(name, time.monotonic() - t0, trace_id)
            return quota

    def contended(self, name: str) -> bool:
        """Is the pick at holder *name*'s program boundary the stride's to
        make now: another client waits, and no directed grant is armed (a
        hold marked preempted yields through :meth:`renew`, at its next
        call, as it always has)."""
        with self._cond:
            return (not self._boost and name not in self._preempt_flags
                    and any(q for n, q in self._waiting.items()
                            if n != name))

    def renew_or_yield(self, name: str, used_ms: float) -> float | None:
        """:meth:`renew` for a holder that is not asking yet: where its
        program ended while other clients wait, release + re-request + ONE
        grant decision, never blocking. The new quota when the weighted
        pick is still *name* (its hold goes on, the usage so far reported);
        None when the token went to a waiter or *name* is at its window
        cap, its request withdrawn: it comes back through :meth:`acquire`.

        This is the one rule for a contended program boundary. The holder
        stands in the pick with what it has used, as in ``renew``, so
        request-weighted shares hold at the grain of one program whatever
        its length, a waiter waits for the program in flight and never for
        the rest of a quantum of several, and a holder the pick prefers (a
        burst of short programs from a client under its share) keeps the
        token without a hand-over between them.
        """
        with self._cond:
            self._core.release_token(name, used_ms, self._clock())
            self._core.request_token(name)
            result = self._poll_grant()
            if isinstance(result, tuple) and result[0] == name:
                self._hold_quota[name] = result[1]
                return result[1]
            self._core.cancel_request(name)
            self._note_release(name, used_ms)
            if isinstance(result, tuple):
                self._grants[result[0]] = result[1]
            self._cond.notify_all()
            return None

    def _take_grant(self, name: str, q: deque) -> float:
        # Caller holds self._cond; a grant for `name` exists and this
        # thread's ticket is the queue head. With more same-name waiters
        # queued, re-arm the core's (idempotent) request flag so the next
        # release can grant the stream again — the core granted once and
        # cleared it.
        quota = self._grants.pop(name)
        self._hold_quota[name] = quota
        if len(q) > 1:
            self._core.request_token(name)
            self._cond.notify_all()
        return quota

    def _poll_grant(self):
        """Core poll with directed grants (caller holds ``self._cond``).

        With an empty boost queue this IS ``core.poll`` — the
        preemption-off grant path is bit-identical to the plain
        scheduler. With a boost armed and the chip free, every other
        waiter's request is withdrawn for one poll so the core must
        pick the boost target, then re-armed — cancel/request are
        idempotent flag flips in both cores, so stride state (vtime,
        usage windows) is untouched and shares stay intact. A target
        that is window-capped drops its boost and the poll is redone
        in normal order: a directed grant may jump the queue but can
        never idle the chip (no livelock)."""
        now = self._clock()
        if not self._boost:
            return self._core.poll(now)
        if self._core.holder() is not None:
            # chip still held (the preempted holder is draining to its
            # program boundary) — keep the boost armed
            return self._core.poll(now)
        # prune targets that vanished or already hold the token
        while self._boost:
            target, _kind = self._boost[0]
            if target not in self._shares or target in self._held_since:
                self._boost.popleft()
                continue
            break
        if not self._boost:
            return self._core.poll(now)
        target, kind = self._boost[0]
        if not self._waiting.get(target):
            # the target isn't asking right now (e.g. the preempted
            # holder hasn't re-requested yet) — grant in normal order,
            # keep the boost for when it arrives
            return self._core.poll(now)
        others = [n for n, q in self._waiting.items() if q and n != target]
        for other in others:
            self._core.cancel_request(other)
        try:
            result = self._core.poll(now)
        finally:
            for other in others:
                try:
                    self._core.request_token(other)
                except KeyError:
                    pass
        if isinstance(result, tuple) and result[0] == target:
            self._boost.popleft()
            if self.preempt is not None:
                self.preempt.note_boost_grant(self.chip,
                                              credit=kind == "credit")
            return result
        if not isinstance(result, tuple):
            # target is window-capped: forfeit the boost, normal order
            self._boost.popleft()
            return self._core.poll(now)
        return result

    def _maybe_preempt(self, name: str, waited_s: float):
        """Evaluate the preemption policy for waiter *name* (caller
        holds ``self._cond``). Fires at most once per hold: the holder
        is marked (ledger tags its idle-tail from this instant), the
        waiter and then the holder are queued for directed grants —
        the holder entry IS the anti-starvation credit, so a preempted
        best-effort tenant regains the chip after exactly one
        higher-class grant. Returns seconds until the decision could
        flip (the waiter's next wake-up), or None."""
        policy = self.preempt
        if policy is None or not policy.enabled:
            return None
        holder = next(iter(self._held_since), None)
        if holder is None or holder == name or holder in self._preempt_flags:
            return None
        waiter_class = self._classes.get(name, "best-effort")
        holder_class = self._classes.get(holder, "best-effort")
        held_s = time.monotonic() - self._held_since[holder]
        if policy.should_preempt(waiter_class, holder_class,
                                 waited_s * 1000.0, held_s * 1000.0):
            self._preempt_flags.add(holder)
            self._preempt_marked_at[holder] = time.monotonic()
            self._boost.append((name, "beneficiary"))
            self._boost.append((holder, "credit"))
            if self._ledger is not None:
                self._ledger.mark_preempted(self.chip,
                                            now=self._ledger_clock())
            policy.note_preemption(self.chip, holder, waiter_class,
                                   holder_class)
            log.debug("%s: preempted holder %s for %s (%s > %s)",
                      self.chip, holder, name, waiter_class, holder_class)
            return None
        if not policy.should_preempt(waiter_class, holder_class,
                                     _INF, _INF):
            return None      # class order can never flip the decision
        due = max(policy.grace_ms / 1000.0 - waited_s,
                  policy.min_hold_ms / 1000.0 - held_s)
        return max(0.001, due)

    def preempted(self, name: str) -> bool:
        """Is *name*'s current hold marked preempted? The proxy's
        program-boundary check (preempt/slicer.py): a True answer asks
        the holder to yield — release or renew — at the next execute
        boundary, forfeiting its remaining quantum."""
        with self._cond:
            return name in self._preempt_flags

    def mark_preempted(self, name: str) -> None:
        """Externally mark holder *name* preempted — the gang
        coordinator's entry point for gang-atomic preemption (it makes
        the policy decision itself, across all member chips, in the
        same sorted-chip total order as every other gang op)."""
        with self._cond:
            if name not in self._held_since or name in self._preempt_flags:
                return
            self._preempt_flags.add(name)
            self._preempt_marked_at[name] = time.monotonic()
            if self._ledger is not None:
                self._ledger.mark_preempted(self.chip,
                                            now=self._ledger_clock())
            self._cond.notify_all()

    def add_boost(self, name: str, credit: bool = False) -> None:
        """Queue *name* for a directed grant (next grant regardless of
        FIFO/stride order) — the gang coordinator's beneficiary and
        anti-starvation hooks."""
        with self._cond:
            self._boost.append((name, "credit" if credit else "beneficiary"))
            self._cond.notify_all()

    def _wait_for_grant(self, name: str, deadline: float | None) -> float:
        # Caller holds self._cond and has already requested the token.
        # FIFO among same-name waiters: only the ticket at the head of the
        # queue may consume a grant, so concurrent gated ops on one client
        # are served strictly in arrival order (no barging, no lost
        # grants).
        ticket = object()
        q = self._waiting.setdefault(name, deque())
        q.append(ticket)
        wait_t0 = time.monotonic()
        try:
            while True:
                due = self._maybe_preempt(
                    name, time.monotonic() - wait_t0)
                result = self._poll_grant()
                if isinstance(result, tuple):
                    granted, quota = result
                    self._grants[granted] = quota
                    self._cond.notify_all()
                if name in self._grants and q[0] is ticket:
                    return self._take_grant(name, q)
                try:
                    self._core.window_usage(name, self._clock())
                except KeyError:
                    # Client was removed while we waited (owner connection
                    # died / unregister): error out instead of blocking on
                    # a grant that can never come.
                    raise RuntimeError(f"{name}: client removed while "
                                       "waiting for token") from None
                wait: float | None
                if isinstance(result, tuple) or result == _INF:
                    wait = None
                else:
                    wait = max(0.001, (result - self._clock()) / 1000.0)
                if due is not None:
                    # wake when the preemption decision could flip
                    wait = due if wait is None else min(wait, due)
                if deadline is not None:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        # Withdraw cleanly: consume-and-return a grant that
                        # raced in (head only), else — when this was the
                        # only waiter — clear the core's waiting flag so it
                        # never hands out a token nobody will consume.
                        # Queued waiters behind this one keep the request
                        # armed.
                        if name in self._grants and q[0] is ticket:
                            return self._take_grant(name, q)
                        if len(q) == 1:
                            self._core.cancel_request(name)
                        raise TimeoutError(f"{name}: token wait timed out")
                    wait = remaining if wait is None else min(wait, remaining)
                self._cond.wait(wait)
        finally:
            try:
                q.remove(ticket)
            except ValueError:  # pragma: no cover - ticket appended above
                pass
            if not q:
                self._waiting.pop(name, None)
            # wake the next same-name ticket (now head) so it can claim a
            # pending grant or resume polling
            self._cond.notify_all()

    def _note_grant(self, name: str, wait_s: float, trace_id: str) -> None:
        # caller holds self._cond; a timed-out wait raised before this.
        # Tenant attribution: client names are "namespace/pod" (the pod
        # manager registers under the pod key); a bare name is its own
        # tenant (tests, ad-hoc clients).
        namespace = name.partition("/")[0]
        tpu_class = self._classes.get(name, "best-effort")
        _GRANT_WAIT.observe(self.chip, namespace, tpu_class,
                            value=wait_s, exemplar=trace_id or None)
        obs_slo.default_evaluator().record(
            namespace, "grant-wait", value_s=wait_s, trace_id=trace_id)
        self._held_since[name] = time.monotonic()
        if self._ledger is not None:
            now = self._ledger_clock()
            if self._blame is not None and wait_s > 0.0:
                # attribute BEFORE recording the grant: the wait window
                # must see the previous occupants, not this grant
                self._blame.account_wait(self.chip, namespace, tpu_class,
                                         wait_s, now=now, trace_id=trace_id)
            self._ledger.grant(self.chip, namespace, tpu_class, now=now)

    def _note_timeout(self, name: str, wait_s: float, trace_id: str) -> None:
        # caller holds self._cond; the wait ended in TimeoutError — the
        # blocked time is just as real as a granted wait, so the blame
        # graph still names whoever occupied the chip during it.
        if self._blame is not None and wait_s > 0.0:
            self._blame.account_wait(
                self.chip, name.partition("/")[0],
                self._classes.get(name, "best-effort"), wait_s,
                now=self._ledger_clock(), trace_id=trace_id, granted=False)

    def _note_release(self, name: str, used_ms: float = 0.0) -> None:
        # caller holds self._cond, AFTER release_token so the utilization
        # gauge includes the usage interval just reported
        since = self._held_since.pop(name, None)
        if since is not None:
            _HOLD.observe(self.chip, value=time.monotonic() - since)
        quota = self._hold_quota.pop(name, 0.0)
        marked = self._preempt_marked_at.pop(name, None)
        if name in self._preempt_flags:
            # the preempted holder yielded: meter mark-to-yield latency
            # and the forfeited quantum it reclaimed for the beneficiary
            self._preempt_flags.discard(name)
            if self.preempt is not None:
                yield_s = (0.0 if marked is None
                           else time.monotonic() - marked)
                self.preempt.note_yield(self.chip, yield_s,
                                        max(0.0, quota - used_ms))
        if self._ledger is not None:
            self._ledger.release(self.chip, now=self._ledger_clock())
        # black-box cadence (rate-limited inside): what this token was
        # doing in the run-up to a trigger
        flight_default_recorder().sample_deltas("tokensched-" + self.chip, {
            "clients": float(len(self._shares)),
            "waiting": float(sum(1 for q in self._waiting.values() if q)),
        })
        try:
            usage = self._core.window_usage(name, self._clock())
        except (KeyError, RuntimeError):
            return
        _UTIL.set(self.chip, name, value=usage / self.window_ms)

    def release(self, name: str, used_ms: float) -> None:
        with self._cond:
            self._core.release_token(name, used_ms, self._clock())
            self._note_release(name, used_ms)
            self._cond.notify_all()

    def execute_begin(self) -> None:
        """An execute started under the current hold (proxy ``_gated``)
        — flips the ledger interval to granted-active."""
        if self._ledger is not None:
            self._ledger.execute_begin(self.chip, now=self._ledger_clock())

    def execute_end(self) -> None:
        if self._ledger is not None:
            self._ledger.execute_end(self.chip, now=self._ledger_clock())

    def window_usage(self, name: str) -> float:
        with self._cond:
            return self._core.window_usage(name, self._clock())

    def close(self) -> None:
        with self._cond:
            self._core.close()
            # Wake every blocked waiter so it hits the closed-core guard
            # instead of sleeping forever on a grant that can never come.
            self._cond.notify_all()


def serve(scheduler: TokenScheduler, host: str = "127.0.0.1", port: int = 0,
          coordinator=None):
    """Expose a :class:`TokenScheduler` over framed-JSON TCP.

    Requests: ``{"op": "register", "name", "request", "limit"}`` (creates
    the client; this connection owns it; optional ``"class"`` tags the
    workload class for per-tenant metrics), ``{"op": "attach", "name"}``
    (binds an extra connection to an existing client — a pod manager's
    per-gate relay channels), ``{"op": "acquire"}`` (blocks; reply carries
    ``quota_ms``), ``{"op": "renew", "used_ms"}`` (atomic
    release+reacquire — the steady-state call), ``{"op": "release",
    "used_ms"}``, ``{"op": "usage"}``, ``{"op": "unregister"}``.
    Token ops act on the *connection-bound* identity (set by
    register/attach) — a connection can never name another pod's client.
    Replies: ``{"ok": true, ...}`` or ``{"ok": false, "error": msg}``.
    The owning connection's disconnect removes the client (≙ gem-schd
    dropping a dead pod manager); attached connections' disconnects don't.

    A server started with a :class:`~kubeshare_tpu.gang.coordinator.
    GangTokenCoordinator` additionally speaks the gang-grant extension
    (doc/isolation-wire.md, negotiated feature): ``gang_register`` /
    ``gang_acquire`` / ``gang_release`` / ``gang_state``. Without a
    coordinator those names answer the standard unknown-op error —
    byte-for-byte the pre-extension wire — so an un-negotiated peer
    observes no difference.

    A scheduler with an attached :class:`~kubeshare_tpu.preempt.policy.
    PreemptionPolicy` likewise speaks the preemption extension
    (doc/isolation-wire.md): ``preempt_poll`` (is the connection-bound
    client's hold marked preempted? — the remote program-boundary
    check) and ``preempt_state`` (the policy snapshot). Without a
    policy those names answer the standard unknown-op error too.
    """
    def handle(req: dict, state: dict) -> dict:
        op = req.get("op")
        if coordinator is not None and op in (
                "gang_register", "gang_acquire", "gang_release",
                "gang_state"):
            return _handle_gang(coordinator, op, req, state)
        if scheduler.preempt is not None and op in ("preempt_poll",
                                                    "preempt_state"):
            if op == "preempt_state":
                return {"ok": True, "state": scheduler.preempt.snapshot()}
            name = state.get("name")
            if not name:
                raise PermissionError(
                    "connection not bound (register/attach first)")
            return {"ok": True, "preempted": scheduler.preempted(name)}
        if op not in ("register", "attach", "acquire", "renew", "release",
                      "usage", "unregister"):
            return {"ok": False, "error": f"unknown op {op!r}"}
        if op == "register":
            if state.get("name"):
                raise ValueError(
                    f"connection already bound to {state['name']!r}")
            name = req["name"]
            scheduler.add_client(name, float(req["request"]),
                                 float(req["limit"]),
                                 tpu_class=req.get("class", "best-effort"))
            state["name"] = name
            state["owner"] = True
            return {"ok": True}
        if op == "attach":
            if state.get("name"):
                raise ValueError(
                    f"connection already bound to {state['name']!r}")
            name = req["name"]
            scheduler.window_usage(name)  # KeyError if no such client
            state["name"] = name
            state["owner"] = False
            return {"ok": True}
        name = state.get("name")
        if not name:
            raise PermissionError("connection not bound (register/attach first)")
        if op == "acquire":
            quota = scheduler.acquire(name, timeout=req.get("timeout"),
                                      trace_id=state.get("trace_id", ""))
            return {"ok": True, "quota_ms": quota}
        if op == "renew":
            quota = scheduler.renew(name, float(req["used_ms"]),
                                    timeout=req.get("timeout"),
                                    trace_id=state.get("trace_id", ""))
            return {"ok": True, "quota_ms": quota}
        if op == "release":
            scheduler.release(name, float(req["used_ms"]))
            return {"ok": True}
        if op == "usage":
            return {"ok": True,
                    "used_ms": scheduler.window_usage(name),
                    "window_ms": scheduler.window_ms}
        if op == "unregister":
            scheduler.remove_client(name)
            state.pop("name", None)
            state.pop("owner", None)
        return {"ok": True}

    def cleanup(state: dict) -> None:
        if state.get("owner") and state.get("name"):
            try:
                scheduler.remove_client(state["name"])
            except RuntimeError:
                pass  # scheduler already closed — nothing left to free
        if coordinator is not None:
            for gang in state.get("gangs", ()):
                try:
                    coordinator.unregister_gang(gang)
                except Exception:
                    pass

    return protocol.serve_framed(host, port, handle, cleanup)


def _handle_gang(coordinator, op: str, req: dict, state: dict) -> dict:
    """Gang-grant wire extension (doc/gang.md). ``gang_register``
    publishes membership and makes this connection the gang's owner
    (disconnect withdraws it, mirroring client ownership);
    ``gang_acquire``/``gang_release`` drive the two-phase gang-atomic
    grant; ``gang_state`` returns the coordinator snapshot."""
    if op == "gang_state":
        return {"ok": True, "state": coordinator.snapshot()}
    gang = req.get("gang")
    if not gang:
        raise ValueError("gang ops require a 'gang' id")
    if op == "gang_register":
        members = [(str(c), str(cl)) for c, cl in req["members"]]
        coordinator.register_gang(
            gang, members, namespace=req.get("namespace", ""),
            tpu_class=req.get("class", "best-effort"))
        state.setdefault("gangs", set()).add(gang)
        return {"ok": True}
    if op == "gang_acquire":
        held = coordinator.acquire(gang, timeout=req.get("timeout"),
                                   trace_id=req.get("trace_id", ""))
        return {"ok": True, "held": dict(held)}
    # gang_release
    coordinator.release(gang, used_ms=req.get("used_ms"))
    return {"ok": True}
