"""The chip proxy: one process owns the chip, clients execute through it.

On NVIDIA, N processes each own a CUDA context on one GPU, so the
reference's isolation layer is an LD_PRELOAD metering shim inside each
client (``libgemhook.so.1``, injected at ``pkg/scheduler/pod.go:445-457``).
A TPU chip is single-tenant per process at the libtpu level, so interception
becomes *proxying*: the :class:`ChipProxy` is the one resident process that
holds the chip; client pods run JAX on the CPU backend, trace + serialize
their programs with ``jax.export`` (StableHLO), and submit them over a local
socket. Buffers stay device-resident between calls (PJRT's buffer model),
so a training loop ships its parameters once and then exchanges only
handles.

Enforcement lives where the reference's lives:

- **compute** — every execution is gated by the per-chip token scheduler
  (:mod:`.tokensched`, gem-schd parity): a client acquires a quota, keeps
  the token across back-to-back programs until the quota is exhausted
  (Gemini's kernel-burst amortization), an idle timer returns the token
  early when the client stalls between steps (while another client waits,
  once the holder's grace, learned from its own gaps, has passed), and
  where a program ends while another client waits the scheduler's
  weighted pick says who holds;
- **HBM** — device bytes are accounted per client at allocation time
  (``put`` and execution outputs), mirroring the hook's ``gpu_mem`` cap at
  ``cuMemAlloc`` (annotation default rule at ``pkg/scheduler/pod.go:419-424``).
"""

from __future__ import annotations

import base64
import os
import re
import socket
import threading
import time
import uuid
from collections import OrderedDict, deque
from concurrent.futures import Future
from contextlib import contextmanager
from dataclasses import dataclass, field

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..obs.flight import default_recorder as flight_default_recorder
from ..resilience import faults as _faults
from ..resilience.journal import SessionJournal
from ..utils.logger import get_logger
from ..utils.realjit import real_jit
from ..preempt.slicer import BoundarySlicer
from . import protocol
from .protocol import load_array
from .tokensched import TokenScheduler

log = get_logger("proxy")

IDLE_RELEASE_MS = 10.0

#: A hold idle while another session asks for the chip lasts its holder's
#: grace (:func:`_grace_ms`), learned from the holder's last
#: ``GRACE_RING`` gaps between a program's end and its next request: the
#: least idle past which at most one gap in ``GRACE_EARLY`` came back
#: before ``idle_release_ms``, at least ``GRACE_FLOOR_MS``. A session
#: with fewer than ``GRACE_SAMPLES`` gaps on record has no grace: its
#: hold waits out ``idle_release_ms``.
GRACE_SAMPLES = 8
GRACE_RING = 64
GRACE_EARLY = 32
GRACE_FLOOR_MS = 1.0


def _grace_ms(gaps, idle_release_ms: float) -> float | None:
    """The grace of a hold whose holder's recent gaps (a program's end to
    its next request) are ``gaps``: the least idle such that no more than
    ``len(gaps) // GRACE_EARLY`` of them lie between it and
    ``idle_release_ms`` (where ending the hold would have kept that
    request off the chip for the waiter's program), so below
    ``idle_release_ms``, and at least ``GRACE_FLOOR_MS``. A back-to-back
    burst's turn-arounds and the arrivals of an open-loop client that come
    soon after its last program both count; None under ``GRACE_SAMPLES``
    gaps."""
    if len(gaps) < GRACE_SAMPLES:
        return None
    short = sorted(g for g in gaps if g < idle_release_ms)
    late = len(gaps) // GRACE_EARLY
    if len(short) <= late:
        return GRACE_FLOOR_MS
    return max(short[-1 - late], GRACE_FLOOR_MS)

#: how long a detached (resumable) session's state is kept before the
#: watchdog reclaims it — the client's reconnect budget must fit inside
DETACH_GRACE_MS = 30_000.0

_KNOWN_OPS = frozenset((
    "register", "put", "put_begin", "put_chunk", "put_commit", "put_abort",
    "get", "free", "compile", "execute", "usage", "unregister",
    "drain", "migrate_begin", "migrate_finish", "export_session",
    "export_buffer", "export_program", "import_session",
    "import_buffer_begin", "import_buffer_chunk", "import_buffer_commit",
    "import_program"))
#: control-plane ops addressed by resume token, not connection identity
#: (the mover — scheduler/operator tooling — is never a registered
#: client; holding a session's token IS the capability to move it)
_ADMIN_OPS = frozenset((
    "drain", "migrate_begin", "migrate_finish", "export_session",
    "export_buffer", "export_program", "import_session",
    "import_buffer_begin", "import_buffer_chunk", "import_buffer_commit",
    "import_program"))
#: side-effect-free (or naturally idempotent) ops: a replayed rid whose
#: reply fell out of the cache — or was never cached because it carries
#: a blob — is simply re-executed
_REPLAY_REEXEC = frozenset((
    "get", "usage", "free", "put_abort", "put_chunk"))
#: session-mutating ops after which the journal manifest is rewritten
_JOURNALED_OPS = frozenset((
    "put", "put_begin", "put_commit", "put_abort", "compile", "execute",
    "free"))
_RPC_LAT = obs_metrics.default_registry().histogram(
    "kubeshare_proxy_rpc_latency_seconds",
    "Chip-proxy RPC handling wall time per op (token waits and device "
    "time included).", labels=("op",))
_OBS = obs_metrics.default_registry()
_RESUMES = _OBS.counter(
    "kubeshare_proxy_session_resumes_total",
    "Sessions re-attached via a resume token after their connection "
    "died.")
_DETACHES = _OBS.counter(
    "kubeshare_proxy_session_detaches_total",
    "Resumable sessions whose connection died (state parked, awaiting "
    "resume or grace expiry).")
_DETACHED = _OBS.gauge(
    "kubeshare_proxy_sessions_detached",
    "Resumable sessions currently parked without a connection.")
_REPLAY_SERVED = _OBS.counter(
    "kubeshare_proxy_replay_served_total",
    "Replayed requests answered from the per-session reply cache (or "
    "re-executed idempotently) instead of being executed twice.")


def _now_ms() -> float:
    return time.monotonic() * 1000.0


#: a parameter aliased to an output, in a compiled module's header:
#: ``input_output_alias={ {0}: (0, {}, may-alias), ... }``
_ALIAS_PARAM = re.compile(r"\{[\d, ]*\}: \((\d+), \{")

#: per-session phase counters, cumulative milliseconds, reported by
#: ``usage`` beside ``exec_ms_total`` (doc/observability.md names each);
#: ``dispatch_ms_total`` + ``barrier_ms_total`` is ``exec_ms_total`` split
_PHASE_KEYS = ("self_ms_total", "idle_attach_ms_total", "idle_gate_ms_total",
               "idle_proxy_ms_total", "shim_ms_total", "wire_ms_total",
               "turn_ms_total", "dispatch_ms_total", "barrier_ms_total")


@dataclass
class _Program:
    """Per-PROGRAM state, shared across sessions by blob hash.

    Identical clients (the common co-location case: N replicas of one
    training script) export byte-identical StableHLO; compiling per
    session would pay every multi-second XLA compile N times.
    """
    single: object = None         # the AOT-compiled plain program (lazy)
    #: for a program with recyclable outputs (``_Executable.recycle_meta``),
    #: a Future of ``(compiled, aliased)``: the same program with a donated,
    #: unread first argument that holds one freed buffer per such output,
    #: which XLA writes those outputs into instead of allocating them;
    #: ``aliased`` counts the outputs the compiled program aliases so.
    #: Compiled on a thread of its own beside ``single`` (_recycle_form)
    recycle: object = None


@dataclass
class _Executable:
    exec_id: int
    call: object                  # the raw exported call (traceable)
    in_specs: list                # ShapeDtypeStruct per arg
    out_nbytes: int               # total output allocation, pre-checked
    out_meta: list[tuple[list[int], str]]  # (shape, dtype) per output
    prog: _Program                # the compiled program, sha-shared
    # Hot-path precomputations (the execute handler runs per dispatched op
    # and is the serial stage of the pipelined transport — jax Array
    # .nbytes/.dtype property chains cost tens of µs per op if consulted
    # per dispatch instead of once per compile):
    # (shape tuple, np.dtype) per arg — validated by direct comparison
    in_meta: list = field(default_factory=list)
    # completion-barrier pick: (index of smallest non-empty output or -1,
    # True when that output is big enough to sync via a 1-element slice)
    sync_out: tuple = (-1, False)
    #: (shape tuple, np.dtype) of each output that some input of the
    #: program matches in shape and dtype, in output order: what a state-
    #: carrying step frees on its next call is exactly such buffers
    recycle_meta: list = field(default_factory=list)


@dataclass
class _Session:
    name: str
    request: float
    limit: float
    memory_cap: int               # bytes; 0 = uncapped
    buffers: dict[int, object] = field(default_factory=dict)
    executables: dict[int, _Executable] = field(default_factory=dict)
    hbm_used: int = 0
    next_id: int = 0
    # token state (guarded by lock)
    lock: threading.Lock = field(default_factory=threading.Lock)
    holding: bool = False
    busy: bool = False            # an execution is in flight right now
    quota_ms: float = 0.0
    used_ms: float = 0.0
    last_end_ms: float = 0.0      # when the last execution finished
    #: waiting at the gate right now (in ``acquire`` or ``renew``)
    asking: bool = False
    #: the recent gaps from a program's end to the session's next request,
    #: and the grace they give (``_grace_ms``)
    gaps: deque = field(default_factory=lambda: deque(maxlen=GRACE_RING))
    grace_ms: float | None = None
    #: the hold goes on from a contended program boundary at which the
    #: pick kept it; counts of such boundaries, of the holds the grace
    #: then ended, and of those whose holder came back before
    #: ``idle_release_ms`` (``usage``); the grace ended the last one
    kept: bool = False
    kept_count: int = 0
    kept_yielded: int = 0
    kept_early: int = 0
    graced: bool = False
    exec_count: int = 0
    exec_ms_total: float = 0.0
    #: round trips: every request handled for the session, any op; host
    #: arrays that came in on an ``execute``; barrier reads sent back in
    #: its reply. ``rpc_count`` over ``exec_count`` is the requests a call.
    rpc_count: int = 0
    inline_in_total: int = 0
    inline_out_total: int = 0
    #: outputs its programs produced, and of them those written into a
    #: buffer the same ``execute`` freed (output recycling)
    out_count: int = 0
    out_recycled: int = 0
    #: where this session's executions blocked and whose idle gap each
    #: ended (_PHASE_KEYS; added to under ``lock``)
    phase_ms: dict = field(
        default_factory=lambda: dict.fromkeys(_PHASE_KEYS, 0.0))
    # Phase stamps of the execute call in flight, all from _now_ms() on
    # the connection's one worker thread: when the request reached
    # _dispatch, and for the whole call [arrival, ms spent waiting at
    # the gate, for _dlock, or running on the device].
    arrived_ms: float = 0.0
    call: list | None = None
    #: the previous execute's handler time (arrival to reply): what the
    #: client's round trip minus this leaves is the wire
    last_handler_ms: float | None = None
    # Chunked-transfer state (connection-serialized like everything else):
    # one cached serialized stream for sliced `get` as
    # (handle, parts list, total bytes) — parts, not joined bytes, so the
    # cache costs exactly the one device→host copy — and in-flight staged
    # uploads for `put_begin`/`put_chunk`/`put_commit` as
    # (total, buffer, hbm charge reserved at put_begin).
    fetch_cache: tuple[int, list, int] | None = None
    staging: dict[int, tuple[int, bytearray, int]] = field(
        default_factory=dict)
    #: trace ID propagated by the client at register (protocol TRACE_KEY);
    #: handed to the token scheduler so grant-waits join the pod's timeline
    trace_id: str = ""
    #: workload class (sharedtpu/class) propagated at register — tags the
    #: token scheduler's per-tenant grant-wait series
    tpu_class: str = "best-effort"
    # -- resilience state (resumable sessions only) ---------------------
    #: features negotiated at register; frozen for the session's lifetime
    features: frozenset = frozenset()
    #: capability to re-attach/migrate this session; empty = classic
    #: session, dropped with its connection
    resume_token: str = ""
    #: a connection currently owns the session (identity stays
    #: connection-bound between detach and resume)
    attached: bool = True
    detached_at: float = 0.0
    #: set while no connection owns the session; resume waits on it so a
    #: racing reconnect can't alias the dying connection
    detach_ev: threading.Event = field(default_factory=threading.Event)
    migrating: bool = False
    #: severs the owning connection (installed by the server transport);
    #: migration and resume takeover use it to kick the old owner
    disconnect: object = None
    #: replay state: highest request id handled + bounded blobless reply
    #: cache, so a replayed request is answered, not re-executed
    last_rid: int = 0
    replies: OrderedDict = field(default_factory=OrderedDict)
    #: staged uploads invalidated by a detach — their bytes are gone and
    #: their HBM reservation released; a replayed chunk referencing one
    #: gets a typed refusal telling the client to restart the upload
    aborted_staging: set = field(default_factory=set)
    #: exec_id -> serialized exported program: retained for
    #: journal/export so a restarted or destination proxy can recompile
    program_blobs: dict = field(default_factory=dict)
    #: import staging sid -> destination handle (migration transfers)
    import_handles: dict = field(default_factory=dict)

    def fresh_id(self) -> int:
        self.next_id += 1
        return self.next_id


class _FifoLock:
    """A FIFO mutex. ``threading.Lock`` lets a fast acquire/release loop
    barge past parked waiters indefinitely (futex wake favors the running
    thread) — under the device lock that starves a client whose first-time
    compile is queued behind another client's hot execute loop. Handing the
    lock to the longest waiter bounds everyone's wait by the queue length.
    """

    def __init__(self):
        self._mu = threading.Lock()
        self._waiters: deque[threading.Event] = deque()
        self._held = False

    def acquire(self) -> None:
        with self._mu:
            if not self._held and not self._waiters:
                self._held = True
                return
            ev = threading.Event()
            self._waiters.append(ev)
        ev.wait()  # ownership is handed off in release — no re-race

    def release(self) -> None:
        with self._mu:
            if self._waiters:
                self._waiters.popleft().set()
            else:
                self._held = False

    def __enter__(self):
        self.acquire()
        return self

    def __exit__(self, *exc):
        self.release()
        return False


class HBMError(RuntimeError):
    pass


def _refuse_loop_keys(req: dict, *keys: str) -> None:
    """Input from outside the program: an older client's fused-loop keys
    name an execution path this proxy does not have. Answered with a clean
    error that names the key, before anything is charged or dispatched."""
    for key in keys:
        # "repeat": 1 is what such a client sent with every plain call
        if key in req and (key, req[key]) != ("repeat", 1):
            raise ValueError(
                f"{key!r} is not supported: a program runs once per "
                f"execute (the fused-loop path is gone)")


class ChipProxy:
    """Owns one chip; serves the framed-JSON execution protocol.

    ``device=None`` grabs the process's default JAX device — on a TPU host
    that is the real chip; in tests it is a CPU device, which exercises the
    identical code path (the proxy is backend-agnostic by construction).
    """

    #: per-session replay cache entries (blobless replies only)
    REPLAY_CACHE = 256

    def __init__(self, device=None, scheduler: TokenScheduler | None = None,
                 idle_release_ms: float = IDLE_RELEASE_MS,
                 journal_dir: str | None = None,
                 detach_grace_ms: float = DETACH_GRACE_MS):
        import jax
        self._jax = jax
        self.device = device if device is not None else jax.devices()[0]
        self.platform = self.device.platform
        # default scheduler feeds the process-global chip-time ledger +
        # blame graph (obs/ledger.py): grant/release/execute intervals
        # and wait attribution with zero extra wiring. An injected
        # scheduler keeps whatever ledger its builder chose.
        from ..obs.blame import default_blame
        from ..obs.ledger import default_ledger
        self.scheduler = (scheduler if scheduler is not None
                          else TokenScheduler(chip=str(self.device),
                                              ledger=default_ledger(),
                                              blame=default_blame()))
        # program-boundary slicing (preempt/slicer.py): between token-
        # gated executes the proxy asks whether its hold was preempted and
        # yields via renew — never mid-execute (the slicer refuses while
        # an execute is in flight and its stats prove it)
        self.slicer = BoundarySlicer(self.scheduler)
        self.idle_release_ms = idle_release_ms
        self.detach_grace_ms = detach_grace_ms
        self.journal = SessionJournal(journal_dir)
        self._sessions: dict[str, _Session] = {}
        self._by_token: dict[str, _Session] = {}
        #: token -> (host, port) tombstones left by migrate_finish, so a
        #: reconnecting client is redirected to the destination proxy
        self._moved: dict[str, tuple[str, int]] = {}
        self._draining = False
        self._crashed = False
        self._recovered = False
        self._slock = threading.Lock()
        # Serializes ALL device interactions (put/get/compile/execute).
        # One thread drives the device at a time (kept pending S3: whether
        # the directly attached runtime needs it is not yet measured).
        # Executions are already exclusive via the token gate; this lock is
        # taken INSIDE the gate (never around it), so there is no ordering
        # cycle with the scheduler's own blocking.
        self._dlock = _FifoLock()
        #: when the chip's last program ended (guarded by _dlock): the
        #: start of the idle gap the next program ends. None until the
        #: first program, whose gap is nobody's
        self._last_device_end: float | None = None
        #: the ``timing`` of the call whose program is on the chip
        #: (guarded by _dlock)
        self._on_device: dict = {}
        # blob-sha → _Program: compiled artifacts shared
        # across sessions (guarded by _slock for lookup; compiles race-safe
        # under _dlock). LRU-capped: a client churning unique programs must
        # not grow the proxy without bound — evicted programs just
        # recompile on next use.
        self._programs: "dict[str, _Program]" = {}
        self._programs_cap = 32
        self.total_execs = 0          # lifetime, survives session drops
        self._server: protocol.FramedServer | None = None
        self._stop = threading.Event()
        #: wakes the idle watchdog before its next tick: a hold was kept at
        #: a contended boundary, or a session started to wait for the token
        self._wake = threading.Event()
        self._watchdog: threading.Thread | None = None

    # -- lifecycle -----------------------------------------------------------

    def serve(self, host: str = "127.0.0.1", port: int = 0) -> protocol.FramedServer:
        if self.journal.enabled and not self._recovered:
            # restore journaled sessions BEFORE the listener exists, so a
            # reconnecting client never races a half-recovered proxy
            self._recovered = True
            self._recover_sessions()
        self._server = protocol.serve_framed(host, port, self._handle_timed,
                                             self._cleanup,
                                             sink=self._blob_sink)
        self._watchdog = threading.Thread(target=self._watch_idle, daemon=True,
                                          name="proxy-idle-watchdog")
        self._watchdog.start()
        log.info("chip proxy serving %s on %s:%d", self.device,
                 *self._server.server_address[:2])
        return self._server

    @property
    def port(self) -> int:
        assert self._server is not None
        return self._server.server_address[1]

    def close(self) -> None:
        self._stop.set()
        self._wake.set()
        if self._server is not None:
            self._server.shutdown()
            self._server.server_close()
            self._server = None
        with self._slock:
            names = list(self._sessions)
        for name in names:
            self._drop_session(name)
        self.scheduler.close()

    # -- session management --------------------------------------------------

    def _register(self, name: str, request: float, limit: float,
                  memory: int,
                  tpu_class: str = "best-effort") -> _Session:
        with self._slock:
            if name in self._sessions:
                raise ValueError(f"duplicate client {name}")
            self.scheduler.add_client(name, request, limit,
                                      tpu_class=tpu_class)
            sess = _Session(name, request, limit, memory)
            sess.tpu_class = tpu_class
            self._sessions[name] = sess
            return sess

    def _session(self, name: str) -> _Session:
        with self._slock:
            try:
                return self._sessions[name]
            except KeyError:
                raise KeyError(f"unknown client {name!r}") from None

    def _drop_session(self, name: str, purge: bool = False) -> None:
        with self._slock:
            sess = self._sessions.pop(name, None)
            if sess is not None and sess.resume_token:
                self._by_token.pop(sess.resume_token, None)
        if sess is None:
            return
        if sess.resume_token and not sess.attached:
            _DETACHED.inc(amount=-1.0)
        with sess.lock:
            holding, used = sess.holding, sess.used_ms
            sess.holding = False
        if holding:
            try:
                self.scheduler.release(name, used)
            except Exception:
                pass
        self.scheduler.remove_client(name)
        sess.buffers.clear()
        sess.executables.clear()
        sess.program_blobs.clear()
        if purge and sess.resume_token:
            self.journal.purge(sess.resume_token)
        log.info("client %s dropped (freed %d bytes HBM)", name, sess.hbm_used)

    def _detach_session(self, sess: _Session) -> None:
        """Connection died but the session holds a resume token: park the
        state instead of dropping it. Everything tied to the *connection*
        is released — the token (a parked client must not hold the chip),
        the fetch cache, and every open staged upload: its window can
        never complete (partially-landed bytes are garbage), so the
        staging buffers are GC'd, their HBM reservation released, and the
        sids remembered as aborted so replayed chunks get a typed refusal
        instead of silently corrupting a commit."""
        with sess.lock:
            holding, used = sess.holding, sess.used_ms
            sess.holding = False
        if holding:
            try:
                self.scheduler.release(sess.name, used)
            except Exception:
                pass
        with self._slock:
            for sid, (_total, _raw, charged) in sess.staging.items():
                sess.hbm_used -= charged
                sess.aborted_staging.add(sid)
            sess.staging.clear()
            while len(sess.aborted_staging) > 256:
                sess.aborted_staging.pop()
            sess.fetch_cache = None
            sess.attached = False
            sess.detached_at = _now_ms()
            sess.disconnect = None
        sess.detach_ev.set()
        _DETACHES.inc()
        _DETACHED.inc()
        flight_default_recorder().note("proxy", "session-detached",
                                       client=sess.name,
                                       trace_id=sess.trace_id,
                                       hbm_parked=sess.hbm_used)
        self._journal_checkpoint(sess)
        log.info("client %s detached (%d bytes HBM parked, %d staged "
                 "uploads aborted)", sess.name, sess.hbm_used,
                 len(sess.aborted_staging))

    # -- accounting introspection -------------------------------------------

    def hbm_accounting(self) -> dict[str, dict]:
        """Per-session HBM double-entry: ``hbm_used`` (what ``_charge``
        accumulated) against what is actually resident — live buffer
        bytes plus staged-upload reservations.  ``balanced`` is the
        chaos plane's hbm-conservation invariant (doc/chaos.md); sample
        at quiesce — an execution in flight legitimately carries a
        transient output charge with no buffer yet."""
        out: dict[str, dict] = {}
        with self._slock:
            sessions = list(self._sessions.values())
        for sess in sessions:
            buffer_bytes = sum(int(getattr(buf, "nbytes", 0))
                               for buf in sess.buffers.values())
            staged_bytes = sum(charged for (_total, _raw, charged)
                               in sess.staging.values())
            out[sess.name] = {
                "hbm_used": sess.hbm_used,
                "buffer_bytes": buffer_bytes,
                "staged_bytes": staged_bytes,
                "memory_cap": sess.memory_cap,
                "balanced": sess.hbm_used == buffer_bytes + staged_bytes,
            }
        return out

    # -- drain / crash -------------------------------------------------------

    def drain(self) -> None:
        """Stop admitting new sessions and bleed tokens down fast —
        the precondition for migrating sessions off this chip."""
        self._draining = True
        # a draining chip should not let idle holders sit on the token
        self.idle_release_ms = min(self.idle_release_ms, 2.0)
        log.info("proxy draining: new sessions refused")

    @property
    def draining(self) -> bool:
        return self._draining

    def crash(self) -> None:
        """Fault-injection hard stop: the listener and every live
        connection die immediately and NO cleanup runs (``_cleanup`` is
        short-circuited) — the closest a test can get to ``kill -9``
        without losing the process. Session recovery must come from the
        journal alone."""
        self._crashed = True
        self._stop.set()
        self._wake.set()
        srv, self._server = self._server, None
        if srv is None:
            return
        with srv._conn_mu:
            socks = list(srv._conn_socks)
        for s in socks:
            try:
                s.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
        # shutdown() joins the serve_forever loop; do it off-thread so a
        # worker-thread crash hook (mid-request) cannot deadlock itself
        threading.Thread(
            target=lambda: (srv.shutdown(), srv.server_close()),
            daemon=True).start()

    # -- journal -------------------------------------------------------------

    def _manifest(self, sess: _Session) -> dict:
        return {
            "token": sess.resume_token,
            "name": sess.name,
            "request": sess.request,
            "limit": sess.limit,
            "memory": sess.memory_cap,
            "features": sorted(sess.features),
            "class": sess.tpu_class,
            "trace_id": sess.trace_id,
            "next_id": sess.next_id,
            "last_rid": sess.last_rid,
            "buffers": [{"handle": int(h), "shape": list(b.shape),
                         "dtype": str(b.dtype), "nbytes": int(b.nbytes)}
                        for h, b in sess.buffers.items()],
            "programs": [{"exec_id": int(i)} for i in sess.program_blobs],
            "staging": sorted(int(s) for s in sess.staging),
            "aborted": sorted(int(s) for s in sess.aborted_staging),
            "replies": [[int(r), rep] for r, rep in sess.replies.items()],
        }

    def _journal_checkpoint(self, sess: _Session) -> None:
        if sess.resume_token and self.journal.enabled:
            self.journal.checkpoint(self._manifest(sess))

    def _journal_buffer(self, sess: _Session, handle: int, buf) -> None:
        if not (sess.resume_token and self.journal.enabled):
            return
        with self._xfer(sess.name, "journal", int(buf.nbytes)):
            host = np.asarray(buf)
        self.journal.save_buffer(sess.resume_token, handle, host)

    @contextmanager
    def _xfer(self, who: str, op: str, nbytes: int):
        """Hold ``_dlock`` for a transfer between host and device: the
        profiler's ``ks.dlock_wait`` while asking for it and ``ks.xfer``
        while it is held, with the stats ``op`` and ``bytes``
        (``scripts/ks_spans.py`` reads them; no counter does)."""
        with obs_trace.phase("dlock_wait", who, op=op, bytes=nbytes):
            self._dlock.acquire()
        try:
            with obs_trace.phase("xfer", who, op=op, bytes=nbytes):
                yield
        finally:
            self._dlock.release()

    def _forget_buffer(self, sess: _Session, handle: int):
        """Drop one buffer (freed or donated): HBM accounting plus the
        journal sidecar, in one place."""
        buf = sess.buffers.pop(int(handle), None)
        if buf is not None:
            sess.hbm_used -= int(buf.nbytes)
            if sess.resume_token and self.journal.enabled:
                self.journal.drop_buffer(sess.resume_token, int(handle))
        return buf

    def _recover_sessions(self) -> None:
        for manifest in self.journal.recover():
            try:
                self._restore_session(manifest)
            except Exception as exc:
                log.warning("journal recovery of session %r failed: %s",
                            manifest.get("name"), exc)

    def _restore_session(self, m: dict) -> None:
        name, token = str(m["name"]), str(m["token"])
        with self._slock:
            if name in self._sessions:
                return
        self.scheduler.add_client(name, float(m["request"]),
                                  float(m["limit"]),
                                  tpu_class=m.get("class", "best-effort"))
        sess = _Session(name, float(m["request"]), float(m["limit"]),
                        int(m.get("memory", 0)))
        sess.features = frozenset(m.get("features", ()))
        sess.tpu_class = m.get("class", "best-effort")
        sess.resume_token = token
        sess.trace_id = str(m.get("trace_id", ""))
        sess.next_id = int(m.get("next_id", 0))
        sess.last_rid = int(m.get("last_rid", 0))
        sess.replies = OrderedDict(
            (int(rid), rep) for rid, rep in m.get("replies", []))
        # open windows can never complete across a crash: recovered as
        # aborted, the client restarts those uploads
        sess.aborted_staging = {int(s) for s in m.get("staging", [])}
        sess.aborted_staging |= {int(s) for s in m.get("aborted", [])}
        sess.attached = False
        sess.detached_at = _now_ms()
        sess.detach_ev.set()
        for spec in m.get("buffers", ()):
            handle = int(spec["handle"])
            arr = self.journal.load_buffer(token, handle)
            with self._dlock:
                dev = self._jax.device_put(arr, self.device)
            sess.buffers[handle] = dev
            sess.hbm_used += int(dev.nbytes)
        for spec in m.get("programs", ()):
            blob = self.journal.load_program(token, int(spec["exec_id"]))
            # an older proxy's manifest may record "ncarry" (a fused-loop
            # program): run once it IS one step, so it restores as a plain
            # program and the field is not read
            self._install_program(sess, blob, exec_id=int(spec["exec_id"]))
        with self._slock:
            self._sessions[name] = sess
            self._by_token[token] = sess
        _DETACHED.inc()
        log.info("recovered session %s from journal (%d buffers, %d "
                 "programs, last_rid=%d)", name, len(sess.buffers),
                 len(sess.program_blobs), sess.last_rid)

    # -- HBM accounting ------------------------------------------------------

    def _charge(self, sess: _Session, nbytes: int) -> None:
        if sess.memory_cap and sess.hbm_used + nbytes > sess.memory_cap:
            raise HBMError(
                f"{sess.name}: HBM cap exceeded "
                f"({sess.hbm_used} + {nbytes} > {sess.memory_cap})")
        sess.hbm_used += nbytes

    # -- token gate ----------------------------------------------------------

    def _gated(self, sess: _Session, fn, timing: dict | None = None):
        """Run ``fn()`` under the chip token (Gemini burst semantics).

        ``timing``: if given, ``fn`` records its device-only time there as
        ``exec_ms`` (time after acquiring the device lock) and THAT is what
        gets charged — wall time around ``fn()`` would bill a client for
        waiting on another connection's put/compile holding ``_dlock``,
        blowing its window limit through no usage of its own.

        On quota exhaustion the token is *renewed* — an atomic
        release + re-request in the scheduler — rather than released and
        re-acquired: a release-then-acquire pair would hand the freed token
        to whichever other client happened to be waiting in the gap,
        collapsing request-weighted shares to round-robin (the same hazard
        ``TokenScheduler.renew`` documents). Idle clients return the token
        via the idle timer instead.

        Where a program ends while another tenant waits, the scheduler
        is asked at once who should hold (``renew_or_yield``: the same
        atomic release + re-request, without the wait): the holder stands
        in the weighted pick with what it has used, so a waiter waits for
        the program in flight and never for the rest of a quantum of
        several, its program runs under the holder's turn-around, and a
        holder the pick prefers keeps its burst. With nobody waiting
        nothing is asked and the hold goes on as above. A hold that goes
        on while another session waits lasts its holder's grace
        (:func:`_grace_ms`, learned here from the holder's own gaps between
        a program's end and its next request); the watchdog, woken here,
        hands it on once that has passed with no request, also where the
        pick kept it (:meth:`_yield_graced`).

        A hold marked preempted (``TokenScheduler.preempted``) yields
        here too — this gate sits exactly at a program boundary, so the
        renew forfeits the remaining quantum without ever interrupting
        an execute; the directed-grant queue hands the token to the
        higher-class beneficiary and then straight back.

        Phase accounting (``_PHASE_KEYS``) happens here and in
        :meth:`_run_fn`: the request ``arrived`` at ``sess.arrived_ms``, was
        ``granted`` when the scheduler returned (its ``ks.gate_wait``
        event is written inside ``TokenScheduler.acquire``/``renew``),
        and ``_run_fn`` leaves the ``_dlock`` wait and the idle gap's
        split in ``timing``.
        """
        timing = timing if timing is not None else {}
        arrived = sess.arrived_ms
        with sess.lock:
            if sess.last_end_ms:    # the gap this request ended
                gap = max(arrived - sess.last_end_ms, 0.0)
                sess.gaps.append(gap)
                sess.grace_ms = _grace_ms(sess.gaps, self.idle_release_ms)
                # back before the idle timer would have let go
                sess.kept_early += sess.graced and gap < self.idle_release_ms
                sess.graced = False
            sess.busy = True
            holding = sess.holding
            exhausted = holding and sess.used_ms >= sess.quota_ms
            used = sess.used_ms
        preempted = (holding and not exhausted
                     and self.slicer.should_yield(sess.name))
        try:
            sess.asking = not holding or exhausted or preempted
            if not holding:
                # another session's hold may be idle past its grace
                self._wake.set()
                quota = self.scheduler.acquire(sess.name,
                                               trace_id=sess.trace_id)
            elif exhausted or preempted:
                if preempted:
                    self.slicer.note_yield(sess.name)
                quota = self.scheduler.renew(sess.name, used,
                                             trace_id=sess.trace_id)
            else:
                quota = None
            sess.asking = False
            if quota is not None:
                with sess.lock:
                    sess.holding = True
                    sess.quota_ms = quota
                    sess.used_ms = 0.0
            granted = _now_ms()
            gate_ms = granted - arrived if quota is not None else 0.0
            timing.update(session=sess.name, arrived=arrived,
                          granted=granted)
            # bracket the execute for the chip-time ledger: the hold is
            # granted-active only while fn() runs (getattr: injected
            # schedulers in tests may predate the ledger hooks)
            exec_begin = getattr(self.scheduler, "execute_begin", None)
            if exec_begin is not None:
                exec_begin()
            self.slicer.execute_begin(sess.name)
            try:
                result = fn()
            finally:
                end = _now_ms()
                self.slicer.execute_end(sess.name)
                exec_end = getattr(self.scheduler, "execute_end", None)
                if exec_end is not None:
                    exec_end()
                elapsed = timing.get("exec_ms", end - granted)
                dispatch_ms = timing.get("dispatch_ms", elapsed)
                dlock_ms = timing.get("dlock_ms", 0.0)
                idle = timing.get("idle", (0.0, 0.0, 0.0))
                with sess.lock:
                    sess.used_ms += elapsed
                    sess.exec_count += 1
                    sess.exec_ms_total += elapsed
                    sess.last_end_ms = end
                    ms = sess.phase_ms
                    ms["idle_attach_ms_total"] += idle[0]
                    ms["idle_gate_ms_total"] += idle[1]
                    ms["idle_proxy_ms_total"] += idle[2]
                    ms["dispatch_ms_total"] += dispatch_ms
                    ms["barrier_ms_total"] += elapsed - dispatch_ms
                    holding, used = sess.holding, sess.used_ms
                # still busy: the idle watchdog leaves this hold alone
                # until the boundary's pick is made
                asked = holding and self.scheduler.contended(sess.name)
                quota = None
                if asked:
                    try:
                        quota = self.scheduler.renew_or_yield(sess.name, used)
                    except Exception:   # raced a drop: the hold is gone
                        pass
                with sess.lock:
                    sess.busy = False
                    sess.kept = False
                    if asked and sess.holding:  # its usage is on the books
                        sess.holding = sess.kept = quota is not None
                        sess.quota_ms, sess.used_ms = quota or 0.0, 0.0
                        sess.kept_count += sess.kept
                if sess.kept:   # the waiter waits out this holder's grace
                    self._wake.set()
                if sess.call is not None:
                    sess.call[1] += gate_ms + dlock_ms + elapsed
            return result
        finally:
            sess.asking = False
            # only reached with busy still set when the token gate itself
            # failed (scheduler closed / renew raised) before dispatch
            if sess.busy:
                now = _now_ms()
                with sess.lock:
                    sess.busy = False
                    sess.last_end_ms = now
                if sess.call is not None:   # a wait, not handler work
                    sess.call[1] += now - arrived

    def _watch_idle(self) -> None:
        """Return tokens from clients that stopped executing (one watchdog
        thread for the whole proxy — not a timer per step): every
        ``idle_release_ms / 2`` a hold idle for ``idle_release_ms``, and
        between those ticks, woken by ``_gated``, a hold idle past its
        holder's grace while another session waits (:meth:`_yield_graced`).
        """
        period = max(self.idle_release_ms / 2.0, 1.0) / 1000.0
        tick = time.monotonic() + period
        while not self._stop.is_set():
            with self._slock:
                sessions = list(self._sessions.values())
            due = self._yield_graced(sessions)
            wait = tick - time.monotonic()
            if due is not None:
                wait = min(wait, (due - _now_ms()) / 1000.0)
            self._wake.wait(max(wait, 0.0))
            self._wake.clear()
            if self._stop.is_set() or time.monotonic() < tick:
                continue
            tick = time.monotonic() + period
            now = _now_ms()
            with self._slock:
                sessions = list(self._sessions.values())
            # black-box cadence: proxy population + traffic counters so a
            # dump shows the proxy's recent shape (rate-limited inside)
            flight_default_recorder().sample_deltas("proxy", {
                "sessions": float(len(sessions)),
                "detached": _DETACHED.value(),
                "resumes_total": _RESUMES.value(),
                "detaches_total": _DETACHES.value(),
            })
            for sess in sessions:
                with sess.lock:
                    idle = (sess.holding and not sess.busy
                            and now - sess.last_end_ms >= self.idle_release_ms)
                    if idle:
                        sess.holding = False
                        used = sess.used_ms
                if idle:
                    try:
                        self.scheduler.release(sess.name, used)
                    except Exception:  # raced a drop
                        pass
            # reclaim detached sessions nobody resumed within the grace
            # window — a crashed-for-good client must not park HBM forever
            for sess in sessions:
                if (sess.resume_token and not sess.attached
                        and not sess.migrating
                        and now - sess.detached_at >= self.detach_grace_ms):
                    log.info("detached session %s expired after %.0f ms",
                             sess.name, now - sess.detached_at)
                    self._drop_session(sess.name, purge=True)

    def _yield_graced(self, sessions: list) -> float | None:
        """Hand on each hold that has sat idle past its holder's grace
        while another session waits at the gate, as the idle timer does
        (``scheduler.release``), and write a ``ks.gate_yield`` event with
        the grace and the idle it ended. Returns when the nearest grace
        still running ends (``_now_ms``), or None. A holder whose next
        request has arrived, or that has no grace yet, keeps its hold."""
        askers = {s.name for s in sessions if s.asking}
        if not askers:
            return None
        now, nearest = _now_ms(), None
        for sess in sessions:
            grace = sess.grace_ms
            if grace is None or not askers - {sess.name}:
                continue
            with sess.lock:
                if (not sess.holding or sess.busy
                        or sess.arrived_ms > sess.last_end_ms):
                    continue
                idle = now - sess.last_end_ms
                if idle < grace:
                    due = sess.last_end_ms + grace
                    nearest = due if nearest is None else min(nearest, due)
                    continue
                used, kept = sess.used_ms, sess.kept
                sess.holding, sess.graced = False, kept
                sess.kept_yielded += kept
            try:
                self.scheduler.release(sess.name, used)
            except Exception:  # raced a drop
                pass
            with obs_trace.phase("gate_yield", sess.name, grace_ms=grace,
                                 idle_ms=idle, kept=int(kept)):
                pass
        return nearest

    # -- protocol ------------------------------------------------------------

    def _blob_sink(self, msg: dict, state: dict, nbytes: int):
        """Connection-reader hook (see ``protocol.serve_framed``): land
        ``put_chunk`` payloads straight in the staged buffer, so an upload
        chunk is copied exactly once on the proxy (kernel→staging) instead
        of kernel→scratch→staging — and the recv overlaps the worker
        handling the previous chunk. Any irregularity (unknown session,
        unknown staging id, out-of-range offset) returns None; the payload
        then lands in a scratch buffer and the worker raises the proper
        error with full context."""
        op = msg.get("op")
        if op == "import_buffer_chunk":
            # migration transfers land the same way; the mover addresses
            # the destination session by token, not connection identity
            with self._slock:
                sess = self._by_token.get(str(msg.get("token", "")))
        elif op == "put_chunk":
            name = state.get("name")
            if not name:
                return None
            with self._slock:
                sess = self._sessions.get(name)
        else:
            return None
        if sess is None:
            return None
        try:
            entry = sess.staging.get(int(msg.get("staging", -1)))
            if entry is None:
                return None
            total, raw, _charged = entry
            off = int(msg.get("offset", -1))
        except (TypeError, ValueError):
            return None
        if off < 0 or off + nbytes > total:
            return None
        return memoryview(raw)[off:off + nbytes]

    def _handle_timed(self, req: dict, state: dict) -> dict:
        op = str(req.get("op"))
        t0 = time.perf_counter()
        name = state.get("name") or str(req.get("name", ""))
        # an execute is also the critical-path "execute" segment:
        # server-side service time under the pod's trace, so topcli
        # --critpath can split the client's RPC round-trip into transport
        # vs on-chip work (obs/critpath.py)
        tid = state.get("trace_id", "") if op == "execute" else ""
        try:
            with obs_trace.phase("rpc", name, tid, op=op, proc="chipproxy"):
                return self._handle(req, state)
        finally:
            # unknown ops share one label — a misbehaving client must not
            # mint unbounded series
            _RPC_LAT.observe(op if op in _KNOWN_OPS else "other",
                             value=time.perf_counter() - t0)
            # an execute that reached _dispatch left its session here
            sess = state.pop("executing", None)
            if sess is not None:
                self._note_replied(sess)

    def _note_replied(self, sess: _Session) -> None:
        """Phase stamp ``replied``: close the execute call ``_dispatch``
        opened. The handler's self time is what is left of arrival to
        reply once the gate wait, the ``_dlock`` wait and the device time
        are taken out, all read on this thread from one clock."""
        (arrived, waited), sess.call = sess.call, None
        handler_ms = _now_ms() - arrived
        sess.last_handler_ms = handler_ms
        with sess.lock:
            sess.phase_ms["self_ms_total"] += handler_ms - waited

    def _note_shim(self, sess: _Session, report) -> None:
        """What the tenant's side measured between its previous execute
        send and this one (``protocol.SHIM_KEY``): time in shim and client
        code, the previous execute's round trip, and the turn-around from
        that call's reply to this send. Each is a difference taken inside
        that process; the round trip less this side's handler time for the
        same call is the wire."""
        try:
            shim_ms = float(report.get("shim_ms", 0.0))
            turn_ms = float(report.get("turn_ms", 0.0))
            rtt_ms = report.get("rtt_ms")
            wire_ms = (float(rtt_ms) - sess.last_handler_ms
                       if rtt_ms is not None
                       and sess.last_handler_ms is not None else 0.0)
        except (AttributeError, TypeError, ValueError):
            return      # not the shim's report: serve the call as ever
        with sess.lock:
            sess.phase_ms["shim_ms_total"] += shim_ms
            sess.phase_ms["wire_ms_total"] += wire_ms
            sess.phase_ms["turn_ms_total"] += turn_ms

    def _handle(self, req: dict, state: dict) -> dict:
        op = req.get("op")
        if op == "register":
            return self._handle_register(req, state)
        if op in _ADMIN_OPS:
            return self._handle_admin(op, req, state)

        # Identity is connection-bound: a session is only reachable from the
        # connection that registered it (a client must not be able to burn
        # another client's quota or free its buffers by naming it).
        name = state.get("name")
        if not name:
            raise PermissionError("not registered on this connection")
        sess = self._session(name)
        with sess.lock:     # a session's connections are handled in parallel
            sess.rpc_count += 1

        rid = req.pop(protocol.RID_KEY, None)
        ack = req.pop(protocol.ACK_KEY, None)
        if ack is not None:
            self._prune_replies(sess, int(ack))
        if rid is None:
            return self._dispatch(op, req, sess, state)
        # Resumed-session replay protocol: a rid at or below the handled
        # watermark was (possibly) executed already — answer from the
        # reply cache, or re-execute only when the op is idempotent. A
        # fresh rid executes normally, with errors captured IN-BAND so
        # the failure outcome itself is replayable (a lost error reply
        # must not turn into a second execution on retry).
        rid = int(rid)
        if rid <= sess.last_rid:
            cached = sess.replies.get(rid)
            if cached is not None:
                _REPLAY_SERVED.inc()
                return dict(cached)
            if op in _REPLAY_REEXEC:
                _REPLAY_SERVED.inc()
                return self._dispatch(op, req, sess, state)
            return {"ok": False,
                    "error": f"ReplayError: request {rid} is outside "
                             f"the replay window"}
        try:
            reply = self._dispatch(op, req, sess, state)
        except Exception as e:
            reply = {"ok": False, "error": f"{type(e).__name__}: {e}"}
        sess.last_rid = max(sess.last_rid, rid)
        if state.get("reply_blob") is None:
            # blob-bearing replies (sliced get) are never cached — the op
            # is idempotent and caching would pin payload bytes
            sess.replies[rid] = dict(reply)
            while len(sess.replies) > self.REPLAY_CACHE:
                sess.replies.popitem(last=False)
        if op in _JOURNALED_OPS:
            self._journal_checkpoint(sess)
        return reply

    def _prune_replies(self, sess: _Session, ack: int) -> None:
        while sess.replies:
            rid = next(iter(sess.replies))
            if rid > ack:
                break
            sess.replies.popitem(last=False)

    def _handle_register(self, req: dict, state: dict) -> dict:
        if "resume" in req:
            return self._resume(str(req["resume"]), state)
        if state.get("name"):
            # A second register would orphan the first session at
            # disconnect (cleanup drops only state["name"]).
            raise ValueError(
                f"connection already registered as {state['name']!r}")
        if self._draining:
            raise RuntimeError("proxy is draining; new sessions refused")
        name = req["name"]
        sess = self._register(name, float(req["request"]),
                              float(req["limit"]),
                              int(req.get("memory", 0)),
                              tpu_class=req.get("class", "best-effort"))
        sess.trace_id = state.get("trace_id", "")
        sess.disconnect = state.get("_disconnect")
        state["name"] = name
        reply = {"ok": True, "platforms": [self.platform],
                 "device": str(self.device)}
        if "features" in req:
            # Feature negotiation: granted = requested ∩ supported.
            # The key is echoed ONLY when the client asked — an
            # un-negotiating (old-protocol) peer gets the reply shape
            # it has always gotten, byte-for-byte.
            granted = protocol.negotiate_features(req.get("features") or ())
            sess.features = frozenset(granted)
            reply["features"] = granted
            if "resume" in sess.features:
                token = uuid.uuid4().hex
                sess.resume_token = token
                with self._slock:
                    self._by_token[token] = sess
                reply["resume"] = token
                self._journal_checkpoint(sess)
        return reply

    def _resume(self, token: str, state: dict) -> dict:
        """Re-attach a parked session to this (new) connection. The
        token is the capability; the old connection — if the kernel has
        not reaped it yet — is kicked and its detach awaited, so exactly
        one connection ever owns the session."""
        if state.get("name"):
            raise ValueError(
                f"connection already registered as {state['name']!r}")
        with self._slock:
            moved = self._moved.get(token)
            sess = self._by_token.get(token)
        if moved is not None:
            return {"ok": True, "moved": [moved[0], moved[1]]}
        if sess is None:
            raise KeyError("unknown resume token")
        if sess.migrating:
            raise RuntimeError("session is migrating; retry")
        if sess.attached:
            kick = sess.disconnect
            if kick is not None:
                try:
                    kick()
                except Exception:
                    pass
            if not sess.detach_ev.wait(timeout=5.0):
                raise RuntimeError("session still attached")
            if sess.migrating:
                raise RuntimeError("session is migrating; retry")
        with self._slock:
            sess.attached = True
            sess.detach_ev.clear()
            sess.disconnect = state.get("_disconnect")
            sess.trace_id = state.get("trace_id", sess.trace_id)
        state["name"] = sess.name
        _RESUMES.inc()
        _DETACHED.inc(amount=-1.0)
        flight_default_recorder().note("proxy", "session-resumed",
                                       client=sess.name,
                                       trace_id=sess.trace_id,
                                       last_rid=sess.last_rid)
        log.info("session %s resumed (last_rid=%d)", sess.name,
                 sess.last_rid)
        return {"ok": True, "platforms": [self.platform],
                "device": str(self.device),
                "features": sorted(sess.features), "resume": token,
                "resumed": True, "last_rid": sess.last_rid}

    def _admin_session(self, req: dict) -> _Session:
        token = str(req.get("token", ""))
        with self._slock:
            sess = self._by_token.get(token)
        if sess is None:
            raise KeyError("unknown resume token")
        return sess

    def _handle_admin(self, op, req: dict, state: dict) -> dict:
        """Control-plane ops for drain + live migration. These arrive on
        an UNREGISTERED connection (the mover is scheduler/operator
        tooling, not a client); the resume token is the capability."""
        if op == "drain":
            self.drain()
            return {"ok": True}

        if op == "import_session":
            if self._draining:
                raise RuntimeError("proxy is draining; imports refused")
            m = dict(req["manifest"])
            name, token = str(m["name"]), str(m["token"])
            with self._slock:
                if name in self._sessions:
                    raise ValueError(f"session {name!r} already exists")
                if token in self._by_token:
                    raise ValueError("resume token already present")
            self.scheduler.add_client(name, float(m["request"]),
                                      float(m["limit"]),
                                      tpu_class=m.get("class",
                                                      "best-effort"))
            sess = _Session(name, float(m["request"]), float(m["limit"]),
                            int(m.get("memory", 0)))
            sess.features = frozenset(m.get("features", ()))
            sess.tpu_class = m.get("class", "best-effort")
            sess.resume_token = token
            sess.trace_id = str(m.get("trace_id", ""))
            sess.next_id = int(m.get("next_id", 0))
            sess.last_rid = int(m.get("last_rid", 0))
            sess.replies = OrderedDict(
                (int(rid), rep) for rid, rep in m.get("replies", []))
            sess.aborted_staging = {int(s) for s in m.get("staging", [])}
            sess.aborted_staging |= {int(s) for s in m.get("aborted", [])}
            sess.attached = False
            sess.detached_at = _now_ms()
            sess.detach_ev.set()
            with self._slock:
                self._sessions[name] = sess
                self._by_token[token] = sess
            _DETACHED.inc()
            self._journal_checkpoint(sess)
            return {"ok": True}

        sess = self._admin_session(req)

        if op == "migrate_begin":
            # freeze the session: resumes get a retryable refusal while
            # its bytes are in flight, and the old connection (if any) is
            # kicked so no request mutates state under the export
            sess.migrating = True
            if sess.attached:
                kick = sess.disconnect
                if kick is not None:
                    try:
                        kick()
                    except Exception:
                        pass
                if not sess.detach_ev.wait(timeout=5.0):
                    sess.migrating = False
                    raise RuntimeError("session still attached; cannot "
                                       "migrate")
            return {"ok": True}

        if op == "export_session":
            return {"ok": True, "manifest": self._manifest(sess)}

        if op == "export_buffer":
            handle = int(req["handle"])
            buf = sess.buffers[handle]
            if sess.fetch_cache is None or sess.fetch_cache[0] != handle:
                with self._xfer(sess.name, "export_buffer", int(buf.nbytes)):
                    parts = protocol.dump_array_parts(buf)
                sess.fetch_cache = (handle, parts,
                                    protocol.buffers_nbytes(parts))
            _, parts, total = sess.fetch_cache
            off, length = int(req["offset"]), int(req["length"])
            if off < 0 or length <= 0:
                raise ValueError(f"bad slice [{off}, +{length})")
            if off + length >= total:
                sess.fetch_cache = None
            state["reply_blob"] = protocol.slice_buffers(parts, off, length)
            return {"ok": True, "total": total}

        if op == "export_program":
            state["reply_blob"] = [sess.program_blobs[int(req["exec_id"])]]
            return {"ok": True}

        if op == "import_buffer_begin":
            total = int(req["nbytes"])
            if not 0 < total <= (64 << 30):
                raise ValueError(f"bad staged size {total}")
            charged = max(total - 4096, 0)
            self._charge(sess, charged)
            sid = sess.fresh_id()
            sess.staging[sid] = (total, bytearray(total), charged)
            sess.import_handles[sid] = int(req["handle"])
            return {"ok": True, "staging": sid}

        if op == "import_buffer_chunk":
            total, raw, _charged = sess.staging[int(req["staging"])]
            if state.get("blob_sunk"):
                return {"ok": True}
            blob = state["blob"] or b""
            off = int(req["offset"])
            if off < 0 or off + len(blob) > total:
                raise ValueError(
                    f"chunk [{off}, {off + len(blob)}) outside staged "
                    f"{total}")
            raw[off:off + len(blob)] = blob
            return {"ok": True}

        if op == "import_buffer_commit":
            sid = int(req["staging"])
            total, raw, charged = sess.staging.pop(sid)
            handle = sess.import_handles.pop(sid)
            sess.hbm_used -= charged
            arr = load_array(raw, writable=False)
            self._charge(sess, arr.nbytes)
            sess.hbm_used -= arr.nbytes
            with self._xfer(sess.name, "import_buffer", int(arr.nbytes)):
                buf = self._jax.device_put(arr, self.device)
            self._charge(sess, int(buf.nbytes))
            sess.buffers[handle] = buf
            self._journal_buffer(sess, handle, buf)
            self._journal_checkpoint(sess)
            return {"ok": True}

        if op == "import_program":
            _refuse_loop_keys(req, "ncarry")
            self._install_program(sess, state["blob"],
                                  exec_id=int(req["exec_id"]))
            self._journal_checkpoint(sess)
            return {"ok": True}

        if op == "migrate_finish":
            host, port = req["moved"]
            token = sess.resume_token
            with self._slock:
                self._moved[token] = (str(host), int(port))
            self._drop_session(sess.name, purge=True)
            log.info("session %s migrated to %s:%d", sess.name,
                     str(host), int(port))
            return {"ok": True}

        return {"ok": False, "error": f"unknown admin op {op!r}"}

    def _dispatch(self, op, req: dict, sess: _Session, state: dict) -> dict:
        if op == "put":
            return self._put_array(sess,
                                   load_array(state["blob"],
                                              writable=False), op)

        if op == "put_begin":
            # Chunked upload: stage the serialized (.npy) stream host-side
            # across calls, then materialize at commit. Lets a checkpoint-
            # sized array cross a wire whose frame cap is far smaller
            # (≙ the hook's repeated cudaMemcpy slabs in the reference).
            total = int(req["nbytes"])
            if not 0 < total <= (64 << 30):
                raise ValueError(f"bad staged size {total}")
            # The .npy stream is ~nbytes + a <4 KiB header. CHARGE the
            # device-bound portion now (not just check): with windowed
            # streaming many chunks are in flight before the first error
            # reply lands, and with pipelined sessions several staged puts
            # can overlap — an upload that cannot fit under the HBM cap
            # must be refused before gigabytes move, atomically against
            # other reservations. Released at commit (where the real
            # device buffer is re-charged) or abort.
            charged = max(total - 4096, 0)
            self._charge(sess, charged)
            sid = sess.fresh_id()
            sess.staging[sid] = (total, bytearray(total), charged)
            return {"ok": True, "staging": sid}

        if op == "put_chunk":
            inj = _faults.active()
            if inj is not None and inj.should_crash_proxy():
                self.crash()
                raise RuntimeError("fault injection: proxy crashed")
            sid = int(req["staging"])
            if sid in sess.aborted_staging:
                raise RuntimeError(
                    f"staging {sid} invalidated by disconnect; "
                    f"restart upload")
            total, raw, _charged = sess.staging[sid]
            if state.get("blob_sunk"):
                # the connection reader already received the payload
                # straight into `raw` (see _blob_sink) — nothing to copy
                return {"ok": True}
            blob = state["blob"] or b""
            off = int(req["offset"])
            if off < 0 or off + len(blob) > total:
                raise ValueError(
                    f"chunk [{off}, {off + len(blob)}) outside staged {total}")
            raw[off:off + len(blob)] = blob
            return {"ok": True}

        if op == "put_commit":
            sid = int(req["staging"])
            if sid in sess.aborted_staging:
                raise RuntimeError(
                    f"staging {sid} invalidated by disconnect; "
                    f"restart upload")
            total, raw, charged = sess.staging.pop(sid)
            # the put_begin reservation hands over to the real device
            # charge taken by _put_array
            sess.hbm_used -= charged
            # load_array views the bytearray directly — bytes(raw) would
            # double peak host memory on checkpoint-sized uploads
            return self._put_array(sess, load_array(raw, writable=False), op)

        if op == "put_abort":
            sid = int(req["staging"])
            sess.aborted_staging.discard(sid)
            entry = sess.staging.pop(sid, None)
            if entry is not None:
                sess.hbm_used -= entry[2]
            return {"ok": True}

        if op == "get":
            handle = int(req["handle"])
            buf = sess.buffers[handle]
            if "offset" in req:
                # Sliced fetch: serialize once, cache the PARTS (header +
                # a flat view over the device→host copy — dump_array_parts
                # never joins, so caching costs exactly that one copy),
                # serve byte ranges via slice_buffers. The cache is evicted
                # when the final byte is served (or the handle is freed),
                # so at most one host copy lives per session regardless of
                # how the client paces its reads.
                if sess.fetch_cache is None or sess.fetch_cache[0] != handle:
                    with self._xfer(sess.name, "get", int(buf.nbytes)):
                        parts = protocol.dump_array_parts(buf)
                    sess.fetch_cache = (handle, parts,
                                        protocol.buffers_nbytes(parts))
                _, parts, total = sess.fetch_cache
                off, length = int(req["offset"]), int(req["length"])
                if off < 0 or length <= 0:
                    raise ValueError(f"bad slice [{off}, +{length})")
                if off + length >= total:
                    sess.fetch_cache = None
                state["reply_blob"] = protocol.slice_buffers(parts, off,
                                                             length)
                return {"ok": True, "total": total}
            if int(buf.nbytes) > protocol.MAX_FRAME - 4096:
                # An over-frame reply would raise in the server's *send*
                # path, tearing down the connection — and with it the whole
                # session's buffers. Refuse here so the client gets an
                # error reply and keeps its state.
                raise ValueError(
                    f"buffer too large to transfer ({int(buf.nbytes)} bytes);"
                    " fetch it in slices (get with offset/length)")
            with self._xfer(sess.name, "get", int(buf.nbytes)):
                # parts: device→host copy (np.asarray) is the only copy;
                # the reply payload streams straight from that buffer
                state["reply_blob"] = protocol.dump_array_parts(buf)
            return {"ok": True}

        if op == "free":
            self._free_handles(sess, req["handles"])
            return {"ok": True}

        if op == "compile":
            _refuse_loop_keys(req, "ncarry")
            exec_id, out_meta, out_nbytes = self._install_program(
                sess, state["blob"])
            return {"ok": True, "exec_id": exec_id,
                    "out_meta": out_meta, "out_nbytes": out_nbytes}

        if op == "execute":
            # phase stamp ``arrived``; _handle_timed closes the call
            now = sess.arrived_ms = _now_ms()
            sess.call = [now, 0.0]
            state["executing"] = sess
            if protocol.SHIM_KEY in req:
                self._note_shim(sess, req[protocol.SHIM_KEY])
            return self._execute(sess, req, state["blob"])

        if op == "usage":
            with self._slock:
                sessions = {s.name: {"exec_ms_total": s.exec_ms_total,
                                     "exec_count": s.exec_count,
                                     "rpc_count": s.rpc_count,
                                     "inline_in_total": s.inline_in_total,
                                     "inline_out_total": s.inline_out_total,
                                     "out_count": s.out_count,
                                     "out_recycled": s.out_recycled,
                                     "kept_count": s.kept_count,
                                     "kept_yielded": s.kept_yielded,
                                     "kept_early": s.kept_early,
                                     **s.phase_ms}
                            for s in self._sessions.values()}
            return {"ok": True,
                    "used_ms": self.scheduler.window_usage(sess.name),
                    "window_ms": self.scheduler.window_ms,
                    "hbm_used": sess.hbm_used,
                    "exec_count": sess.exec_count,
                    "exec_ms_total": sess.exec_ms_total,
                    # the chip owner's own report: this process is the
                    # only one that can see the device (one process per
                    # chip), so node daemons and chip_smoke.py ask here
                    "chip": {"platform": self.platform,
                             "device_kind": self.device.device_kind,
                             "total_execs": self.total_execs,
                             "sessions": sessions}}

        if op == "unregister":
            # clean exit: the durable record must not outlive the session
            self._drop_session(sess.name, purge=True)
            state.pop("name", None)
            return {"ok": True}

        return {"ok": False, "error": f"unknown op {op!r}"}

    def _free_handles(self, sess: _Session, handles) -> list:
        """Drop the handles; the arrays they held, for a caller that can
        still put their memory to use (refunded all the same)."""
        freed = []
        for handle in map(int, handles):
            buf = self._forget_buffer(sess, handle)
            if buf is not None:
                freed.append(buf)
            if sess.fetch_cache and sess.fetch_cache[0] == handle:
                sess.fetch_cache = None
        return freed

    def _put_array(self, sess: _Session, arr, op: str) -> dict:
        # Pre-check with the host-side size so an over-cap upload is
        # refused before touching the device at all...
        self._charge(sess, arr.nbytes)
        sess.hbm_used -= arr.nbytes
        with self._xfer(sess.name, op, int(arr.nbytes)):
            buf = self._jax.device_put(arr, self.device)
        try:
            # ...then account the *device* buffer: device_put
            # canonicalizes dtypes (e.g. int64→int32 with x64 off), so
            # charging the host size would leak on every put/free cycle.
            self._charge(sess, int(buf.nbytes))
        except HBMError:
            del buf
            raise
        handle = sess.fresh_id()
        sess.buffers[handle] = buf
        self._journal_buffer(sess, handle, buf)
        return {"ok": True, "handle": handle,
                "shape": list(buf.shape), "dtype": str(buf.dtype)}

    def _install_program(self, sess: _Session, blob: bytes,
                         exec_id: int | None = None):
        """Deserialize + register an exported program. Shared by compile
        (fresh exec_id), migration import and journal recovery (caller
        pins the original exec_id so client-held ids stay valid)."""
        import hashlib

        from jax import export
        exported = export.deserialize(blob)
        out_meta = [(list(a.shape), str(a.dtype)) for a in exported.out_avals]
        out_nbytes = sum(
            int(np.prod(shape or [1])) * np.dtype(dtype).itemsize
            for shape, dtype in out_meta)
        in_specs = [self._jax.ShapeDtypeStruct(a.shape, a.dtype)
                    for a in exported.in_avals]
        # Program identity = the STRIPPED StableHLO text: the serialized
        # blob embeds source locations (the client's compile call
        # site!), so hashing it raw would defeat sharing between identical
        # clients started from different scripts/lines. Alias'd locs are
        # `loc(#locN)` refs plus `#locN = loc(...)` definition lines — both
        # carry no program semantics.
        import re
        text = exported.mlir_module()
        text = re.sub(r"^#loc.*$", "", text, flags=re.MULTILINE)
        text = re.sub(r"loc\(#loc\d*\)", "", text)
        sha = hashlib.sha256(text.encode()).hexdigest()
        with self._slock:
            prog = self._programs.pop(sha, None) or _Program()
            self._programs[sha] = prog      # (re-)insert at MRU position
            while len(self._programs) > self._programs_cap:
                # Live _Executables keep their direct prog reference;
                # eviction only stops FUTURE compiles from sharing it.
                self._programs.pop(next(iter(self._programs)))
        in_meta = [(tuple(a.shape), np.dtype(a.dtype))
                   for a in exported.in_avals]
        out_sizes = [int(np.prod(shape or [1])) * np.dtype(dtype).itemsize
                     for shape, dtype in out_meta]
        nonempty = [(n, i) for i, n in enumerate(out_sizes) if n > 0]
        # the smallest; of several that small the LAST (a step returns
        # its loss after its state, whose step count is as small)
        least = min(nonempty, key=lambda ni: (ni[0], -ni[1]), default=None)
        sync_out = ((-1, False) if least is None
                    else (least[1], least[0] > protocol.INLINE_MAX))
        recycle_meta = [key for key in ((tuple(shape), np.dtype(dtype))
                                        for shape, dtype in out_meta)
                        if key in in_meta]
        if exec_id is None:
            exec_id = sess.fresh_id()
        sess.executables[exec_id] = _Executable(
            exec_id, exported.call, in_specs, out_nbytes, out_meta,
            prog=prog, in_meta=in_meta, sync_out=sync_out,
            recycle_meta=recycle_meta)
        sess.program_blobs[exec_id] = bytes(blob)
        if sess.resume_token:
            self.journal.save_program(sess.resume_token, exec_id, blob)
        return exec_id, out_meta, out_nbytes

    def _single_fn(self, exe: _Executable):
        """AOT-compile the program (lazily, OUTSIDE the token
        gate — a multi-second XLA compile charged as device usage would
        lock the client out for windows and starve everyone else of the
        token meanwhile).

        A plain wrapper traced by jit, not jit(exported.call): the
        exported-call object itself defeats pjit's C++ fast path, and the
        slow per-call python dispatch re-stages every argument on every
        step.
        """
        if exe.prog.single is None:
            call = exe.call

            def _single(*args):
                return call(*args)

            with self._dlock:
                if exe.prog.single is None:  # racing session lost; reuse
                    exe.prog.single = (real_jit()(_single)
                                       .lower(*exe.in_specs).compile())
        return exe.prog.single

    def _recycle_form(self, exe: _Executable) -> Future:
        """The program's recycling form (``_Program.recycle``), started on
        a thread of its own the first time it is asked for: at the
        program's first call, beside ``single``'s compile, so the two
        compiles (or cache loads) overlap inside set-up and the call whose
        frees first cover its outputs finds it done. Without ``_dlock``:
        a compile drives no device, and holding it would queue ``single``
        behind this one."""
        prog = exe.prog
        if prog.recycle is not None:
            return prog.recycle
        with self._slock:
            fut, start = prog.recycle, prog.recycle is None
            if start:
                fut = prog.recycle = Future()
        if start:
            threading.Thread(target=self._compile_recycle, args=(exe, fut),
                             name="ks-recycle-compile", daemon=True).start()
        return fut

    def _compile_recycle(self, exe: _Executable, fut: Future) -> None:
        """``_recycle(scratch, *args) = call(*args)`` with ``scratch`` (one
        buffer per recyclable output) donated: XLA aliases each to an
        output of its shape and dtype, and the runtime allocates none of
        them. ``keep_unused``: an unread argument is otherwise pruned, and
        its donation with it. ``aliased`` is read from the compiled
        module's ``input_output_alias`` (the scratch's parameters are the
        first ``len(recycle_meta)``): what each call writes into freed
        buffers, whatever the runtime deletes on donation."""
        call = exe.call

        def _recycle(scratch, *args):
            return call(*args)

        try:
            jitted = real_jit()(_recycle, donate_argnums=(0,),
                                keep_unused=True)
            compiled = jitted.lower(
                [self._jax.ShapeDtypeStruct(shape, dtype)
                 for shape, dtype in exe.recycle_meta],
                *exe.in_specs).compile()
            header = compiled.as_text().split("\n", 1)[0]
            aliased = sum(int(param) < len(exe.recycle_meta) for param in
                          _ALIAS_PARAM.findall(header))
            fut.set_result((compiled, aliased))
        except BaseException as exc:    # the call that needs it raises it
            fut.set_exception(exc)

    @staticmethod
    def _scratch(sess: _Session, exe: _Executable, freed: list):
        """A freed array for each recyclable output of ``exe``, matched by
        shape and dtype, or None where the frees do not cover them all. An
        array that a live handle still holds is never given, nor one array
        twice: donation deletes the array object itself."""
        if len(freed) < len(exe.recycle_meta):
            return None
        # a snapshot: another connection of the session may put or free
        taken = {id(buf) for buf in list(sess.buffers.values())}
        pool: dict = {}
        for buf in freed:
            if id(buf) not in taken:
                taken.add(id(buf))
                pool.setdefault((buf.shape, buf.dtype), []).append(buf)
        scratch = []
        for key in exe.recycle_meta:
            if not pool.get(key):
                return None
            scratch.append(pool[key].pop())
        return scratch

    @staticmethod
    def _recycling(compiled, scratch: list):
        """``compiled`` with ``scratch`` as its first argument. The freed
        arrays are let go right after dispatch, while the program runs:
        released after it, ~450 array destructions would hold the
        interpreter lock just as the next tenant's program is dispatched
        (PERF.md, PR 35)."""
        def run(*args):
            outs = compiled(scratch, *args)
            scratch.clear()
            return outs
        return run

    def _execute(self, sess: _Session, req: dict, blob=None) -> dict:
        _refuse_loop_keys(req, "repeat", "chain_steps")
        # handles the tenant dropped since its last request ride in on this
        # one and go first: their memory is back before anything of this
        # call is charged, and their arrays may become its outputs
        freed = self._free_handles(sess, req.get("free", ()))
        exe = sess.executables[int(req["exec_id"])]
        # a small host input came in the frame's blob: it has no handle (a
        # null in ``args``), goes to the device under the program's own
        # _dlock hold and is dropped when the program ends
        inline = self._inline_inputs(req.get("inline", ()), blob)
        args = [None if h is None else sess.buffers[int(h)]
                for h in req["args"]]
        # Validate args BEFORE dispatch: a shape/dtype mismatch must be a
        # clean client error, not a device failure.
        if len(args) != len(exe.in_specs):
            raise ValueError(f"expected {len(exe.in_specs)} args, "
                             f"got {len(args)}")

        def mismatch(i, shape, dtype):
            return ValueError(
                f"arg {i}: got {tuple(shape)}/{dtype}, program expects "
                f"{exe.in_meta[i][0]}/{exe.in_meta[i][1]}")

        # direct tuple/np.dtype comparison against the compile-time
        # in_meta — stringifying dtypes here costs ~10 µs per dispatch
        for i, (buf, (shape, dtype)) in enumerate(zip(args, exe.in_meta)):
            if buf is not None and (tuple(buf.shape) != shape
                                    or buf.dtype != dtype):
                raise mismatch(i, buf.shape, buf.dtype)
        if req["args"].count(None) != len(inline):
            raise ValueError(f"{req['args'].count(None)} nulls in args, "
                             f"{len(inline)} inline inputs")
        canonical = self._jax.dtypes.canonicalize_dtype
        inline_nbytes = 0
        for pos, arr in inline:
            if not (0 <= pos < len(args)) or args[pos] is not None:
                raise ValueError(f"inline input for arg {pos}, which is "
                                 f"no null in args")
            # what device_put will make of it (int64 -> int32 with x64
            # off): the check and the charge are the device buffer's
            dtype = canonical(arr.dtype)
            if (arr.shape, dtype) != exe.in_meta[pos]:
                raise mismatch(pos, arr.shape, dtype)
            inline_nbytes += arr.size * dtype.itemsize
            args[pos] = arr
        donate = [int(h) for h in req.get("donate", [])]
        # output recycling: where this call's frees hold a buffer for
        # every output that matches an input, the recycling form writes
        # those outputs into them; any other call runs the plain program,
        # and its frees go back to the allocator here. A session without
        # "inline" frees by requests of its own, so it never recycles.
        scratch = None
        if exe.recycle_meta and "inline" in sess.features:
            recycle = self._recycle_form(exe)
            scratch = self._scratch(sess, exe, freed)
        del freed
        # Cap check up front — allocation must not happen over-cap even
        # transiently (donated buffers are freed only after success). An
        # inline input is charged like a put, refused before dispatch, and
        # refunded when the call is over, whichever way it ends.
        self._charge(sess, inline_nbytes)
        timing: dict = {}
        try:
            self._charge(sess, exe.out_nbytes)
            try:
                if scratch is None:
                    fn, aliased = self._single_fn(exe), 0
                else:
                    form, aliased = recycle.result()
                    fn = self._recycling(form, scratch)
                outs, read = self._gated(
                    sess, lambda: self._run_fn(fn, args, timing,
                                               exe.sync_out, inline,
                                               aliased),
                    timing)
            except Exception:
                # A token-gate failure (scheduler closed / client removed
                # while waiting) dispatched nothing, and the compiled
                # program aliases no argument the tenant holds (only its
                # scratch: buffers it freed), so a device failure consumed
                # none either: every buffer is intact and only the output
                # charge goes back.
                sess.hbm_used -= exe.out_nbytes
                raise
        finally:
            sess.hbm_used -= inline_nbytes
        sess.inline_in_total += len(inline)
        sess.out_count += len(outs)
        sess.out_recycled += aliased
        with self._slock:   # counter shared across connections
            self.total_execs += 1
        handles = []
        for out in outs:
            handle = sess.fresh_id()
            sess.buffers[handle] = out
            handles.append(handle)
            self._journal_buffer(sess, handle, out)
        for handle in donate:
            self._forget_buffer(sess, handle)
        reply = {"ok": True, "handles": handles}
        if read is not None and "inline" in sess.features:
            # the barrier's host read IS this output's value: it goes back
            # with its handle (shape and dtype are out_meta's), as text, so
            # the replay cache and the journal keep the reply whole
            reply["inline"] = [exe.sync_out[0],
                               base64.b64encode(read.tobytes()).decode()]
            sess.inline_out_total += 1
        return reply

    @staticmethod
    def _inline_inputs(spec, blob) -> list:
        """``[(arg position, host array)]`` of an ``execute``'s ``inline``
        key, ``[[position, dtype, shape], ...]``: the arrays' C-order bytes
        lie end to end in the frame's blob, in that order. Input from
        outside the program: a size over ``protocol.INLINE_MAX`` or a blob
        of another length is a clean error before anything is charged."""
        out, off = [], 0
        mv = memoryview(blob if blob is not None else b"")
        for pos, dtype, shape in spec:
            dtype = np.dtype(dtype)
            if dtype.hasobject:
                raise ValueError("object arrays cannot cross the proxy wire")
            shape = tuple(int(d) for d in shape)
            nbytes = int(np.prod(shape, dtype=np.int64)) * dtype.itemsize
            if nbytes > protocol.INLINE_MAX or off + nbytes > mv.nbytes:
                raise ValueError(
                    f"inline input for arg {pos}: {nbytes} bytes (at most "
                    f"{protocol.INLINE_MAX}, {mv.nbytes - off} left in the "
                    f"blob)")
            out.append((int(pos), np.frombuffer(
                mv[off:off + nbytes], dtype=dtype).reshape(shape)))
            off += nbytes
        if off != mv.nbytes:
            raise ValueError(f"execute blob holds {mv.nbytes} bytes, its "
                             f"inline inputs {off}")
        return out

    def _run_fn(self, fn, args: list, timing: dict, sync_out: tuple,
                inline=(), recycled: int = 0):
        # _dlock inside the token gate: execution is already exclusive per
        # the scheduler, but a concurrent put/get/compile from another
        # connection must not drive the device while this runs. Device
        # time is measured AFTER the lock is ours — the wait belongs to
        # whoever held the lock, not to this client's quota.
        # Phase stamps device_start / device_end bound ``exec_ms``; with
        # ``arrived`` and ``granted`` (left in ``timing`` by _gated) they
        # split the idle gap this program ends. ``dispatched`` (left by
        # _run_to_completion) splits ``exec_ms`` itself into ``dispatch_ms``
        # and what the barrier took. ``recycled`` (outputs written into a
        # freed buffer) is a stat of the ``ks.device`` event.
        who = timing.get("session", "")
        asked = _now_ms()
        with obs_trace.phase("dlock_wait", who):
            self._dlock.acquire()
        try:
            timing["dlock_ms"] = _now_ms() - asked
            if inline:
                # before device_start: the handler's own work, so it is
                # in idle_proxy and self_ms and not in the tenant's exec_ms
                args = list(args)
                # one call for all of them: a put costs the host a few
                # hundred microseconds each way it is made
                put = self._jax.device_put([a for _, a in inline],
                                           self.device)
                for (pos, _), buf in zip(inline, put):
                    args[pos] = buf
            start = _now_ms()
            timing["idle"] = self._split_idle(timing, start)
            self._on_device = timing
            try:
                with obs_trace.phase("device", who, recycled=recycled):
                    result = self._run_to_completion(fn, args, sync_out)
            finally:
                end = self._last_device_end = _now_ms()
                timing["exec_ms"] = end - start
                # a program that failed in its dispatch: all of it
                timing["dispatch_ms"] = timing.get("dispatched", end) - start
        finally:
            self._dlock.release()
        return result

    def _split_idle(self, timing: dict, start: float) -> tuple:
        """The chip's idle gap ``[last program's end, start]`` in three
        parts that sum to it, each bound clamped into the gap: up to the
        request's arrival nobody had asked (**attach**: the tenant's
        turn-around and the wire); from there to the grant a session was
        asking while the token was elsewhere (**gate**, which includes the
        previous holder's turn-around before its renew); from there to
        ``start`` the **proxy**'s own handler work and ``_dlock``. Caller
        holds ``_dlock``. The first program of a chip ends no gap."""
        last = self._last_device_end
        if last is None or "arrived" not in timing:
            return (0.0, 0.0, 0.0)
        arrived = min(max(timing["arrived"], last), start)
        granted = min(max(timing["granted"], arrived), start)
        return (arrived - last, granted - arrived, start - granted)

    def _run_to_completion(self, fn, args: list, sync_out: tuple):
        """``(outputs, read)``: ``read`` is the host value of output
        ``sync_out[0]`` where the barrier read it whole, else None.

        Two phases inside ``ks.device``: ``ks.dispatch`` until the
        executable's call returns (the stamp ``dispatched`` in the call's
        ``timing``, ``_on_device``), then ``ks.barrier`` until the host
        read returns."""
        timing = self._on_device
        who = timing.get("session", "")
        with obs_trace.phase("dispatch", who):
            outs = fn(*args)
        timing["dispatched"] = _now_ms()
        if not isinstance(outs, (list, tuple)):
            outs = [outs]
        with obs_trace.phase("barrier", who):
            # Completion barrier = a host read of the smallest output
            # (kept pending S3). Quota accounting needs the program
            # FINISHED before the clock is read, or a client could
            # queue bursts past its token. A host read cannot complete
            # before the program does, and every output comes from the
            # SAME XLA program, so one read is a barrier for all of
            # them. On the directly attached v5e block_until_ready is
            # a barrier too (PERF.md, PR 21) and ~0.3 ms cheaper per
            # dispatch; S3 decides whether to switch. ``sync_out`` is the
            # pick precomputed at compile time (_Executable.sync_out) —
            # scanning jax .nbytes properties per dispatch costs ~25 µs and
            # this runs per op on the pipelined wire's serial stage.
            idx, big = sync_out
            small = outs[idx] if 0 <= idx < len(outs) else None
            read = None
            if small is None:     # all-empty: block_until_ready only
                self._jax.block_until_ready(outs)
            elif big:
                # Don't haul a big buffer to host just to sync:
                # a 1-element slice is a dependent dispatch that
                # completes strictly after the program.
                np.asarray(small.ravel()[:1])
            else:
                read = np.asarray(small)
        return list(outs), read

    def _cleanup(self, state: dict) -> None:
        if self._crashed:
            # fault-injected hard stop: no graceful teardown — recovery
            # must come from the journal, exactly as after a real crash
            return
        name = state.get("name")
        if not name:
            return
        with self._slock:
            sess = self._sessions.get(name)
        if sess is None:
            return
        if sess.resume_token:
            # resumable session: park it for the grace window instead of
            # dropping — the client is (probably) already re-dialing
            self._detach_session(sess)
        else:
            self._drop_session(name)


def main(argv=None) -> None:
    """``python -m kubeshare_tpu.isolation.proxy -P 49901 ...`` — the
    gem-schd launch shape (``launcher.py:22-32``), owning the chip too."""
    import argparse
    import signal

    from ..constants import BASE_QUOTA_MS, MIN_QUOTA_MS, WINDOW_MS

    from .tokensched import serve as serve_tokens

    parser = argparse.ArgumentParser(prog="kubeshare_tpu.isolation.proxy")
    parser.add_argument("-P", "--port", type=int, default=0)
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("-q", "--base-quota", type=float, default=BASE_QUOTA_MS)
    parser.add_argument("-m", "--min-quota", type=float, default=MIN_QUOTA_MS)
    parser.add_argument("-w", "--window", type=float, default=WINDOW_MS)
    parser.add_argument("-S", "--token-port", type=int, default=-1,
                        help="also serve the token scheduler over TCP for "
                             "pod managers (gem-schd parity); -1 = off, "
                             "0 = ephemeral")
    parser.add_argument("--platform", default="",
                        help="force a JAX platform (e.g. 'cpu' for an "
                             "off-chip rehearsal); same effect as "
                             "JAX_PLATFORMS")
    parser.add_argument("--journal-dir",
                        default=os.environ.get("KUBESHARE_JOURNAL_DIR", ""),
                        help="directory for the durable session journal; "
                             "empty disables on-disk durability")
    parser.add_argument("--remote-write", default="",
                        help="HOST:PORT of the telemetry registry; when "
                             "set, this proxy pushes its metric snapshot "
                             "to the fleet TSDB every --push-period "
                             "seconds (topcli --fleet)")
    parser.add_argument("--push-period", type=float, default=5.0)
    parser.add_argument("--instance", default="",
                        help="instance label for remote-write (default "
                             "node:port)")
    args = parser.parse_args(argv)

    if args.platform:
        import jax
        jax.config.update("jax_platforms", args.platform)

    # this process compiles every tenant's program: one fixed cache
    from ..utils.compilecache import enable_compile_cache
    enable_compile_cache()

    inj = _faults.from_env()
    if inj is not None:
        _faults.install(inj)

    from ..obs.blame import default_blame
    from ..obs.ledger import default_ledger
    sched = TokenScheduler(window_ms=args.window, base_quota_ms=args.base_quota,
                           min_quota_ms=args.min_quota,
                           ledger=default_ledger(), blame=default_blame())
    proxy = ChipProxy(scheduler=sched,
                      journal_dir=args.journal_dir or None)
    server = proxy.serve(args.host, args.port)
    token_server = None
    token_port = ""
    if args.token_port >= 0:
        token_server = serve_tokens(sched, args.host, args.token_port)
        token_port = f" TOKENS {token_server.server_address[1]}"
    writer = None
    if args.remote_write:
        from ..telemetry.registry import RegistryClient
        from ..telemetry.remote_write import RemoteWriter, default_instance
        rw_host, _, rw_port = args.remote_write.rpartition(":")
        writer = RemoteWriter(
            RegistryClient(rw_host or "127.0.0.1", int(rw_port)),
            args.instance or default_instance(server.server_address[1]),
            "chipproxy", period_s=args.push_period).start()
    # which chip this process owns and which token core serves it: the
    # node's daemons and chip_smoke.py read it here instead of opening
    # the chip themselves
    log.info("chip proxy owns %s platform=%s kind=%r; token core %s",
             proxy.device, proxy.platform, proxy.device.device_kind,
             type(sched.core).__name__)
    print(f"READY {server.server_address[1]}{token_port}", flush=True)
    stop = threading.Event()
    signal.signal(signal.SIGTERM, lambda *a: stop.set())
    signal.signal(signal.SIGINT, lambda *a: stop.set())
    stop.wait()
    if writer is not None:
        writer.stop()
    if token_server is not None:
        token_server.shutdown()
        token_server.server_close()
    proxy.close()


if __name__ == "__main__":
    main()
