"""Framed-JSON socket protocol shared by the isolation components.

The reference's runtime wires hook ⇄ gem-pmgr ⇄ gem-schd over localhost TCP
(env ``SCHEDULER_IP/PORT``, ``POD_MANAGER_IP/PORT`` —
``docker/kubeshare-gemini-scheduler/launcher.py:13-19``). Same shape here:
every message is a 4-byte big-endian length followed by a UTF-8 JSON object.
Binary payloads (device buffers crossing the proxy boundary) ride as a raw
byte blob after the JSON header, announced by ``_blob`` (its byte length).

Transport modes (see ``doc/isolation-wire.md`` for the full wire spec):

- **lockstep** (the default, and the only mode un-negotiated peers ever
  see): one request, one reply, strictly alternating. This is the seed
  protocol byte-for-byte.
- **pipelined**: when a peer negotiates the ``"seq"`` feature at
  ``register``, every message carries a ``_seq`` tag and a connection
  becomes a multiplexed stream — many requests in flight, replies
  resolved to per-seq futures by a dedicated reader thread, completion
  possibly out of order from the caller's point of view. Servers always
  speak both: a request with ``_seq`` gets a ``_seq``-tagged reply; a
  request without one gets the classic untagged reply.
"""

from __future__ import annotations

import io
import json
import queue
import socket
import socketserver
import struct
import threading
import time

from ..obs import metrics as _obs_metrics
from ..resilience import faults as _faults

_HDR = struct.Struct(">I")
MAX_FRAME = 1 << 30

#: reserved message key carrying the sender's trace ID (obs/trace.py).
#: Like ``_blob`` it is transport metadata, not part of any op's schema:
#: stripped server-side into ``state["trace_id"]`` before dispatch, so
#: one pod's timeline stitches across the client/proxy/tokensched hops.
TRACE_KEY = "_trace"

#: reserved message key tagging a request/reply pair on a pipelined
#: connection. Assigned by the client, echoed verbatim by the server;
#: never part of any op's schema. Absent on lockstep connections.
SEQ_KEY = "_seq"

#: reserved message key carrying a session-scoped *request id* on a
#: connection that negotiated the ``"resume"`` feature. Unlike ``_seq``
#: (which is per-connection and dies with the socket), ``_rid`` is
#: assigned once per logical request and SURVIVES reconnects: the proxy
#: records the highest rid it has handled per session plus a bounded
#: reply cache, so a replayed request is answered from the cache instead
#: of being executed twice. Stripped by the session layer (the proxy),
#: not the transport — relays that never negotiate ``resume`` never see
#: it. See doc/isolation-wire.md § resume token and replay semantics.
RID_KEY = "_rid"

#: reserved companion to ``_rid``: the highest rid whose reply the
#: client has observed. Lets the server prune its replay cache.
ACK_KEY = "_ack"

#: optional key of an ``execute`` request (of the op's schema: not one of
#: the reserved transport keys above): what the tenant's side measured
#: since its previous ``execute`` send, ``{"shim_ms": <CPU time of the
#: calling thread inside shim and client code>, "rtt_ms": <the previous
#: execute, send to reply coming in>, "turn_ms": <that reply coming in to
#: this send, wall clock>}`` (``rtt_ms`` and ``turn_ms`` only where there
#: was one). Differences taken inside the client process; the proxy adds
#: them to the session's ``shim_ms_total`` / ``wire_ms_total`` /
#: ``turn_ms_total``. A request without it (or without one of its keys),
#: and a proxy that does not know it, behave as they always have.
SHIM_KEY = "shim"

#: a host array of at most this many bytes travels INSIDE an ``execute``
#: (request key ``inline``, its bytes in the frame's blob), and a
#: program's completion-barrier output of at most this many bytes comes
#: back inside the reply (reply key ``inline``): the barrier reads such an
#: output whole, above it only a one-element slice. One constant, so what
#: rides in the call is what the proxy would have read anyway.
INLINE_MAX = 65536

#: transport features this build can negotiate at register time.
#: ``"inline"``: an ``execute`` may carry ``inline`` (small host inputs)
#: and ``free`` (handles to drop first), and its reply may carry ``inline``
#: (the barrier's host read); see doc/isolation-wire.md.
FEATURES = ("resume", "seq", "preempt", "inline")

#: per-connection server credit: requests accepted off the wire but not
#: yet replied to. Bounds the dispatch queue AND the reply queue, so a
#: client that streams faster than the handler drains hits TCP
#: backpressure instead of ballooning server memory.
SERVER_CREDIT = 8

_OBS = _obs_metrics.default_registry()
_INFLIGHT = _OBS.gauge(
    "kubeshare_transport_inflight_requests",
    "Requests accepted by framed-JSON servers but not yet replied to "
    "(dispatch queue + in-handler), summed over live connections.")
_DISPATCH_WAIT = _OBS.histogram(
    "kubeshare_transport_dispatch_wait_seconds",
    "Time a request sat in a connection's dispatch queue between the "
    "reader accepting it and the worker starting it.", labels=("op",))
_HANDLER_BUSY = _OBS.counter(
    "kubeshare_transport_handler_busy_seconds_total",
    "Cumulative wall time spent inside request handlers, per op — the "
    "pipeline-occupancy numerator (rate() against wall time gives the "
    "per-op duty cycle of the server worker).", labels=("op",))


def negotiate_features(requested) -> list:
    """Intersection of a peer's requested features with this build's."""
    return sorted(set(requested) & set(FEATURES))


def dump_array_parts(arr) -> list:
    """numpy array → ``[npy header bytes, raw data buffer]``.

    The parts are sent as separate scatter-gather buffers (``send_msg``
    accepts a list), so the payload is never copied when the input is
    already C-contiguous — the data buffer is a flat memoryview straight
    over the array. ``np.save`` into a growing BytesIO costs several full
    copies; for a 64 MiB buffer this path is the difference between
    memcpy-bound and syscall-bound. Wire format is plain .npy."""
    import numpy as np
    # order="C" (NOT ascontiguousarray, which promotes 0-d scalars to
    # shape-(1,)) — copies only when the input isn't already C-ordered
    arr = np.asarray(arr, order="C")
    if arr.dtype.hasobject:
        # np.save(allow_pickle=False) used to reject these locally;
        # serializing them would stream raw PyObject POINTERS
        raise ValueError("object arrays cannot cross the proxy wire")
    hdr = io.BytesIO()  # write_array_header_* emits magic+version itself
    np.lib.format.write_array_header_2_0(
        hdr, np.lib.format.header_data_from_array_1_0(arr))
    # cast("B") rejects zero-sized views; an empty payload is just b""
    data = memoryview(arr).cast("B") if arr.nbytes else b""
    return [hdr.getvalue(), data]


def dump_array(arr) -> bytes:
    """numpy array → .npy bytes in ONE contiguous buffer (one payload
    copy — the join). Use :func:`dump_array_parts` on send paths; this
    form is for callers that need random byte access (slice caches)."""
    return b"".join(dump_array_parts(arr))


def slice_buffers(parts, offset: int, length: int) -> list:
    """Byte-range ``[offset, offset+length)`` over a logical stream of
    buffers, without materializing the stream — the chunked-put path
    slices header+payload as if they were one blob."""
    out = []
    for p in parts:
        mv = memoryview(p)
        n = mv.nbytes
        if offset >= n:
            offset -= n
            continue
        take = min(length, n - offset)
        out.append(mv[offset:offset + take])
        length -= take
        offset = 0
        if length <= 0:
            break
    return out


def buffers_nbytes(parts) -> int:
    """Total byte length of a list of buffers."""
    return sum(memoryview(p).nbytes for p in parts)


def load_array(blob, writable: bool = True):
    """.npy bytes (or any byte buffer: bytearray, memoryview) → array.

    Parses the header and views the data with ``np.frombuffer`` instead
    of ``np.load``'s read-and-copy (~50 ms → ~1 ms for 64 MiB).
    ``writable=True`` (callers handing the array to user code) returns a
    mutable array — zero-copy when the source buffer is itself mutable
    (the chunked get's reassembly bytearray), one copy otherwise;
    ``writable=False`` returns a READ-ONLY zero-copy view — right for
    paths that immediately copy onward (device puts)."""
    import numpy as np
    mv = memoryview(blob)
    # the npy header is tiny; parse it from a bounded prefix so giant
    # payloads never round-trip through BytesIO
    fp = io.BytesIO(bytes(mv[:min(mv.nbytes, 65536)]))
    version = np.lib.format.read_magic(fp)
    read_header = (np.lib.format.read_array_header_1_0 if version == (1, 0)
                   else np.lib.format.read_array_header_2_0)
    shape, fortran, dtype = read_header(fp)
    if dtype.hasobject:      # never produced by dump_array; be safe
        return np.load(io.BytesIO(bytes(mv)), allow_pickle=False)
    count = 1
    for d in shape:
        count *= d
    arr = np.frombuffer(blob, dtype=dtype, offset=fp.tell(), count=count)
    arr = arr.reshape(shape, order="F" if fortran else "C")
    if writable:
        return arr if arr.flags.writeable else arr.copy()
    if arr.flags.writeable:
        arr.flags.writeable = False
    return arr


class ProtocolError(ConnectionError):
    pass


class FrameTooLarge(ValueError):
    """Raised before any bytes hit the wire — the stream stays in sync, so
    callers must NOT tear down the connection for it (one oversized ``put``
    would otherwise destroy the whole session's device state)."""


def _recv_into(sock: socket.socket, view: memoryview) -> None:
    # Preallocate + recv_into: the naive recv/extend loop tops out well
    # under 0.5 GB/s on loopback (per-chunk temporaries); this path does
    # multi-GB/s and checkpoint-sized buffers ride it. ``view`` may be a
    # slice of the caller's final destination (the chunked get's
    # reassembly buffer, the proxy's staging area) — receiving straight
    # into it is what keeps the transfer path single-copy.
    n = view.nbytes
    got = 0
    while got < n:
        r = sock.recv_into(view[got:], n - got)
        if not r:
            raise ProtocolError("peer closed mid-frame" if got
                                else "peer closed")
        got += r


def _recv_exact(sock: socket.socket, n: int) -> bytearray:
    # Returns the bytearray ITSELF — a bytes(buf) conversion would memcpy
    # the whole frame a second time (load_array views bytearrays
    # zero-copy, and a mutable receive buffer is what its writable=True
    # path wants).
    buf = bytearray(n)
    _recv_into(sock, memoryview(buf))
    return buf


class _RecvStream:
    """Buffered receive side of a socket for the dedicated reader
    threads (client reply reader, server connection reader).

    At pipelined small-op rates many frames sit back-to-back in the
    kernel buffer; reading header and body with separate ``recv``
    syscalls costs two syscalls (plus two GIL round-trips) per message.
    One buffered fill drains a whole burst. Large payloads bypass the
    buffer: any remainder ≥ the buffer size is received STRAIGHT into
    the caller's destination (the zero-copy landing pads still work).

    Only safe where a single thread owns the socket's receive side —
    lockstep connections keep using the unbuffered helpers."""

    CHUNK = 1 << 16

    __slots__ = ("sock", "_buf", "_pos", "_end")

    def __init__(self, sock: socket.socket):
        self.sock = sock
        self._buf = bytearray(self.CHUNK)
        self._pos = 0
        self._end = 0

    def _fill(self) -> None:
        if self._pos == self._end:
            self._pos = self._end = 0
        r = self.sock.recv_into(memoryview(self._buf)[self._end:],
                                len(self._buf) - self._end)
        if not r:
            raise ProtocolError("peer closed")
        self._end += r

    def recv_into(self, view: memoryview) -> None:
        n = view.nbytes
        got = min(self._end - self._pos, n)
        if got:
            view[:got] = memoryview(self._buf)[self._pos:self._pos + got]
            self._pos += got
        while got < n:
            rem = n - got
            if rem >= self.CHUNK:
                # big remainder: land it directly, no staging copy
                r = self.sock.recv_into(view[got:], rem)
                if not r:
                    raise ProtocolError("peer closed mid-frame")
                got += r
                continue
            try:
                self._fill()
            except ProtocolError:
                raise ProtocolError("peer closed mid-frame" if got
                                    else "peer closed") from None
            take = min(self._end - self._pos, rem)
            view[got:got + take] = \
                memoryview(self._buf)[self._pos:self._pos + take]
            self._pos += take
            got += take

    def recv_exact(self, n: int) -> bytearray:
        buf = bytearray(n)
        self.recv_into(memoryview(buf))
        return buf


def _as_byte_views(parts) -> list:
    out = []
    for p in parts:
        mv = p if isinstance(p, memoryview) else memoryview(p)
        if mv.nbytes == 0:
            continue
        if mv.ndim != 1 or mv.format != "B":
            try:
                mv = mv.cast("B")
            except (TypeError, ValueError):   # non-contiguous: last resort
                mv = memoryview(bytes(mv))
        out.append(mv)
    return out


def _send_buffers(sock: socket.socket, parts) -> None:
    """Scatter-gather send: header + JSON + every blob part in one
    ``sendmsg`` syscall (vs one ``sendall`` each). Loops on partial
    sends — ``sendmsg`` is not all-or-nothing for payloads larger than
    the socket buffer."""
    bufs = _as_byte_views(parts)
    while bufs:
        sent = sock.sendmsg(bufs)
        while sent:
            head = bufs[0]
            if head.nbytes <= sent:
                sent -= head.nbytes
                bufs.pop(0)
            else:
                bufs[0] = head[sent:]
                sent = 0


def _frame(msg: dict, blob=None) -> list:
    """Wire parts for one message: ``[header+JSON, *blob parts]``.
    Raises :class:`FrameTooLarge` BEFORE anything could hit the wire."""
    parts: list = []
    nblob = 0
    if blob is not None:
        parts = list(blob) if isinstance(blob, (list, tuple)) else [blob]
        nblob = buffers_nbytes(parts)
        if nblob > MAX_FRAME:
            raise FrameTooLarge(f"blob too large: {nblob}")
        msg = dict(msg, _blob=nblob)
    # default separators on purpose: the seed wire format is frozen
    # byte-for-byte for un-negotiated peers, and the native relay
    # (podmgr_relay.cpp) string-matches replies including whitespace
    data = json.dumps(msg).encode()
    if len(data) > MAX_FRAME:
        raise FrameTooLarge(f"frame too large: {len(data)}")
    return [_HDR.pack(len(data)) + data, *parts]


def send_msg(sock: socket.socket, msg: dict, blob=None) -> None:
    """``blob`` may be bytes, any buffer (memoryview), or a LIST of
    buffers (``dump_array_parts`` output) — each sent as-is after the
    JSON frame, never concatenated (a join would copy the whole
    payload). Length accounting is BYTES (``nbytes``), never element
    count — a non-byte memoryview would otherwise desync the framing."""
    _send_buffers(sock, _frame(msg, blob))


def recv_msg(sock: socket.socket, sink=None) -> tuple:
    """Receive one message. ``sink``: optional writable buffer; when the
    reply is ok and its blob fits, the payload is received DIRECTLY into
    ``sink`` (returned blob is the filled ``memoryview``) — the
    zero-copy landing pad for chunked downloads."""
    (size,) = _HDR.unpack(_recv_exact(sock, _HDR.size))
    if size > MAX_FRAME:
        raise ProtocolError(f"frame too large: {size}")
    msg = json.loads(_recv_exact(sock, size))
    blob = None
    if "_blob" in msg:
        blob_len = int(msg.pop("_blob"))
        if not 0 <= blob_len <= MAX_FRAME:
            raise ProtocolError(f"blob too large: {blob_len}")
        dest = None
        if sink is not None and msg.get("ok", True):
            mv = memoryview(sink)
            if blob_len <= mv.nbytes:
                dest = mv[:blob_len]
        if dest is not None:
            _recv_into(sock, dest)
            blob = dest
        else:
            blob = _recv_exact(sock, blob_len)
    return msg, blob


class PendingReply:
    """One in-flight request's reply slot on a pipelined connection —
    a minimal future resolved by the connection's reader thread.

    All of a connection's futures share ONE condition variable (the
    connection passes its own): a per-future ``threading.Event`` costs an
    Event + Condition + two lock allocations per request, which is real
    money at pipelined small-op rates, and a windowed caller only ever
    blocks on one future at a time anyway."""

    __slots__ = ("sink", "done_at", "_cond", "_done", "_msg", "_blob",
                 "_err")

    def __init__(self, sink=None, cond: threading.Condition | None = None):
        self.sink = sink
        #: ``time.monotonic()`` when the reply came in (0.0 until then):
        #: a round trip ends there, whenever the caller asks for it
        self.done_at = 0.0
        self._cond = cond if cond is not None else threading.Condition()
        self._done = False
        self._msg = None
        self._blob = None
        self._err: Exception | None = None

    def _resolve(self, msg: dict, blob) -> None:
        with self._cond:
            self._msg = msg
            self._blob = blob
            self.done_at = time.monotonic()
            self._done = True
            self._cond.notify_all()

    def _fail(self, err: Exception) -> None:
        with self._cond:
            self._err = err
            self._done = True
            self._cond.notify_all()

    def done(self) -> bool:
        return self._done

    def wait(self, timeout: float | None = None) -> bool:
        if self._done:
            return True
        with self._cond:
            return self._cond.wait_for(lambda: self._done, timeout)

    def result(self, timeout: float | None = None) -> tuple:
        """Block for the reply; same contract as ``Connection.call``:
        raises the transport error if the connection died, RuntimeError
        if the peer replied ``ok: false``."""
        if not self.wait(timeout):
            raise TimeoutError("no reply within timeout")
        if self._err is not None:
            raise self._err
        if not self._msg.get("ok", False):
            raise RuntimeError(self._msg.get("error", "remote error"))
        return self._msg, self._blob


class Connection:
    """Client-side request/reply channel.

    Starts in lockstep mode (request, reply, repeat — the seed wire
    behavior, what un-negotiated peers expect). After the application
    negotiates the ``"seq"`` feature it calls :meth:`start_pipeline`:
    from then on the connection is multiplexed — :meth:`submit` tags
    each request with a fresh ``_seq`` and returns a
    :class:`PendingReply`; a dedicated reader thread resolves replies to
    their futures as they arrive, so many requests ride the wire
    concurrently and a slow op never blocks the channel."""

    def __init__(self, host: str, port: int, timeout: float | None = None,
                 trace_id: str = "", fault_tag: str = ""):
        self.sock = socket.create_connection((host, port), timeout=timeout)
        self.sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self.trace_id = trace_id
        #: label for the fault injector's connection-kill filter
        #: (resilience/faults.py) — lets a test target e.g. only a pod
        #: manager's upstream connections. Inert without an injector.
        self.fault_tag = fault_tag
        self._lock = threading.Lock()        # wire write / lockstep RTT
        self._plock = threading.Lock()       # pending table + liveness
        self._cond = threading.Condition()   # shared by all PendingReplys
        self._pending: dict[int, PendingReply] = {}
        self._outbox: list = []              # corked frames (under _lock)
        self._ncorked = 0
        self._next_seq = 0
        self._reader: threading.Thread | None = None
        self._broken: Exception | None = None

    @property
    def pipelined(self) -> bool:
        return self._reader is not None

    def start_pipeline(self) -> None:
        """Switch to multiplexed mode. Call ONLY after the peer
        negotiated ``"seq"`` — an old peer would reply untagged and the
        reader would (correctly) tear the connection down."""
        if self._reader is not None:
            return
        # the reader legitimately idles between replies; a dial timeout
        # left on the socket would kill healthy idle connections
        self.sock.settimeout(None)
        self._reader = threading.Thread(target=self._read_loop, daemon=True,
                                        name="conn-reader")
        self._reader.start()

    #: deferred submits auto-flush once this many frames are corked —
    #: bounds the latency a corked request can sit in the outbox
    CORK_FRAMES = 16

    def submit(self, msg: dict, blob=None, sink=None,
               defer: bool = False) -> PendingReply:
        """Send one request on a pipelined connection; returns its
        future. ``sink``: optional writable buffer the reply's blob is
        received into (see :func:`recv_msg`).

        ``defer=True`` corks the frame instead of sending it: it is
        buffered and goes out in ONE scatter-gather write with its
        neighbors — on the next non-deferred submit, an explicit
        :meth:`flush`, or automatically after ``CORK_FRAMES`` corked
        frames. User-space corking is what makes a window of small ops
        cost one syscall (and one peer wakeup) per batch instead of per
        op. A caller that defers MUST flush before blocking on a corked
        request's future, or it waits on a frame still in the outbox."""
        if self._reader is None:
            raise RuntimeError("connection is not pipelined "
                               "(peer did not negotiate 'seq')")
        rep = PendingReply(sink, cond=self._cond)
        with self._plock:
            if self._broken is not None:
                raise ProtocolError(f"connection broken: {self._broken}")
            self._next_seq += 1
            seq = self._next_seq
            self._pending[seq] = rep
        wire = {**msg, SEQ_KEY: seq}
        if self.trace_id and TRACE_KEY not in msg:
            wire[TRACE_KEY] = self.trace_id
        try:
            parts = _frame(wire, blob)   # FrameTooLarge before any buffering
            with self._lock:
                # frames always go through the outbox so corked requests
                # keep submission order on the wire
                self._outbox.extend(parts)
                self._ncorked += 1
                if not defer or self._ncorked >= self.CORK_FRAMES:
                    bufs, self._outbox = self._outbox, []
                    self._ncorked = 0
                    _send_buffers(self.sock, bufs)
        except FrameTooLarge:
            # nothing hit the wire — the stream is intact, just unregister
            with self._plock:
                self._pending.pop(seq, None)
            raise
        except OSError as e:
            self._break(e)
            raise
        self._maybe_kill_after_send()
        return rep

    def _maybe_kill_after_send(self, nframes: int = 1) -> None:
        """Fault-injection hook, called after a request's bytes left.
        Killing *after* the send models the ambiguous failure — the peer
        may or may not have handled the request — which is the case
        reconnect-and-replay exists for. No-op without an injector."""
        inj = _faults.active()
        if inj is None:
            return
        if inj.should_kill_connection(self.fault_tag, nframes):
            if self._reader is not None:
                self._break(ProtocolError("fault injection: connection "
                                          "killed"))
            else:
                try:
                    self.sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

    def flush(self) -> None:
        """Send every corked frame (no-op when the outbox is empty)."""
        try:
            with self._lock:
                if not self._outbox:
                    return
                bufs, self._outbox = self._outbox, []
                self._ncorked = 0
                _send_buffers(self.sock, bufs)
        except OSError as e:
            self._break(e)
            raise

    def call(self, msg: dict, blob=None, sink=None) -> tuple:
        if self._reader is not None:
            return self.submit(msg, blob, sink=sink).result()
        if self.trace_id and TRACE_KEY not in msg:
            msg = dict(msg, **{TRACE_KEY: self.trace_id})
        with self._lock:
            try:
                send_msg(self.sock, msg, blob)
                self._maybe_kill_after_send()
                reply, rblob = recv_msg(self.sock, sink=sink)
            except OSError:
                # Fail-stop: a timeout or error mid-exchange leaves the
                # stream desynced (the next recv would read this request's
                # stale reply) — kill the channel rather than corrupt it.
                self.close()
                raise
        if not reply.get("ok", False):
            raise RuntimeError(reply.get("error", "remote error"))
        return reply, rblob

    def _read_loop(self) -> None:
        stream = _RecvStream(self.sock)
        try:
            while True:
                (size,) = _HDR.unpack(stream.recv_exact(_HDR.size))
                if size > MAX_FRAME:
                    raise ProtocolError(f"frame too large: {size}")
                msg = json.loads(stream.recv_exact(size))
                seq = msg.pop(SEQ_KEY, None)
                with self._plock:
                    rep = self._pending.pop(seq, None)
                if rep is None:
                    raise ProtocolError(f"reply for unknown seq {seq!r}")
                blob = None
                if "_blob" in msg:
                    blob_len = int(msg.pop("_blob"))
                    if not 0 <= blob_len <= MAX_FRAME:
                        raise ProtocolError(f"blob too large: {blob_len}")
                    dest = None
                    if rep.sink is not None and msg.get("ok", False):
                        mv = memoryview(rep.sink)
                        if blob_len <= mv.nbytes:
                            dest = mv[:blob_len]
                    if dest is not None:
                        stream.recv_into(dest)
                        blob = dest
                    else:
                        blob = stream.recv_exact(blob_len)
                rep._resolve(msg, blob)
        except Exception as e:
            self._break(e)

    def _break(self, exc: Exception) -> None:
        """Fail-stop for the multiplexed stream: mark dead, close the
        socket, fail every outstanding future (each with its OWN
        exception object — a shared instance re-raised from several
        threads would interleave tracebacks)."""
        with self._plock:
            if self._broken is None:
                self._broken = exc
            pending = list(self._pending.values())
            self._pending.clear()
        try:
            # shutdown BEFORE close: the reader thread blocked in recv
            # holds a kernel reference to the socket, so a bare close()
            # would neither wake it nor send FIN until that recv returns
            # (i.e. never) — the peer would see a live connection forever.
            self.sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self.sock.close()
        except OSError:
            pass
        for rep in pending:
            rep._fail(ProtocolError(f"connection broken: {exc}"))

    def close(self) -> None:
        if self._reader is not None:
            self._break(ConnectionError("connection closed"))
            return
        try:
            self.sock.close()
        except OSError:
            pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class FramedServer(socketserver.ThreadingTCPServer):
    allow_reuse_address = True
    daemon_threads = True


def serve_framed(host: str, port: int, handle, cleanup=None,
                 sink=None) -> FramedServer:
    """Start a threaded framed-JSON server.

    ``handle(request: dict, state: dict) -> dict`` runs per message on the
    connection's WORKER thread (``state`` is per-connection, with blob
    bytes under ``state['blob']`` — plus ``state['blob_sunk']`` when the
    payload already landed via ``sink`` — and reply blobs via
    ``state['reply_blob']``); ``cleanup(state)`` runs on disconnect.

    Every connection is a three-stage pipeline: a reader (the connection
    thread) parses frames and queues requests, one worker runs ``handle``
    strictly in arrival order (per-connection state needs no locking),
    and a writer sends replies — so a ``put_chunk``'s payload recv
    overlaps the previous request's handling, and a pipelined client's
    burst of small ops is drained back-to-back instead of one per RTT.
    Accepted-but-unreplied requests are bounded by ``SERVER_CREDIT``
    (a credit the reader takes per request and the writer returns per
    reply): past that, the reader stops accepting and TCP backpressure
    holds the client.

    ``sink(msg, state, nbytes)`` (optional) runs on the READER thread
    after a request's JSON is parsed but before its blob is received;
    returning a writable buffer of exactly ``nbytes`` makes the reader
    receive the payload straight into it (zero-copy landing pad for
    chunked uploads). It must be fast, must not throw for control flow
    (any exception falls back to a fresh buffer), and must tolerate
    running concurrently with the worker.

    Returns the running server — caller owns ``server.shutdown()``; the
    bound port is ``server.server_address[1]``.
    """

    def _recv_request(stream: _RecvStream, state: dict) -> tuple:
        (size,) = _HDR.unpack(stream.recv_exact(_HDR.size))
        if size > MAX_FRAME:
            raise ProtocolError(f"frame too large: {size}")
        msg = json.loads(stream.recv_exact(size))
        seq = msg.pop(SEQ_KEY, None)
        blob = None
        sunk = False
        if "_blob" in msg:
            blob_len = int(msg.pop("_blob"))
            if not 0 <= blob_len <= MAX_FRAME:
                raise ProtocolError(f"blob too large: {blob_len}")
            dest = None
            if sink is not None and blob_len:
                try:
                    dest = sink(msg, state, blob_len)
                except Exception:
                    dest = None
            if dest is not None and memoryview(dest).nbytes == blob_len:
                mv = memoryview(dest)
                stream.recv_into(mv)
                blob = mv
                sunk = True
            else:
                blob = stream.recv_exact(blob_len)
        return seq, msg, blob, sunk

    class Handler(socketserver.BaseRequestHandler):
        def handle(self):
            sock = self.request
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            state: dict = {}
            with self.server._conn_mu:
                self.server._conn_socks.add(sock)

            def _disconnect():
                # Server-initiated kick (migration detaches the old
                # owner; fault tests simulate crashes): shutting down the
                # socket unblocks the reader and runs the normal
                # disconnect path — cleanup semantics identical to the
                # peer dying.
                try:
                    sock.shutdown(socket.SHUT_RDWR)
                except OSError:
                    pass

            #: handlers may stash this to sever the connection later
            state["_disconnect"] = _disconnect
            # SimpleQueue (C-implemented) for the stage handoffs — the
            # per-op cost of a bounded queue.Queue's lock+condition dance
            # is measurable at pipelined small-op rates. Credit (accepted
            # but unreplied ≤ SERVER_CREDIT) is enforced by a semaphore
            # the reader takes per request and the writer returns per
            # reply, which is what turns a runaway client into TCP
            # backpressure instead of server memory growth.
            requests: queue.SimpleQueue = queue.SimpleQueue()
            replies: queue.SimpleQueue = queue.SimpleQueue()
            credit = threading.Semaphore(SERVER_CREDIT)

            def run_worker():
                # Replies are handed to the writer in BATCHES (flushed the
                # moment the request queue runs empty, so a lone request —
                # the lockstep case — is never delayed): waking the writer
                # through the GIL once per reply costs a thread handoff
                # per op, which at pipelined small-op rates is comparable
                # to the handler itself. The batch is naturally bounded by
                # SERVER_CREDIT — the reader stops accepting past that.
                out: list = []
                while True:
                    item = requests.get()
                    if item is None:
                        if out:
                            replies.put(out)
                        replies.put(None)
                        return
                    seq, msg, blob, sunk, t_enq = item
                    op = str(msg.get("op", ""))
                    t0 = time.perf_counter()
                    _DISPATCH_WAIT.observe(op, value=t0 - t_enq)
                    state["blob"] = blob
                    state["blob_sunk"] = sunk
                    state.pop("reply_blob", None)
                    if TRACE_KEY in msg:
                        state["trace_id"] = str(msg.pop(TRACE_KEY))
                    try:
                        reply = handle(msg, state)
                    except Exception as e:  # surfaced to the caller
                        reply = {"ok": False,
                                 "error": f"{type(e).__name__}: {e}"}
                    _HANDLER_BUSY.inc(op, amount=time.perf_counter() - t0)
                    if seq is not None:
                        reply = {**reply, SEQ_KEY: seq}
                    out.append((reply, state.get("reply_blob")))
                    if requests.empty() or len(out) >= SERVER_CREDIT:
                        replies.put(out)
                        out = []

            def run_writer():
                # Replies are drained in a BATCH per wakeup and the whole
                # batch goes out in one scatter-gather send: at pipelined
                # small-op rates the per-reply syscall (and the GIL
                # round-trip around it) is a measurable share of the
                # serial path, and back-to-back replies are the common
                # case whenever the worker runs ahead of the socket.
                dead = False
                stop = False
                while not stop:
                    batch: list = []
                    item = replies.get()
                    while True:
                        if item is None:
                            stop = True
                            break
                        batch.extend(item)   # worker enqueues reply LISTS
                        try:
                            item = replies.get_nowait()
                        except queue.Empty:
                            break
                    if not batch:
                        continue             # lone shutdown sentinel
                    _INFLIGHT.inc(amount=-float(len(batch)))
                    inj = _faults.active()
                    if inj is not None:
                        delay = inj.writer_delay_s()
                        if delay:
                            time.sleep(delay)
                    parts: list = []
                    for reply, rblob in batch:
                        if dead:
                            continue
                        if inj is not None and inj.should_drop_reply(
                                reply.get(SEQ_KEY)):
                            # lost-reply fault: the request WAS handled;
                            # credit accounting is untouched (the batch
                            # length below still counts it)
                            continue
                        try:
                            parts.extend(_frame(reply, rblob))
                        except FrameTooLarge as e:
                            # pre-send refusal: nothing hit the wire, the
                            # stream is in sync — report instead of
                            # leaving the peer waiting on a reply that
                            # never comes
                            err = {"ok": False,
                                   "error": f"FrameTooLarge: {e}"}
                            if SEQ_KEY in reply:
                                err[SEQ_KEY] = reply[SEQ_KEY]
                            parts.extend(_frame(err))
                    if parts and not dead:
                        try:
                            _send_buffers(sock, parts)
                        except OSError:
                            dead = True
                    credit.release(len(batch))

            worker = threading.Thread(target=run_worker, daemon=True,
                                      name="framed-worker")
            writer = threading.Thread(target=run_writer, daemon=True,
                                      name="framed-writer")
            worker.start()
            writer.start()
            stream = _RecvStream(sock)
            try:
                while True:
                    credit.acquire()
                    try:
                        item = _recv_request(stream, state)
                    except (ProtocolError, OSError, ValueError):
                        break
                    _INFLIGHT.inc()
                    requests.put((*item, time.perf_counter()))
            finally:
                # Drain in order: the worker finishes every accepted
                # request (a half-closed peer may still be reading
                # replies), the writer flushes, then cleanup — which must
                # run strictly after the last handler touched state.
                requests.put(None)
                worker.join()
                writer.join()
                with self.server._conn_mu:
                    self.server._conn_socks.discard(sock)
                if cleanup is not None:
                    cleanup(state)

    server = FramedServer((host, port), Handler)
    # live per-connection sockets, for hard-crash fault injection (the
    # proxy's crash() severs every client at once) — and any future
    # admin-initiated mass disconnect
    server._conn_mu = threading.Lock()
    server._conn_socks = set()
    thread = threading.Thread(target=server.serve_forever, daemon=True,
                              name=f"framed-server-{server.server_address[1]}")
    thread.start()
    return server
