"""Client side of the isolation runtime.

Two pieces, matching the reference's two client obligations
(``pkg/scheduler/pod.go:445-457`` injects both):

- :class:`ProxyClient` — the stand-in for the chip itself. The workload
  process runs JAX on its CPU backend, traces its step with ``jax.export``,
  and ships programs + buffers to the :class:`~.proxy.ChipProxy`; tensors
  live on the proxy as handles (:class:`RemoteBuffer`), so a training loop
  transfers parameters once. This replaces ``libgemhook.so.1``'s CUDA
  interception — a TPU client never owns the chip.
- :class:`ExecutionGate` — the token round-trip for processes that *do* own
  a chip (whole-chip pods, or the proxy itself): call it before every step;
  it acquires quota from its pod manager / token scheduler, measures the
  inter-call elapsed time as device usage, and renews when the quota runs
  dry — exactly the hook ⇄ gem-pmgr ⇄ gem-schd loop
  (``docker/kubeshare-gemini-scheduler/launcher.py:13-19``).
"""

from __future__ import annotations

import base64
import threading
import time
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..obs import metrics as obs_metrics
from ..obs import trace as obs_trace
from ..utils.logger import get_logger
from ..utils.realjit import real_jit
from . import protocol
from .protocol import load_array

log = get_logger("client")

_WINDOW_STALLS = obs_metrics.default_registry().counter(
    "kubeshare_client_window_stalls_total",
    "Times a windowed put/get stream had to block on its oldest in-flight "
    "chunk before submitting the next (transfer credit exhausted — the "
    "wire or the peer is the bottleneck, not this client).", labels=("op",))


class ShimClock:
    """What the tenant's side costs between two ``execute`` sends,
    measured where it is spent and carried on the next ``execute``
    (``protocol.SHIM_KEY``).

    ``with clock:`` around shim code counts the calling thread's CPU time
    there (``time.thread_time``; sections nest, the outermost counts):
    the shim's and the client's own Python and the system calls of the
    ``free``/``put``/``get`` round trips it chooses to make. A wait for a
    reply costs the thread nothing, so none is in it: that time is the
    wire's, the proxy's or, behind its device lock, the neighbour's
    program's. ``replied`` records an ``execute``'s round trip, from the
    send to the reply's coming in (not to the caller's asking for it: an
    async caller may do its own work in between), a difference of
    ``time.monotonic`` inside this process.

    The report also carries ``turn_ms``, the wall-clock turn-around: from
    the previous ``execute``'s reply coming in to this send, on the same
    clock. It holds everything the tenant did between the two calls (the
    shim, its own code, its other round trips, an open-loop tenant's
    sleep), and is sent with ``rtt_ms`` only.
    """

    def __init__(self):
        self._local = threading.local()     # depth, t0 of a thread
        self._mu = threading.Lock()
        self._shim_s = 0.0
        self._sent = 0                      # execute sends so far
        #: (of send number, seconds, when its reply came in)
        self._rtt = (0, 0.0, 0.0)

    def __enter__(self) -> "ShimClock":
        loc = self._local
        depth = getattr(loc, "depth", 0)
        if depth == 0:
            loc.t0 = time.thread_time()
        loc.depth = depth + 1
        return self

    def __exit__(self, *exc) -> None:
        loc = self._local
        loc.depth -= 1
        if loc.depth == 0:
            with self._mu:
                self._shim_s += time.thread_time() - loc.t0

    def send(self) -> tuple[int, float, dict]:
        """An ``execute`` goes out now: its number, the time, and the
        report of what was measured since the one before."""
        loc = self._local
        with self._mu:
            now = time.monotonic()
            if getattr(loc, "depth", 0):    # the open section, so far
                cpu = time.thread_time()
                self._shim_s += cpu - loc.t0
                loc.t0 = cpu
            report = {"shim_ms": round(self._shim_s * 1e3, 3)}
            if self._sent and self._rtt[0] == self._sent:
                report["rtt_ms"] = round(self._rtt[1] * 1e3, 3)
                report["turn_ms"] = round((now - self._rtt[2]) * 1e3, 3)
            self._shim_s = 0.0
            self._sent += 1
            return self._sent, now, report

    def replied(self, number: int, t_send: float, t_reply: float) -> None:
        """``execute`` number ``number``, sent at ``t_send``, had its
        reply come in at ``t_reply``."""
        with self._mu:
            self._rtt = (number, t_reply - t_send, t_reply)


@dataclass(frozen=True)
class RemoteBuffer:
    """A device-resident array on the proxy."""

    handle: int
    shape: tuple[int, ...]
    dtype: str
    #: the array's value, where the ``execute`` that made it brought it
    #: back in its reply (the proxy's completion barrier had read it):
    #: ``ProxyClient.get`` answers from it without a request
    value: "np.ndarray | None" = field(default=None, compare=False,
                                       repr=False)

    @property
    def nbytes(self) -> int:
        n = 1
        for d in self.shape:
            n *= d
        return n * np.dtype(self.dtype).itemsize


class RemoteFuture:
    """A not-yet-resolved result of an async proxy dispatch
    (:meth:`ProxyClient.execute_async` / ``call_async``).

    ``result()`` blocks until the reply arrives, raises the remote error
    if the op failed, and maps the reply exactly once (subsequent calls
    return/raise the cached outcome). On a lockstep (un-pipelined)
    connection the dispatch already completed synchronously and
    ``result()`` just unwraps it — caller code is mode-agnostic.
    """

    __slots__ = ("_resolve", "_pending", "_mu", "_done", "_value", "_exc")

    def __init__(self, resolve, pending: "protocol.PendingReply | None" = None):
        self._resolve = resolve        # () -> value; blocks, may raise
        self._pending = pending
        self._mu = threading.Lock()
        self._done = False
        self._value = None
        self._exc: Exception | None = None

    def done(self) -> bool:
        with self._mu:
            if self._done:
                return True
        return self._pending is None or self._pending.done()

    def result(self):
        with self._mu:
            if not self._done:
                try:
                    self._value = self._resolve()
                except Exception as e:
                    self._exc = e
                self._done = True
                self._resolve = None   # drop captured state
            if self._exc is not None:
                raise self._exc
            return self._value


class RemoteExecutable:
    """A compiled program on the proxy; call with pytrees of
    :class:`RemoteBuffer` (or host arrays, which are uploaded per call)."""

    def __init__(self, client: "ProxyClient", exec_id: int, in_tree, out_tree,
                 out_meta: list[tuple[list[int], str]]):
        self._client = client
        self._exec_id = exec_id
        self._in_tree = in_tree
        self._out_tree = out_tree
        self.out_meta = out_meta

    def __call__(self, *args, donate: bool = False):
        return self.call_async(*args, donate=donate).result()

    def call_async(self, *args, donate: bool = False) -> RemoteFuture:
        """Dispatch without waiting for completion: the call is ONE
        request where every host leaf is small (it rides inside the
        ``execute``; a larger one is uploaded now, synchronously), the
        execute itself rides the pipelined connection, and the returned
        :class:`RemoteFuture` resolves to the output pytree — so call
        sites overlap dispatch with host work (and with further
        dispatches)."""
        import jax
        leaves = jax.tree_util.tree_leaves(args)
        handles, inline, uploaded = [], [], []
        # donate=True donates every argument that has a handle (uploaded
        # ones included); otherwise per-call uploads are freed afterwards —
        # including on any failure from the upload loop onward (a retried
        # step must not leak its auto-uploads against the HBM cap).
        # Donation frees only after success, so the failure path never
        # double-frees; the failure-path free is best-effort (the failure
        # may have been the connection itself dying — the original error
        # must win). An inline leaf has no handle: the proxy drops it when
        # the program ends, whichever way.
        client = self._client
        can_inline = "inline" in client.features
        try:
            for pos, leaf in enumerate(leaves):
                if isinstance(leaf, RemoteBuffer):
                    handles.append(leaf.handle)
                    continue
                arr = np.asarray(leaf, order="C")
                if (can_inline and arr.nbytes <= protocol.INLINE_MAX
                        and not arr.dtype.hasobject):
                    inline.append((pos, arr))
                    handles.append(None)
                else:
                    buf = client.put(arr)
                    handles.append(buf.handle)
                    uploaded.append(buf)
            fut = client._send_execute(
                self._exec_id, handles, inline=inline,
                donate=[h for h in handles if h is not None] if donate
                else ())
        except Exception:
            if uploaded:
                try:
                    client.free(*uploaded)
                except Exception:
                    pass
            raise

        def resolve():
            try:
                reply = fut.result()
            except Exception:
                if uploaded:
                    try:
                        client.free(*uploaded)
                    except Exception:
                        pass
                raise
            if not donate and uploaded:
                client.free(*uploaded)
            out_bufs = [RemoteBuffer(h, tuple(shape), dtype)
                        for h, (shape, dtype) in zip(reply["handles"],
                                                     self.out_meta)]
            if "inline" in reply:
                idx, text = reply["inline"]
                buf = out_bufs[idx]
                out_bufs[idx] = RemoteBuffer(
                    buf.handle, buf.shape, buf.dtype,
                    np.frombuffer(base64.b64decode(text),
                                  dtype=np.dtype(buf.dtype)
                                  ).reshape(buf.shape))
            return jax.tree_util.tree_unflatten(self._out_tree, out_bufs)

        return RemoteFuture(resolve, fut._pending)


class ProxyClient:
    """Connection to a :class:`~.proxy.ChipProxy` for one named client."""

    def __init__(self, host: str, port: int, name: str, request: float,
                 limit: float, memory: int = 0, timeout: float | None = None,
                 chunk_bytes: int = 64 << 20, trace_id: str = "",
                 reconnect="auto", fault_tag: str = "",
                 tpu_class: str = "best-effort"):
        self.name = name
        #: the tenant side's own cost, reported on every execute
        self.shim_clock = ShimClock()
        #: transfer slab size for put/get; arrays whose serialized form
        #: exceeds it stream in slices, so checkpoint-sized buffers cross a
        #: wire whose frame cap is far smaller than the buffer.
        self.chunk_bytes = chunk_bytes
        register = {
            "op": "register", "name": name, "request": request,
            "limit": limit, "memory": memory,
            # feature negotiation: ask for the pipelined transport and a
            # resume token; an old proxy simply ignores the key and omits
            # it from the reply, leaving this client in lockstep mode
            # with no resilience — exactly the seed behavior
            "features": list(protocol.FEATURES)}
        if tpu_class != "best-effort":
            # per-tenant SLO attribution (sharedtpu/class); sent only when
            # non-default so the wire to an old proxy stays unchanged
            register["class"] = tpu_class
        if reconnect is None:
            # legacy transport: failures surface immediately, no replay —
            # and no resume token either, so a dropped connection frees the
            # session at once instead of parking it for the detach grace
            register["features"] = [f for f in protocol.FEATURES
                                    if f != "resume"]
            self._conn = protocol.Connection(host, port, timeout=timeout,
                                             trace_id=trace_id,
                                             fault_tag=fault_tag)
            reply, _ = self._conn.call(register)
            if "seq" in frozenset(reply.get("features", ())):
                self._conn.start_pipeline()
        else:
            # "auto" (default) or an explicit ReconnectPolicy: wrap the
            # channel so peer death becomes reconnect-and-replay. When
            # the proxy grants no "resume" feature the wrapper degrades
            # to a passthrough, so this is safe against old proxies.
            from ..resilience.reconnect import (ReconnectPolicy,
                                                ResilientConnection)
            policy = (reconnect if isinstance(reconnect, ReconnectPolicy)
                      else None)
            self._conn = ResilientConnection(host, port, timeout=timeout,
                                             trace_id=trace_id,
                                             policy=policy,
                                             fault_tag=fault_tag)
            reply = self._conn.open(register)
        self.platforms: list[str] = reply["platforms"]
        self.device: str = reply.get("device", "")
        #: transport features BOTH ends agreed on at register
        self.features: frozenset[str] = frozenset(reply.get("features", ()))
        #: handles dropped with no request of their own (``free_later``):
        #: they ride on the next ``execute``, or go out before any other
        #: request. A deque, appended to from ``__del__`` on any thread.
        self._deferred: deque = deque()

    # -- buffers -------------------------------------------------------------

    def _chunk(self) -> int:
        # Re-read MAX_FRAME at call time: the headroom must track whatever
        # cap the wire actually enforces (tests shrink it to prove the
        # sliced path; deployments may lower it for memory hygiene).
        return max(1, min(self.chunk_bytes, protocol.MAX_FRAME - 4096))

    @staticmethod
    def _window(chunk: int) -> int:
        """Chunks of transfer credit in flight for windowed put/get:
        enough to keep the wire busy across the reply RTT, but never more
        than ~256 MiB of payload outstanding (the peer buffers in-flight
        chunks; see SERVER_CREDIT for its own bound)."""
        return max(2, min(16, (256 << 20) // max(chunk, 1)))

    def put(self, array) -> RemoteBuffer:
        self._flush_deferred()
        arr = np.asarray(array)
        # parts = [npy header, flat data view]: the payload crosses the
        # socket straight from the array's memory — zero host copies on
        # this side (protocol.dump_array_parts)
        parts = protocol.dump_array_parts(arr)
        nbytes = protocol.buffers_nbytes(parts)
        chunk = self._chunk()
        if nbytes <= chunk:
            reply, _ = self._conn.call({"op": "put", "name": self.name},
                                       blob=parts)
        else:
            try:
                reply = self._put_chunked(parts, nbytes, chunk)
            except RuntimeError as exc:
                if "invalidated by disconnect" not in str(exc):
                    raise
                # the connection died mid-window and the proxy GC'd the
                # half-landed staging (its bytes can never be trusted);
                # the session itself survived — restart the upload once
                # on the recovered channel
                reply = self._put_chunked(parts, nbytes, chunk)
        return RemoteBuffer(reply["handle"], tuple(reply["shape"]),
                            reply["dtype"])

    def _put_chunked(self, parts: list, nbytes: int, chunk: int) -> dict:
        """Staged upload. Pipelined connections stream a WINDOW of chunks
        before the first ack (each landing straight in the proxy's staging
        buffer via its reader-side sink); lockstep connections keep the
        one-chunk-per-RTT loop. Either way the HBM cap was reserved at
        put_begin, so refusal happens before the stream moves."""
        conn = self._conn
        reply0, _ = conn.call({"op": "put_begin", "name": self.name,
                               "nbytes": nbytes})
        sid = reply0["staging"]
        pending: deque = deque()
        try:
            if conn.pipelined:
                window = self._window(chunk)
                for off in range(0, nbytes, chunk):
                    if len(pending) >= window:
                        head = pending.popleft()
                        if not head.done():
                            _WINDOW_STALLS.inc("put")
                        head.result()
                    pending.append(conn.submit(
                        {"op": "put_chunk", "name": self.name,
                         "staging": sid, "offset": off},
                        blob=protocol.slice_buffers(parts, off, chunk)))
                while pending:
                    pending.popleft().result()
            else:
                for off in range(0, nbytes, chunk):
                    conn.call(
                        {"op": "put_chunk", "name": self.name,
                         "staging": sid, "offset": off},
                        blob=protocol.slice_buffers(parts, off, chunk))
            reply, _ = conn.call({"op": "put_commit", "name": self.name,
                                  "staging": sid})
            return reply
        except RuntimeError:
            # Remote-side refusal (HBM cap, bad chunk): drain any
            # remaining window credit (later chunks may have failed too —
            # immaterial now), then drop the staged bytes; the connection
            # itself is still in sync. put_abort works mid-window because
            # the server handles strictly in arrival order.
            while pending:
                try:
                    pending.popleft().result()
                except Exception:
                    pass
            try:
                conn.call({"op": "put_abort", "name": self.name,
                           "staging": sid})
            except Exception:
                pass
            raise

    def get(self, buf: RemoteBuffer) -> np.ndarray:
        if buf.value is not None:
            # came back with the execute's reply; a copy, so the caller's
            # array is writable and its own
            return buf.value.copy()
        self._flush_deferred()
        chunk = self._chunk()
        conn = self._conn
        # The serialized stream is the buffer's bytes plus a <4 KiB .npy
        # header, so its length is known within slack BEFORE the first
        # reply: preallocate the reassembly buffer and receive every
        # chunk — the first included — directly into it (protocol sink),
        # eliminating both client-side copies of the old path.
        est = int(buf.nbytes) + 4096
        raw = bytearray(est)
        mv = memoryview(raw)
        n0 = min(chunk, est)
        reply, part = conn.call({"op": "get", "name": self.name,
                                 "handle": buf.handle,
                                 "offset": 0, "length": n0},
                                sink=mv[:n0])
        assert part is not None
        total = int(reply["total"])
        if total > est:  # header beyond the 4 KiB allowance — never in
            # practice, but never corrupt data over it: restart exact-sized
            raw2 = bytearray(total)
            mv2 = memoryview(raw2)
            mv2[:len(part)] = part
            raw, mv = raw2, mv2
        got = len(part)
        if not (isinstance(part, memoryview) and part.obj is raw):
            # reader fell back to a scratch buffer (sink size mismatch)
            mv[:got] = part
        if got < total:
            if conn.pipelined:
                self._get_windowed(buf, mv, got, total, chunk)
            else:
                off = got
                while off < total:
                    length = min(chunk, total - off)
                    _, part = conn.call(
                        {"op": "get", "name": self.name,
                         "handle": buf.handle, "offset": off,
                         "length": length}, sink=mv[off:off + length])
                    assert part is not None and len(part) > 0
                    if not (isinstance(part, memoryview)
                            and part.obj is raw):
                        mv[off:off + len(part)] = part
                    off += len(part)
        # zero-copy: the array views the reassembly buffer (mutable, so
        # the user-facing result stays writable without a copy); the view
        # is length-exact — trailing slack must not reach np.frombuffer
        return load_array(mv[:total])

    def _get_windowed(self, buf: RemoteBuffer, mv: memoryview, start: int,
                      total: int, chunk: int) -> None:
        """Pipelined tail of a sliced download: keep a window of slice
        requests in flight, each reply landing straight in its offset view
        of the destination. The server returns exactly the requested
        lengths (offsets are deterministic), so submission order is free
        of data dependencies."""
        conn = self._conn
        window = self._window(chunk)
        pending: deque = deque()
        off = start
        while off < total or pending:
            while off < total and len(pending) < window:
                length = min(chunk, total - off)
                pending.append((off, length, conn.submit(
                    {"op": "get", "name": self.name, "handle": buf.handle,
                     "offset": off, "length": length},
                    sink=mv[off:off + length])))
                off += length
            doff, dlen, rep = pending.popleft()
            if not rep.done():
                _WINDOW_STALLS.inc("get")
            _, part = rep.result()
            assert part is not None and len(part) == dlen
            if not (isinstance(part, memoryview) and part.obj is mv.obj):
                mv[doff:doff + dlen] = part

    def free(self, *bufs) -> None:
        self.free_later(*bufs)
        self._flush_deferred()

    def free_later(self, *bufs) -> None:
        """Drop buffers without a request of their own: no I/O here (safe
        from ``__del__``); the handles go out with the next request."""
        for b in bufs:
            if isinstance(b, RemoteBuffer):     # the common, cheap case
                leaves = (b,)
            else:
                import jax
                leaves = [leaf for leaf in jax.tree_util.tree_leaves(b)
                          if isinstance(leaf, RemoteBuffer)]
            for leaf in leaves:
                self._deferred.append(leaf.handle)
                # a freed buffer answers no get: not from the value that
                # came with its execute's reply either
                object.__setattr__(leaf, "value", None)

    def _take_deferred(self) -> list[int]:
        handles = []
        try:
            while True:
                handles.append(self._deferred.popleft())
        except IndexError:
            return handles

    def _flush_deferred(self) -> None:
        """The round trip for queued frees, paid by every request that
        cannot carry them (any but an ``execute`` to a proxy that speaks
        ``inline``), so a tenant that stops calling its programs does not
        keep dead buffers charged."""
        handles = self._take_deferred()
        if handles:
            self._conn.call({"op": "free", "name": self.name,
                             "handles": handles})

    def put_tree(self, tree):
        """Upload a pytree of host arrays → same-shaped tree of buffers."""
        import jax
        return jax.tree_util.tree_map(self.put, tree)

    def get_tree(self, tree):
        import jax
        return jax.tree_util.tree_map(
            lambda b: self.get(b) if isinstance(b, RemoteBuffer) else b, tree)

    # -- programs ------------------------------------------------------------

    def compile(self, fn, *example_args) -> RemoteExecutable:
        """Trace ``fn`` locally (abstract — no local execution), export
        StableHLO for the proxy's platform, and compile it on the proxy's
        chip.

        ``example_args`` may contain host arrays, :class:`RemoteBuffer`\\ s,
        or ``jax.ShapeDtypeStruct``\\ s — only shapes/dtypes matter.
        """
        import jax
        from jax import export

        def spec(leaf):
            if isinstance(leaf, RemoteBuffer):
                return jax.ShapeDtypeStruct(leaf.shape, np.dtype(leaf.dtype))
            if isinstance(leaf, jax.ShapeDtypeStruct):
                return leaf
            arr = np.asarray(leaf)
            return jax.ShapeDtypeStruct(arr.shape, arr.dtype)

        self._flush_deferred()
        flat_specs, in_tree = jax.tree_util.tree_flatten(
            jax.tree_util.tree_map(spec, example_args))
        out_tree_store = []

        def flat_fn(*leaves):
            args = jax.tree_util.tree_unflatten(in_tree, leaves)
            out = fn(*args)
            out_leaves, out_tree = jax.tree_util.tree_flatten(out)
            out_tree_store.append(out_tree)
            return tuple(out_leaves)

        # the genuine jit: attach.py routes workload jits through THIS
        # client, so tracing here must not recurse into its shim
        exported = export.export(
            real_jit()(flat_fn), platforms=list(self.platforms))(*flat_specs)
        reply, _ = self._conn.call({"op": "compile", "name": self.name},
                                   blob=exported.serialize())
        return RemoteExecutable(self, reply["exec_id"], in_tree,
                                out_tree_store[0], reply["out_meta"])

    def execute_async(self, exec_id: int, handles: list[int],
                      donate=(), defer: bool = False) -> "RemoteFuture":
        """Submit an execute without waiting for its reply; the future
        resolves to the output handle list. On a pipelined connection
        many dispatches ride the wire concurrently (the proxy still
        serializes THIS session's ops in submission order, so handle
        dependencies between back-to-back dispatches are safe).

        ``defer=True`` corks the request (see ``Connection.submit``):
        back-to-back small dispatches share one wire write. Call
        ``flush()`` before blocking on a deferred future."""
        fut = self._send_execute(exec_id, handles, donate=donate,
                                 defer=defer)
        return RemoteFuture(lambda: list(fut.result()["handles"]),
                            fut._pending)

    def _send_execute(self, exec_id: int, handles: list, inline=(),
                      donate=(), defer: bool = False) -> "RemoteFuture":
        """One ``execute`` request; the future resolves to its reply.
        ``inline``: ``[(arg position, host array)]`` for the nulls in
        ``handles``; the arrays' bytes are the frame's blob. Queued frees
        ride along where the proxy speaks ``inline``."""
        msg = {"op": "execute", "name": self.name, "exec_id": exec_id,
               "args": handles}
        if donate:
            msg["donate"] = list(donate)
        blob = None
        if inline:
            msg["inline"] = [[pos, str(arr.dtype), list(arr.shape)]
                             for pos, arr in inline]
            blob = [arr.reshape(-1).view(np.uint8) for _, arr in inline
                    if arr.nbytes]
        frees = ()
        if "inline" in self.features:
            frees = self._take_deferred()
            if frees:
                msg["free"] = frees
        else:
            self._flush_deferred()
        clock = self.shim_clock
        number, t_send, msg[protocol.SHIM_KEY] = clock.send()
        tid = getattr(self._conn, "trace_id", "")
        tracer = obs_trace.get_tracer() if tid else None
        t0 = tracer.now_ms() if tracer is not None else 0.0
        def transported():
            # client-measured round trip: the critical-path "transport"
            # segment (the proxy's own "execute" span is subtracted in
            # obs/critpath.py)
            if tracer is not None:
                tracer.record("transport", tid, t0, tracer.now_ms(),
                              proc="client", op="execute")

        try:
            if not self._conn.pipelined:    # lockstep: resolved already
                reply, _ = self._conn.call(msg, blob=blob)
                clock.replied(number, t_send, time.monotonic())
                transported()
                return RemoteFuture(lambda: reply)
            rep = self._conn.submit(msg, blob=blob, defer=defer)
        except protocol.FrameTooLarge:
            self._deferred.extendleft(reversed(frees))  # nothing was sent
            raise

        def resolve():
            reply = rep.result()[0]
            clock.replied(number, t_send, rep.done_at)
            transported()
            return reply

        return RemoteFuture(resolve, rep)

    def flush(self) -> None:
        """Send any corked (``defer=True``) requests now."""
        if self._conn.pipelined:
            self._conn.flush()

    def usage(self) -> dict:
        self._flush_deferred()
        reply, _ = self._conn.call({"op": "usage", "name": self.name})
        return reply

    def set_endpoint(self, host: str, port: int) -> None:
        """Point future reconnects at a different proxy (the migration
        flip). Requires a resilient connection."""
        fn = getattr(self._conn, "set_endpoint", None)
        if fn is None:
            raise RuntimeError(
                "set_endpoint requires reconnect support "
                "(ProxyClient(..., reconnect='auto'))")
        fn(host, port)

    def close(self) -> None:
        if getattr(self._conn, "healthy", True):
            # unregister only over a live channel: tearing down a LOST
            # session would otherwise spend the whole reconnect budget
            # inside close()
            try:
                self._flush_deferred()
                self._conn.call({"op": "unregister", "name": self.name})
            except Exception:
                pass
        self._conn.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


class HbmCap:
    """``tpu_mem`` enforcement for chip-OWNING (gate-mode) processes.

    The reference's hook caps ``gpu_mem`` at allocation time inside every
    shared pod (``pkg/scheduler/pod.go:419-424``; hook built at
    ``docker/kubeshare-gemini-hook-init/Dockerfile:10-14``). On TPU the
    proxy path charges allocations centrally (``proxy.py`` ``_charge``),
    but a gate-mode pod owns its chip — only the owning process can see
    the device allocator, so the check lives here: poll
    ``device.memory_stats()`` and kill the workload with an attributable
    error on breach. Death releases the pod's token via the manager's
    crash-release path, so co-tenants are unharmed; the pod crash-loops
    with a clear message instead of silently starving neighbours of HBM.
    """

    def __init__(self, cap_bytes: int, stats_fn=None,
                 min_poll_interval_s: float = 0.25):
        self.cap_bytes = int(cap_bytes)
        self._stats = stats_fn or self._device_stats
        self._min_poll_s = min_poll_interval_s
        self._last_poll = 0.0
        #: stats have been read successfully at least once — separates
        #: "backend has no allocator stats" (fail closed) from "one poll
        #: failed transiently" (skip, keep running)
        self._supported = False

    @staticmethod
    def _device_stats():
        """Aggregate allocator stats over EVERY locally visible device —
        a pod granted several chips shards across them, and the tpu_mem
        grant covers the pod's total, not chip 0's. Returns None when the
        backend exposes no stats; RAISES on a transport/runtime error
        (the caller treats those differently)."""
        import jax
        per_dev = [d.memory_stats() for d in jax.local_devices()]
        known = [s for s in per_dev if s is not None]
        if not known:
            return None
        return {"bytes_in_use":
                sum(int(s.get("bytes_in_use", 0)) for s in known)}

    def check(self, extra_bytes: int = 0) -> None:
        """Enforce the cap now. ``extra_bytes`` pre-charges a transfer
        about to happen (host→device puts are checked BEFORE the bytes
        land, so a single oversized put cannot OOM co-tenants between
        call-boundary polls)."""
        if not self.cap_bytes:
            return
        try:
            stats = self._stats()
        except Exception as exc:
            if self._supported:
                # The backend HAS stats; this one poll failed. Killing an
                # hours-old healthy pod over one failed poll would be
                # fail-closed in the wrong place — skip this poll. Stamp
                # the throttle so a stats outage degrades to one poll
                # per interval, not one per eager op.
                self._last_poll = time.monotonic()
                log.warning("memory_stats() poll failed transiently "
                            "(%s); skipping this check", exc)
                return
            # First-ever poll: a transient transport error is NOT
            # "backend has no stats" — retry briefly before deciding,
            # and when it still fails, say what actually happened.
            for _ in range(3):
                time.sleep(0.1)
                try:
                    stats = self._stats()
                    break
                except Exception as retry_exc:
                    exc = retry_exc
            else:
                raise SystemExit(
                    f"kubeshare-tpu: tpu_mem={self.cap_bytes} is granted "
                    f"but the allocator stats query keeps failing "
                    f"({exc}) — the HBM cap cannot be enforced in gate "
                    f"mode. Refusing to run unenforced; fix the device "
                    f"runtime or drop sharedtpu/tpu_mem.")
        if stats is None:
            # Fail CLOSED: a backend with no
            # allocator stats cannot enforce tpu_mem — running anyway
            # would silently strip a co-tenant protection on exactly the
            # misconfigured nodes that need it. Same posture as
            # _pin_visible_devices: die loudly, crash-loop with a clear
            # message.
            raise SystemExit(
                f"kubeshare-tpu: tpu_mem={self.cap_bytes} is granted but "
                f"the device backend exposes no memory_stats() — the HBM "
                f"cap cannot be enforced in gate mode. Refusing to run "
                f"unenforced; drop sharedtpu/tpu_mem or use proxy attach "
                f"(centrally metered).")
        self._supported = True
        self._last_poll = time.monotonic()
        used = int(stats.get("bytes_in_use", 0)) + int(extra_bytes)
        if used > self.cap_bytes:
            raise SystemExit(
                f"kubeshare-tpu: HBM cap exceeded: {used} bytes "
                f"{'(incl. pending transfer) ' if extra_bytes else ''}in "
                f"use > tpu_mem={self.cap_bytes} — the pod is over its "
                f"granted share (sharedtpu/tpu_mem); reduce model/batch "
                f"or raise the request")

    def maybe_check(self) -> None:
        """Throttled :meth:`check` for hot paths (the eager-op meter):
        bound the poll rate, not the op rate."""
        if not self.cap_bytes:
            return
        if time.monotonic() - self._last_poll >= self._min_poll_s:
            self.check()


class ExecutionGate:
    """Token gate for a chip-owning process (hook parity).

    Call the gate before every step; the elapsed time between the previous
    call and this one is accounted as device usage. Because JAX dispatch is
    asynchronous, wall time alone under-counts device time — a huge jitted
    program returns immediately — so the workload's dispatched result is
    handed to :meth:`note_dispatch` and the NEXT gate call first blocks on
    it with a host read (the completion barrier, kept pending S3 —
    see ``proxy._run_fn``) before reading the clock. One-step
    pipelining survives; the charge covers real device duration, so one
    giant program cannot buy unlimited runtime for one token (Gemini
    meters actual kernel-burst time, ``launcher.py:78-80``). The gate
    acquires a quota on first use and renews — atomically release +
    re-request — when the measured usage exhausts it.
    """

    def __init__(self, conn: protocol.Connection, name: str):
        self._conn = conn
        self.name = name
        self._quota_ms = 0.0
        self._used_ms = 0.0
        self._last: float | None = None
        self._pending = None
        # The eager-op meter calls the gate from EVERY thread (a prefetch
        # thread's jnp ops race the training thread's steps); quota
        # accounting must stay coherent. An RLock also means every thread
        # blocks through a renew — which is the correct semantics: quota
        # exhausted pauses the whole process, not one thread.
        self._mu = threading.RLock()

    def note_dispatch(self, out) -> None:
        """Record the (possibly still executing) result of the gated call;
        the next gate call charges through its completion."""
        with self._mu:
            self._pending = out

    def _complete_pending(self) -> None:
        # caller holds self._mu
        if self._pending is None:
            return
        pending, self._pending = self._pending, None
        import jax
        leaves = [x for x in jax.tree_util.tree_leaves(pending)
                  if isinstance(x, jax.Array)]
        if not leaves:
            return
        # Host-read the smallest output: XLA materializes outputs when the
        # program finishes, so reading any one is a completion barrier
        # (kept pending S3; see proxy._run_fn).
        leaf = min(leaves, key=lambda a: getattr(a, "size", 1 << 62))
        try:
            np.asarray(leaf)
        except Exception:
            pass  # deleted/donated buffer — the program still completed

    def __call__(self) -> None:
        with self._mu:
            self._complete_pending()
            now = time.monotonic() * 1000.0
            if self._last is not None:
                self._used_ms += now - self._last
            if self._quota_ms <= 0.0:
                reply, _ = self._conn.call({"op": "acquire",
                                            "name": self.name})
                self._quota_ms = reply["quota_ms"]
                self._used_ms = 0.0
            elif self._used_ms >= self._quota_ms:
                reply, _ = self._conn.call({"op": "renew", "name": self.name,
                                            "used_ms": self._used_ms})
                self._quota_ms = reply["quota_ms"]
                self._used_ms = 0.0
            self._last = time.monotonic() * 1000.0

    def close(self) -> None:
        with self._mu:
            if self._quota_ms > 0.0:
                self._complete_pending()
                now = time.monotonic() * 1000.0
                if self._last is not None:
                    self._used_ms += now - self._last
                try:
                    self._conn.call({"op": "release", "name": self.name,
                                     "used_ms": self._used_ms})
                except Exception:
                    pass
                self._quota_ms = 0.0

    @classmethod
    def connect(cls, host: str, port: int, name: str, request: float,
                limit: float, trace_id: str = "") -> "ExecutionGate":
        """Dial a pod manager / token scheduler and register.

        ``trace_id`` (the pod's, from the scheduler binding) rides every
        message so server-side token-grant spans join the pod's timeline.
        """
        conn = protocol.Connection(host, port, trace_id=trace_id)
        conn.call({"op": "register", "name": name, "request": request,
                   "limit": limit})
        return cls(conn, name)
