"""Scheduler-driven live migration of proxy sessions.

A migration is three wire conversations and one tombstone:

1. **freeze** — ``migrate_begin`` on the source kicks the session's
   connection (if any) and marks it migrating, so resumes are refused
   with a retryable error while its bytes are in flight;
2. **copy** — ``export_session`` hands over the manifest (identity,
   replay state, buffer/program inventory); each buffer streams
   source→destination in chunks (``export_buffer`` slices on one side,
   the ``import_buffer_*`` staging protocol on the other) and each
   compiled program's serialized blob rides ``export_program`` →
   ``import_program`` with its original ``exec_id`` — client-held
   handles and exec ids stay valid verbatim;
3. **flip** — ``migrate_finish`` drops the source copy and leaves a
   ``moved`` tombstone: a client that reconnects to the old address is
   redirected (``{"moved": [host, port]}``) and replays against the
   destination. No client participation is required beyond its normal
   reconnect path.

The mover holds the session's resume token — that IS the capability; it
is never a registered client of either proxy.
"""

from __future__ import annotations

import time

from ..isolation import protocol
from ..obs import metrics as obs_metrics
from ..obs.trace import get_tracer
from ..utils.logger import get_logger

log = get_logger("migrate")

_MIGRATIONS = obs_metrics.default_registry().counter(
    "kubeshare_migrations_total",
    "Session migrations by outcome.", labels=("outcome",))
_MIG_DUR = obs_metrics.default_registry().histogram(
    "kubeshare_migration_duration_seconds",
    "End-to-end session migration time (freeze -> copy -> flip).",
    buckets=(0.01, 0.025, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0))


def migrate_session(source_addr: tuple, dest_addr: tuple, token: str, *,
                    drain: bool = False, chunk_bytes: int = 8 << 20,
                    timeout: float = 10.0, trace_id: str = "") -> dict:
    """Move the session identified by ``token`` from ``source_addr`` to
    ``dest_addr``. Returns the migrated manifest (augmented with
    ``moved`` and ``duration_s``). ``drain=True`` additionally puts the
    whole source proxy into draining (refusing new sessions) first —
    the evacuate-the-chip case.

    Both connections are plain lockstep admin channels: migration is a
    control-plane act, losing it mid-way simply leaves the source
    authoritative (``migrate_finish`` is the only destructive step, and
    it runs last).
    """
    t0 = time.monotonic()
    tracer = get_tracer() if trace_id else None
    span = (tracer.begin("migrate", trace_id, src=f"{source_addr[0]}:"
                         f"{source_addr[1]}", dst=f"{dest_addr[0]}:"
                         f"{dest_addr[1]}") if tracer else None)
    src = protocol.Connection(source_addr[0], int(source_addr[1]),
                              timeout=timeout, trace_id=trace_id)
    try:
        dst = protocol.Connection(dest_addr[0], int(dest_addr[1]),
                                  timeout=timeout, trace_id=trace_id)
    except BaseException:
        src.close()
        raise
    try:
        if drain:
            src.call({"op": "drain"})
        src.call({"op": "migrate_begin", "token": token})
        rep, _ = src.call({"op": "export_session", "token": token})
        manifest = rep["manifest"]
        dst.call({"op": "import_session", "manifest": manifest})
        for spec in manifest.get("buffers", ()):
            _copy_buffer(src, dst, token, spec, chunk_bytes, tracer,
                         trace_id, span)
        for spec in manifest.get("programs", ()):
            exec_id = int(spec["exec_id"])
            _, blob = src.call({"op": "export_program", "token": token,
                                "exec_id": exec_id})
            dst.call({"op": "import_program", "token": token,
                      "exec_id": exec_id}, blob=bytes(blob))
        # the point of no return: source state drops, tombstone goes up
        src.call({"op": "migrate_finish", "token": token,
                  "moved": [dest_addr[0], int(dest_addr[1])]})
    except BaseException:
        _MIGRATIONS.inc("failed")
        if span is not None:
            span.attrs["outcome"] = "failed"
            tracer.finish(span)
        src.close()
        dst.close()
        raise
    duration = time.monotonic() - t0
    _MIGRATIONS.inc("moved")
    _MIG_DUR.observe(value=duration)
    if span is not None:
        span.attrs["outcome"] = "moved"
        span.attrs["buffers"] = len(manifest.get("buffers", ()))
        span.attrs["programs"] = len(manifest.get("programs", ()))
        tracer.finish(span)
    src.close()
    dst.close()
    log.info("migrated session %r (%d buffers, %d programs) "
             "%s:%d -> %s:%d in %.3fs", manifest.get("name"),
             len(manifest.get("buffers", ())),
             len(manifest.get("programs", ())),
             source_addr[0], int(source_addr[1]),
             dest_addr[0], int(dest_addr[1]), duration)
    return dict(manifest, moved=[dest_addr[0], int(dest_addr[1])],
                duration_s=duration)


def _copy_buffer(src: protocol.Connection, dst: protocol.Connection,
                 token: str, spec: dict, chunk_bytes: int, tracer,
                 trace_id: str, parent) -> None:
    """Stream one buffer source→destination without ever materializing
    it whole on the mover: each exported slice is immediately re-sent as
    an import chunk."""
    handle = int(spec["handle"])
    sub = (tracer.begin("migrate.buffer", trace_id,
                        parent_id=parent.span_id if parent else "",
                        handle=handle) if tracer else None)
    off, total, sid = 0, None, None
    while total is None or off < total:
        length = chunk_bytes if total is None else min(chunk_bytes,
                                                       total - off)
        rep, blob = src.call({"op": "export_buffer", "token": token,
                              "handle": handle, "offset": off,
                              "length": length})
        total = int(rep["total"])
        if sid is None:
            brep, _ = dst.call({"op": "import_buffer_begin",
                                "token": token, "handle": handle,
                                "nbytes": total})
            sid = brep["staging"]
        nblob = memoryview(blob).nbytes
        dst.call({"op": "import_buffer_chunk", "token": token,
                  "staging": sid, "offset": off}, blob=blob)
        off += nblob
    dst.call({"op": "import_buffer_commit", "token": token,
              "staging": sid})
    if sub is not None:
        sub.attrs["nbytes"] = total
        tracer.finish(sub)
