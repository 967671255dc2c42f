"""From a profiler trace (``.xplane.pb``) to numbers, with nothing but JAX.

    python benchmark/reduce_trace.py <trace dir or .xplane.pb> <out.json>

What it reads (looked at by hand on a v5e trace before this was written;
``tests/`` hold a small recorded one):

- device planes are named ``/device:TPU:<n>``; their line ``XLA Ops`` holds
  one event per executed HLO op, with start and duration in nanoseconds;
- an event's name is its HLO instruction's text; the scope an op was
  compiled under (``jax.named_scope("bench_attn")`` gives
  ``jit(step)/jvp(bench_attn)/jit(_flash_fwd)/.../pallas_call``) is a string
  stat of the event's METADATA, which ``ProfileData`` does not hand out:
  ``event_scopes`` reads it from the file's protobuf wire format directly
  (four message types of tsl's ``xplane.proto``), whoever implements the op;
- host threads are lines of the plane ``/host:CPU``.

``busy_s`` is the union of the op intervals of a device's ``XLA Ops`` line,
averaged over the devices; an idle gap is a hole in that union, named by
the shortest host event that spans (nearly) all of it, or ``unattributed``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import sys

SCOPES = ("bench_attn", "bench_opt")
OPS_LINE = "XLA Ops"
DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
TOP = 10
MIN_GAP_NS = 1000       # shorter holes are the sequencer, not the host


def find_xplane(path: str) -> str:
    if os.path.isfile(path):
        return path
    found = sorted(glob.glob(os.path.join(path, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise SystemExit(f"no .xplane.pb under {path}")
    return found[-1]


# -- the metadata ProfileData leaves out --------------------------------------
# tsl/profiler/protobuf/xplane.proto: XSpace{planes=1}; XPlane{name=2,
# event_metadata=4 (map), stat_metadata=5 (map)}; XEventMetadata{name=2,
# stats=5}; XStat{str_value=5, ref_value=7}; XStatMetadata{name=2}.

def _fields(buf: bytes):
    """``(field number, wire type, value)`` of one protobuf message."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        field, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire == 1:
            value, i = buf[i:i + 8], i + 8
        elif wire == 5:
            value, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield field, wire, value


def _varint(buf: bytes, i: int) -> tuple[int, int]:
    shift = value = 0
    while True:
        b = buf[i]
        i += 1
        value |= (b & 0x7F) << shift
        if not b & 0x80:
            return value, i
        shift += 7


def _map_values(entries: list[bytes]):
    for entry in entries:
        for field, _wire, value in _fields(entry):
            if field == 2:
                yield value


def event_scopes(xplane: str) -> dict[str, dict[str, str]]:
    """``{plane name: {event name: its metadata's string stats, joined}}``
    for the device planes: where an op's scope path lives."""
    with open(xplane, "rb") as f:
        space = f.read()
    out: dict[str, dict[str, str]] = {}
    for field, _wire, plane in _fields(space):
        if field != 1:
            continue
        name, events, stat_names = "", [], {}
        for pf, _w, value in _fields(plane):
            if pf == 2:
                name = value.decode(errors="replace")
            elif pf == 4:
                events.append(value)
            elif pf == 5:
                for meta in _map_values([value]):
                    sid, sname = 0, ""
                    for mf, _w2, mv in _fields(meta):
                        if mf == 1:
                            sid = mv
                        elif mf == 2:
                            sname = mv.decode(errors="replace")
                    stat_names[sid] = sname
        if not name.startswith(DEVICE_PREFIX):
            continue
        table = out.setdefault(name, {})
        for meta in _map_values(events):
            ev_name, texts = "", []
            for mf, _w2, mv in _fields(meta):
                if mf == 2:
                    ev_name = mv.decode(errors="replace")
                elif mf == 5:
                    for sf, _w3, sv in _fields(mv):
                        if sf == 5:
                            texts.append(sv.decode(errors="replace"))
                        elif sf == 7:
                            texts.append(stat_names.get(sv, ""))
            table[ev_name] = " ".join(texts)
    return out


def _scope_of(event_name: str, table: dict[str, str]) -> str | None:
    text = table.get(event_name, "")
    for scope in SCOPES:
        if scope in text or scope in event_name:
            return scope
    return None


def _union(intervals: list[tuple[float, float]]):
    """Merged intervals and the gaps between them."""
    merged: list[list[float]] = []
    for lo, hi in sorted(intervals):
        if merged and lo <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], hi)
        else:
            merged.append([lo, hi])
    gaps = [(a[1], b[0]) for a, b in zip(merged, merged[1:])]
    return merged, gaps


def reduce(xplane: str) -> dict:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(xplane)
    tables = event_scopes(xplane)
    devices, host_events = [], []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [ln for ln in plane.lines if ln.name == OPS_LINE]
            devices.append((tables.get(plane.name, {}), lines))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host_events.append((ev.start_ns,
                                            ev.start_ns + ev.duration_ns,
                                            ev.name, line.name))
    busy, by_op, scopes, all_gaps, op_scope = [], {}, {}, [], {}
    for table, lines in devices:
        intervals, scope_cache = [], {}
        for line in lines:
            for ev in line.events:
                lo, hi = ev.start_ns, ev.start_ns + ev.duration_ns
                intervals.append((lo, hi))
                by_op[ev.name] = by_op.get(ev.name, 0.0) + ev.duration_ns
                if ev.name not in scope_cache:
                    scope_cache[ev.name] = _scope_of(ev.name, table)
                scope = op_scope[ev.name] = scope_cache[ev.name]
                if scope:
                    s = scopes.setdefault(scope, {"seconds": 0.0,
                                                  "events": 0, "ops": {}})
                    s["seconds"] += ev.duration_ns / 1e9
                    s["events"] += 1
                    kind = ev.name.split(" = ")[0].rstrip(".0123456789")
                    s["ops"][kind] = s["ops"].get(kind, 0) + 1
        merged, gaps = _union(intervals)
        busy.append(sum(hi - lo for lo, hi in merged) / 1e9)
        all_gaps.extend(gaps)
    n = max(1, len(devices))
    idle = []
    all_gaps = [g for g in all_gaps if g[1] - g[0] >= MIN_GAP_NS]
    for lo, hi in sorted(all_gaps, key=lambda g: g[0] - g[1])[:TOP]:
        idle.append([_blame(lo, hi, host_events), (hi - lo) / 1e9])
    # the scopes' totals first, then the single ops that took most time
    total_ns = sum(by_op.values())
    scoped_ns = sum(s["seconds"] for s in scopes.values()) * 1e9
    ops = [(f"all ops under the scope {name} ({s['events']} events)",
            s["seconds"] * 1e9) for name, s in sorted(scopes.items())]
    ops.append(("all ops outside those scopes", total_ns - scoped_ns))
    single = sorted(by_op.items(), key=lambda kv: -kv[1])[:TOP - len(ops)]
    ops += [(_short(name, op_scope.get(name)), ns) for name, ns in single]
    return {"xplane_bytes": os.path.getsize(xplane),
            "devices": len(devices),
            "busy_s": sum(busy) / n if busy else 0.0,
            "device_ops": [[name, ns / 1e9 / n] for name, ns in ops],
            "idle_gaps": idle,
            # averaged over the chips, as busy_s is
            "scopes": {k: dict(v, seconds=v["seconds"] / n)
                       for k, v in scopes.items()}}


_HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9\-]*)\(")


def _short(hlo: str, scope: str | None = None) -> str:
    """An HLO instruction's text cut to what names it: result name, opcode,
    first result shape, a custom call's target, and its scope if it has
    one of ours."""
    m = _HLO.match(hlo)
    if not m:
        return hlo[:100]
    name, shape, opcode = m.groups()
    shape = shape.lstrip("(").split("{")[0]
    extra = ""
    if "custom_call_target=" in hlo:
        extra = " " + hlo.split("custom_call_target=", 1)[1].split(",")[
            0].strip('"}')
    elif "kind=" in hlo:
        extra = " " + hlo.split("kind=", 1)[1].split(",")[0]
    tag = f" [{scope}]" if scope else ""
    return f"{name} {opcode}{extra} {shape}{tag}"[:120]


def _blame(lo: float, hi: float, host_events) -> str:
    """What the host was doing over a device gap: the shortest host event
    that covers nine tenths of it."""
    need, best = 0.9 * (hi - lo), None
    for a, b, name, thread in host_events:
        if min(b, hi) - max(a, lo) >= need:
            if best is None or b - a < best[0]:
                best = (b - a, f"{name} [{thread}]" if thread else name)
    return best[1][:120] if best else "unattributed"


def main(argv) -> None:
    if len(argv) != 3:
        raise SystemExit(__doc__)
    out = reduce(find_xplane(argv[1]))
    with open(argv[2], "w") as f:
        json.dump(out, f)


if __name__ == "__main__":
    main(sys.argv)
