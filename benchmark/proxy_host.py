"""Host of the chip proxy for one benchmark run.

Calls the program's own ``kubeshare_tpu.isolation.proxy.main(argv)``
unchanged on the main thread (with the argv ``launcherd.default_proxy_cmd``
builds) and keeps ONE side thread of the benchmark's. Only the process
that owns the chip can see it, and the proxy has no option that starts a
device trace or reports its registry (PERF.md lists both for the
``tracing`` issue), so the side thread does what a benchmark file may:

- once the proxy is READY it writes ``<rundir>/chip.json``: the device as
  JAX reports it here and the allocator's ``bytes_limit``;
- at the window's two ends (``go.json``: times on CLOCK_MONOTONIC) it
  snapshots ``obs.metrics.collect_default()``,
  ``utils.compilecache.counts`` and ``device.memory_stats()``;
- with a trace asked for, it runs ``jax.profiler.start_trace`` /
  ``stop_trace`` over a few seconds in the middle of the window;
- it writes all of it to ``<rundir>/proxy_snap.json`` once the window
  has closed.

    python benchmark/proxy_host.py <rundir> -- <proxy argv...>
"""

from __future__ import annotations

import json
import os
import sys
import threading
import time
from pathlib import Path


def _snapshot() -> dict:
    import jax
    from kubeshare_tpu.obs import metrics as obs_metrics
    from kubeshare_tpu.utils import compilecache

    dev = jax.devices()[0]
    stats = dev.memory_stats() or {}
    keep = ("kubeshare_token_grant_wait_seconds",
            "kubeshare_token_hold_seconds",
            "kubeshare_proxy_rpc_latency_seconds")
    samples = [[name, labels, value]
               for name, labels, value in obs_metrics.collect_default()[
                   "samples"]
               if name.startswith(keep) and not name.endswith("_bucket")]
    return {"t": time.monotonic(), "samples": samples,
            "compile": dict(compilecache.counts),
            "memory": {k: int(v) for k, v in stats.items()
                       if isinstance(v, (int, float))},
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "count": len(jax.devices())}}


def _sleep_until(t: float, stop: threading.Event) -> None:
    while not stop.is_set():
        left = t - time.monotonic()
        if left <= 0:
            return
        stop.wait(min(left, 0.05))


def _side(rundir: Path, stop: threading.Event) -> None:
    # the parent writes ``ready`` once the proxy has printed READY: only
    # then is the backend the proxy's own choice, and safe to ask
    while not (rundir / "ready").exists():
        if stop.wait(0.01):
            return
    try:
        first = _snapshot()
    except Exception as exc:
        first = {"error": repr(exc)}
    tmp = rundir / "chip.json.tmp"
    tmp.write_text(json.dumps(first))
    os.replace(tmp, rundir / "chip.json")
    go_path = rundir / "go.json"
    while not go_path.exists():
        if stop.wait(0.01):
            return
    go = json.loads(go_path.read_text())
    out: dict = {"trace": None}
    try:
        _sleep_until(float(go["t0"]), stop)
        out["begin"] = _snapshot()
        trace = go.get("trace")
        if trace:
            import jax
            _sleep_until(float(trace["mark"]), stop)
            out["mid"] = _snapshot()    # counters end here in a traced run
            _sleep_until(float(trace["start"]), stop)
            jax.profiler.start_trace(trace["dir"])
            t_on = time.monotonic()
            _sleep_until(float(trace["stop"]), stop)
            t_off = time.monotonic()
            jax.profiler.stop_trace()
            out["trace"] = {"dir": trace["dir"], "start": t_on,
                            "stop": t_off,
                            "stop_trace_s": time.monotonic() - t_off}
        _sleep_until(float(go["t_end"]), stop)
        out["end"] = _snapshot()
        # the tenants' state is freed as they leave; the peak is a
        # high-water mark and is read again when the parent says so
        done = rundir / "tenants_done"
        while not done.exists() and not stop.wait(0.02):
            pass
        out["final"] = _snapshot()
    except Exception as exc:      # the parent reads this and fails the run
        out["error"] = repr(exc)
    tmp = rundir / "proxy_snap.json.tmp"
    tmp.write_text(json.dumps(out))
    os.replace(tmp, rundir / "proxy_snap.json")


def main(argv) -> None:
    if len(argv) < 3 or argv[2] != "--":
        raise SystemExit(__doc__)
    rundir = Path(argv[1])
    from kubeshare_tpu.isolation import proxy

    stop = threading.Event()
    side = threading.Thread(target=_side, args=(rundir, stop),
                            name="bench-side", daemon=True)
    side.start()
    try:
        proxy.main(argv[3:])      # returns on SIGTERM / SIGINT
    finally:
        stop.set()
        side.join(timeout=30.0)


if __name__ == "__main__":
    main(sys.argv)
