"""Proxy-transport micro-benchmark: the isolation runtime's own overhead.

SURVEY §7.3's hard part #1 is keeping the PJRT-proxying overhead — the
serialize/socket/token-gate path around each remote execution — far
below one training step. That overhead is protocol work, not device
work, so it IS meaningful on the CPU backend (on a directly attached
chip it is the whole cost added to each dispatch):

- ``execute_rtt_ms``: round-trip of a trivial compiled program through
  register→execute→reply, p50/p99 — the per-dispatch floor.
- ``put/get_gbps``: host↔proxy buffer bandwidth over the framed socket
  (64 MiB array, chunked path — windowed streaming when negotiated).
- ``async_dispatch_ops_per_sec``: small-op throughput with a window of
  ``execute_async`` futures in flight — the pipelined transport's
  multiplexing win over the lockstep rate (1 / ``execute_rtt_ms``).

Run: ``python scripts/bench_proxy.py`` → one JSON object
(committed as ``bench_proxy.json``). ``--baseline FILE`` also prints
deltas vs a committed baseline; ``--write FILE`` saves the fresh
numbers (``make bench-proxy`` does both against ``bench_proxy.json``).
"""

from __future__ import annotations

import argparse
import json
import statistics
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

#: keys worth a delta line (the rest of the JSON is descriptive)
_METRICS = ("execute_rtt_ms_p50", "execute_rtt_ms_p99", "put_gbps",
            "get_gbps", "async_dispatch_ops_per_sec")
#: metrics where larger is better (the rest are latencies)
_HIGHER_IS_BETTER = ("put_gbps", "get_gbps", "async_dispatch_ops_per_sec")


class _ProxyProcess:
    """The chip proxy in its own process — the deployment shape (client
    pods talk to one resident proxy process over a local socket). An
    in-process proxy shares the client's GIL, which serializes the very
    overlap the pipelined-transport numbers measure."""

    def __init__(self):
        import subprocess
        self._proc = subprocess.Popen(
            [sys.executable, "-m", "kubeshare_tpu.isolation.proxy",
             "-P", "0", "--platform", "cpu"],
            stdout=subprocess.PIPE, text=True,
            cwd=str(Path(__file__).resolve().parent.parent))
        line = self._proc.stdout.readline()
        if not line.startswith("READY "):
            raise RuntimeError(f"proxy failed to start: {line!r}")
        self.port = int(line.split()[1])

    def close(self):
        self._proc.terminate()
        try:
            self._proc.wait(timeout=10)
        except Exception:
            self._proc.kill()


def run_bench(in_process: bool = False) -> dict:
    import jax

    jax.config.update("jax_platforms", "cpu")
    import numpy as np

    from kubeshare_tpu.isolation.client import ProxyClient

    if in_process:
        from kubeshare_tpu.isolation.proxy import ChipProxy
        from kubeshare_tpu.isolation.tokensched import TokenScheduler
        proxy = ChipProxy(scheduler=TokenScheduler())
        proxy.serve()
    else:
        proxy = _ProxyProcess()
    out: dict = {"bench": "proxy transport overhead (CPU backend)"}
    try:
        with ProxyClient("127.0.0.1", proxy.port, "bench", 1.0, 1.0) as c:
            # --- dispatch round trip on a trivial program ---------------
            exe = c.compile(lambda x: x + 1.0, np.float32(0))
            buf = c.put(np.float32(0))
            for _ in range(20):           # warm: compile + token steady
                c.free(exe(buf))
            rtts = []
            for _ in range(300):
                t0 = time.perf_counter()
                res = exe(buf)
                rtts.append((time.perf_counter() - t0) * 1e3)
                c.free(res)
            out["execute_rtt_ms_p50"] = round(statistics.median(rtts), 3)
            out["execute_rtt_ms_p99"] = round(
                sorted(rtts)[int(len(rtts) * 0.99) - 1], 3)

            # --- async (windowed) small-op dispatch throughput ----------
            # a window of execute_async futures rides the multiplexed
            # connection; each op still passes the token gate and device
            # dispatch — the win is overlap, not skipped work
            window = 64
            n_ops = 2000
            pending: list = []
            done_handles: list[int] = []

            def drain_one():
                out_handles = pending.pop(0).result()
                done_handles.extend(out_handles)

            # defer=True corks submits (Connection.CORK_FRAMES per write);
            # the window is deep enough that the head future being drained
            # was always flushed long ago — only the final drain needs an
            # explicit flush()
            for _ in range(200):          # warm the pipelined path
                pending.append(c.execute_async(exe._exec_id, [buf.handle],
                                               defer=True))
            c.flush()
            while pending:
                drain_one()
            rates = []
            for _ in range(3):            # median beats one noisy sample
                c._conn.call({"op": "free", "name": c.name,
                              "handles": done_handles})
                done_handles.clear()
                t0 = time.perf_counter()
                for _ in range(n_ops):
                    if len(pending) >= window:
                        drain_one()
                    pending.append(
                        c.execute_async(exe._exec_id, [buf.handle],
                                        defer=True))
                c.flush()
                while pending:
                    drain_one()
                rates.append(n_ops / (time.perf_counter() - t0))
            out["async_dispatch_ops_per_sec"] = round(
                statistics.median(rates), 0)
            # free in batches: one giant handle list would dwarf MAX_FRAME
            for i in range(0, len(done_handles), 1000):
                c._conn.call({"op": "free", "name": c.name,
                              "handles": done_handles[i:i + 1000]})

            # --- transfer bandwidth (chunked path) ----------------------
            big = np.random.default_rng(0).random(
                (16 << 20,)).astype(np.float32)         # 64 MiB (fp32:
            #                       jax without x64 truncates float64)
            puts, gets = [], []
            for i in range(3):              # median beats one cold sample
                t0 = time.perf_counter()
                bbuf = c.put(big)
                puts.append(time.perf_counter() - t0)
                t0 = time.perf_counter()
                back = c.get(bbuf)
                gets.append(time.perf_counter() - t0)
                if i == 0:  # the chunked path's correctness, not just size
                    assert np.array_equal(back, big)
                c.free(bbuf)
            gbits = big.nbytes / 1e9 * 8    # decimal Gbit (NIC convention)
            out["put_gbps"] = round(gbits / statistics.median(puts), 2)
            out["get_gbps"] = round(gbits / statistics.median(gets), 2)
    finally:
        proxy.close()
    return out


def print_deltas(fresh: dict, baseline_path: Path) -> None:
    try:
        base = json.loads(baseline_path.read_text())
    except (OSError, ValueError) as e:
        print(f"# no usable baseline at {baseline_path}: {e}",
              file=sys.stderr)
        return
    print(f"# deltas vs {baseline_path}:", file=sys.stderr)
    for key in _METRICS:
        new, old = fresh.get(key), base.get(key)
        if new is None or old is None:
            print(f"#   {key:28s} {old!s:>10} -> {new!s:>10}",
                  file=sys.stderr)
            continue
        ratio = (new / old) if old else float("inf")
        better = (ratio >= 1.0) == (key in _HIGHER_IS_BETTER)
        tag = "better" if better else "worse"
        if abs(ratio - 1.0) < 0.02:
            tag = "~same"
        print(f"#   {key:28s} {old:>10} -> {new:>10}  ({ratio:5.2f}x {tag})",
              file=sys.stderr)


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(prog="bench_proxy")
    parser.add_argument("--baseline", type=Path, default=None,
                        help="committed baseline JSON to print deltas "
                             "against (stderr)")
    parser.add_argument("--write", type=Path, default=None,
                        help="write the fresh numbers to this JSON file")
    parser.add_argument("--in-process", action="store_true",
                        help="run the proxy inside this interpreter "
                             "(debugging; shares the GIL with the client)")
    args = parser.parse_args(argv)
    out = run_bench(in_process=args.in_process)
    print(json.dumps(out, indent=2))
    if args.baseline is not None:
        print_deltas(out, args.baseline)
    if args.write is not None:
        args.write.write_text(json.dumps(out, indent=2) + "\n")


if __name__ == "__main__":
    main()
