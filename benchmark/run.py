#!/usr/bin/env python3
"""One benchmark run: one cell, one new process tree, one JSON line last.

    python benchmark/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

The parent never imports jax (a chip belongs to one process). It starts
the chip proxy (the launcher's own command, hosted by ``proxy_host.py``),
one process per tenant of the cell's mix (attached by environment alone,
``_shim`` first on PYTHONPATH), keeps an idle observer session, opens the
window when every tenant is warm, and, once the window has closed and the
proxy has given the chip back, runs the plain reference in a process of
its own to decide ``correct``.

Driven by data: the cell's entry in ``BENCHMARK.json`` names a
configuration (its ``file``) and a traffic mix (``mixes/<traffic>.json``);
the configuration's file names what depends on its model's family (its
``binding`` to the program's model, its ``counts``, its plain
``reference``); the mix names tenant roles (``tenants/<role>.py``, checked
by ``checks/<role>.py``); every metric is a reader ``metrics/<name>.py``;
the limits of what is compared are ``limits/<workload>.json``. This file
holds no cell's, configuration's, family's, mix's, role's or metric's name.
"""

from __future__ import annotations

import argparse
import atexit
import importlib.util
import json
import os
import re
import shutil
import signal
import socket
import subprocess
import sys
import tempfile
import time
from dataclasses import dataclass, field
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(REPO))
SHIM = REPO / "kubeshare_tpu" / "_shim"

ATTACH_MODES = ("proxy",)       # the mix's schema knows more; see README
#: what a configuration's file names by path: all that knows its family
FAMILY_FILES = ("binding", "counts", "reference")


@dataclass
class Plan:
    """Limits of one run. The defaults ARE a run as the driver makes it;
    ``tests/`` rehearse the same path on the CPU with another plan (a
    test-only hook: no option of this program selects it)."""

    platform: str = "tpu"           # what the chip owner must report
    proxy_argv: tuple = ()          # added to the proxy's argv
    child_env: dict = field(default_factory=dict)
    ready_timeout_s: float = 240.0
    warm_timeout_s: float = 1000.0
    drain_s: float = 60.0           # a request may answer this long late
    check_timeout_s: float = 300.0
    trace_max_s: float = 3.0
    sample_requests: int = 32
    #: the chip's memory where the backend reports none (CPU rehearsal)
    chip_bytes: int | None = None


class RunFailed(Exception):
    pass


def note(msg: str) -> None:
    print(f"[bench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


# -- children ----------------------------------------------------------------

_LIVE: list[subprocess.Popen] = []


class Child:
    """One process group, its output in files, killed on the way out."""

    def __init__(self, tag: str, cmd: list[str], env: dict, rundir: Path):
        self.tag = tag
        self.out_path = rundir / f"{tag}.out"
        self.err_path = rundir / f"{tag}.err"
        with open(self.out_path, "w") as out, open(self.err_path, "w") as err:
            self.proc = subprocess.Popen(cmd, env=env, cwd=str(REPO),
                                         stdout=out, stderr=err,
                                         start_new_session=True)
        _LIVE.append(self.proc)

    @property
    def out(self) -> str:
        return self.out_path.read_text(errors="replace")

    @property
    def err(self) -> str:
        return self.err_path.read_text(errors="replace")

    def tail(self, n: int = 30) -> str:
        lines = (self.err + "\n" + self.out).strip().splitlines()
        return "\n".join(lines[-n:])

    def wait_line(self, pattern: str, timeout_s: float) -> re.Match:
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            m = re.search(pattern, self.out, re.MULTILINE)
            if m:
                return m
            if self.proc.poll() is not None:
                m = re.search(pattern, self.out, re.MULTILINE)
                if m:
                    return m
                raise RunFailed(f"{self.tag} exited rc={self.proc.returncode}"
                                f" before {pattern!r}:\n{self.tail()}")
            time.sleep(0.05)
        raise RunFailed(f"{self.tag}: no {pattern!r} after {timeout_s:.0f}s:"
                        f"\n{self.tail()}")

    def wait_exit(self, timeout_s: float) -> int:
        try:
            return self.proc.wait(timeout=timeout_s)
        except subprocess.TimeoutExpired:
            raise RunFailed(f"{self.tag} still running after "
                            f"{timeout_s:.0f}s:\n{self.tail()}") from None

    def stop(self) -> None:
        _kill(self.proc)


def _kill(proc: subprocess.Popen) -> None:
    if proc.poll() is not None:
        return
    for sig, grace in ((signal.SIGTERM, 15.0), (signal.SIGKILL, 5.0)):
        try:
            os.killpg(os.getpgid(proc.pid), sig)
        except (ProcessLookupError, PermissionError):
            return
        try:
            proc.wait(timeout=grace)
            return
        except subprocess.TimeoutExpired:
            continue


def _kill_all() -> None:
    for proc in _LIVE:
        _kill(proc)


atexit.register(_kill_all)


def base_env(plan: Plan, **extra) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.pathsep.join(
        [str(REPO)] + [p for p in env.get("PYTHONPATH", "").split(os.pathsep)
                       if p])
    env.setdefault("TPU_LOG_DIR", "disabled")
    # every compile is cached, so a second run of a cell compiles nothing
    env.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0")
    env.update(plan.child_env)
    env.update({k: str(v) for k, v in extra.items()})
    # ...and kept: under a size cap (the chip tool's machine sets 192 MiB)
    # the step's program and the reference's evict each other on every run,
    # and each run of the larger cell then compiles for six minutes. Set
    # last: the launcher's env for the proxy is a copy of this process's.
    env["JAX_COMPILATION_CACHE_MAX_SIZE"] = "-1"
    return env


def shim_env(env: dict) -> dict:
    env = dict(env)
    env["PYTHONPATH"] = os.pathsep.join([str(SHIM), env["PYTHONPATH"]])
    return env


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def write_json(path: Path, obj) -> None:
    tmp = path.with_suffix(path.suffix + ".tmp")
    tmp.write_text(json.dumps(obj))
    os.replace(tmp, path)


def wait_file(path: Path, timeout_s: float, who: Child) -> dict:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        if path.exists():
            return json.loads(path.read_text())
        if who.proc.poll() is not None:
            raise RunFailed(f"{who.tag} exited rc={who.proc.returncode} "
                            f"before writing {path.name}:\n{who.tail()}")
        time.sleep(0.02)
    raise RunFailed(f"{who.tag}: no {path.name} after {timeout_s:.0f}s:\n"
                    f"{who.tail()}")


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.05))


# -- the manifest and the files it names -------------------------------------

def load_module(path: Path):
    if not path.is_file():
        raise RunFailed(f"no such file: {path.relative_to(REPO)}")
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", path.stem), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_cell(workload: str) -> dict:
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in manifest["workloads"]}
    if workload not in cells:
        raise RunFailed(f"unknown workload {workload!r} (BENCHMARK.json has "
                        f"{sorted(cells)})")
    cell = cells[workload]
    configs = {c["name"]: c for c in manifest["configs"]}
    config = json.loads((REPO / configs[cell["config"]]["file"]).read_text())
    for key in FAMILY_FILES:
        if not (config.get(key) and (REPO / config[key]).is_file()):
            raise RunFailed(f"configuration {cell['config']!r}: its "
                            f"{key!r} names no file ({config.get(key)!r})")
    mix_path = HERE / "mixes" / f"{cell['traffic']}.json"
    if not mix_path.is_file():
        raise RunFailed(f"no mix file {mix_path.relative_to(REPO)}")
    mix = json.loads(mix_path.read_text())
    limits_path = HERE / "limits" / f"{workload}.json"
    if not limits_path.is_file():
        raise RunFailed(f"no limits file {limits_path.relative_to(REPO)}")
    limits = json.loads(limits_path.read_text())["numbers"]
    if int(mix.get("chips", 1)) != int(cell["chips"]):
        raise RunFailed(f"mix asks for {mix.get('chips', 1)} chip(s), the "
                        f"cell for {cell['chips']}")
    if int(cell["chips"]) != 1 or mix.get("mesh"):
        raise RunFailed("this runner drives one chip and no mesh; a gang "
                        "cell needs the runner extended (README)")
    for t in mix["tenants"]:
        mode = t.get("attach", "proxy")
        if mode not in ATTACH_MODES:
            raise RunFailed(f"tenant {t['name']!r}: unknown attach mode "
                            f"{mode!r} (this runner knows {ATTACH_MODES})")
        for sub in ("tenants", "checks"):
            if not (HERE / sub / f"{t['role']}.py").is_file():
                raise RunFailed(f"tenant {t['name']!r}: unknown role "
                                f"{t['role']!r} (no {sub}/{t['role']}.py)")
    if (len(metrics_for(manifest, "end_to_end", workload)) < 2
            or not metrics_for(manifest, "per_layer", workload)):
        raise RunFailed(f"cell {workload!r} reports too little: append its "
                        "name to the `workloads` list of every metric it "
                        "reports (an end-to-end metric beside setup_s and "
                        "a per-layer metric at the least; README)")
    return {"manifest": manifest, "cell": cell, "config": config,
            "mix": mix, "limits": limits}


def metrics_for(manifest: dict, group: str, workload: str) -> list[dict]:
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


# -- one run -----------------------------------------------------------------

def run(args, plan: Plan) -> dict:
    t_proc = time.monotonic()
    if not (REPO / "kubeshare_tpu").is_dir():
        raise RunFailed("the program (kubeshare_tpu/) is not in this "
                        "checkout: nothing to measure")
    loaded = load_cell(args.workload)
    seconds = float(args.seconds)
    work = REPO / ".bench_work"
    work.mkdir(exist_ok=True)
    rundir = Path(tempfile.mkdtemp(prefix="run-", dir=work))
    try:
        return _run_in(rundir, args, plan, loaded, seconds, t_proc)
    finally:
        _kill_all()
        shutil.rmtree(rundir, ignore_errors=True)


def _run_in(rundir, args, plan, loaded, seconds, t_proc) -> dict:
    from kubeshare_tpu import constants as C
    from kubeshare_tpu.isolation.client import ProxyClient
    from kubeshare_tpu.nodeagent import launcherd

    cell, config, mix = loaded["cell"], loaded["config"], loaded["mix"]
    seed = int(args.seed)
    peaks_table = json.loads((HERE / "peaks.json").read_text())

    # 1. the chip proxy: the launcher's own command, hosted
    exec_port, token_port = free_port(), free_port()
    cmd, env = launcherd.default_proxy_cmd("bench-0", 0, exec_port,
                                           token_port)
    if cmd[1:3] != ["-m", "kubeshare_tpu.isolation.proxy"]:
        raise RunFailed(f"the launcher's proxy command changed shape: {cmd}")
    cmd = [cmd[0], str(HERE / "proxy_host.py"), str(rundir), "--",
           *cmd[3:], *plan.proxy_argv]
    proxy = Child("proxy", cmd, base_env(plan, **env), rundir)
    proxy.wait_line(r"^READY (\d+)", plan.ready_timeout_s)
    t_ready = time.monotonic()
    (rundir / "ready").write_text("1")
    chip = wait_file(rundir / "chip.json", 60.0, proxy)
    if "error" in chip:
        raise RunFailed(f"the chip owner could not report: {chip['error']}")

    # 2. the observer: idle, asks for no token; the chip owner's own report
    observer = ProxyClient("127.0.0.1", exec_port, "bench-observer/pod-0",
                           0.01, 0.01)
    owner = observer.usage()["chip"]
    if owner["platform"] != plan.platform:
        raise RunFailed(f"the chip proxy runs on platform "
                        f"{owner['platform']!r}, a run needs "
                        f"{plan.platform!r}: no accelerator, or JAX held to "
                        "another backend")
    kind = owner["device_kind"]
    if kind not in peaks_table["kinds"]:
        raise RunFailed(f"device_kind {kind!r} is not in peaks.json "
                        f"({sorted(peaks_table['kinds'])}): no peak, no "
                        "roofline")
    if int(chip["device"]["count"]) < int(cell["chips"]):
        raise RunFailed(f"the cell needs {cell['chips']} chip(s), the "
                        f"machine has {chip['device']['count']}")
    chip_bytes = chip["memory"].get("bytes_limit") or plan.chip_bytes
    if not chip_bytes:
        raise RunFailed("the chip reports no bytes_limit: tpu_mem grants "
                        "cannot be sized")
    note(f"proxy ready in {t_ready - t_proc:.1f}s on {kind} "
         f"({chip_bytes} bytes)")

    # 3. the tenants, by environment alone
    tenants = []
    for i, entry in enumerate(mix["tenants"]):
        pod = f"{entry['name']}/pod-0"       # a namespace of its own
        spec_path = rundir / f"{entry['name']}.spec.json"
        write_json(spec_path, {"tenant": entry, "config": config,
                               "seed": seed, "index": i,
                               "rundir": str(rundir), "seconds": seconds})
        tenv = shim_env(base_env(plan, **{
            C.ENV_CHIP_PROXY_PORT: exec_port,
            C.ENV_TPU_REQUEST: entry["tpu_request"],
            C.ENV_TPU_LIMIT: entry["tpu_limit"],
            C.ENV_TPU_MEMORY: int(float(entry["tpu_mem_fraction"])
                                  * chip_bytes),
            C.ENV_POD_NAME: pod}))
        child = Child(entry["name"], [sys.executable,
                                      str(HERE / "tenants" /
                                          f"{entry['role']}.py"),
                                      str(spec_path)], tenv, rundir)
        tenants.append({"name": entry["name"], "pod": pod,
                        "namespace": entry["name"], "role": entry["role"],
                        "entry": entry, "index": i, "child": child})
    deadline = time.monotonic() + plan.warm_timeout_s
    for t in tenants:
        m = t["child"].wait_line(r"^WARM (\{.*\})$",
                                 max(1.0, deadline - time.monotonic()))
        t["warm"] = json.loads(m.group(1))
        note(f"{t['name']} warm after {t['warm']['setup_s']:.1f}s")

    # 4. the window
    t0 = time.monotonic() + 0.25
    t_end = t0 + seconds
    go = {"t0": t0, "t_end": t_end, "drain_s": plan.drain_s, "trace": None}
    if int(args.trace):
        span = min(plan.trace_max_s, 0.3 * seconds)
        # counters are read at ``mark``, a little before the tracer starts:
        # starting it stalls the proxy's Python for a moment, and two
        # readings taken across that stall disagree by whole steps
        go["trace"] = {"dir": str(rundir / "trace"),
                       "mark": t0 + max(0.2 * seconds,
                                          0.45 * seconds - 0.5),
                       "start": t0 + 0.45 * seconds,
                       "stop": t0 + 0.45 * seconds + span}
    write_json(rundir / "go.json", go)
    setup_s = t0 - t_proc
    sleep_until(t0)
    usage = {"begin": observer.usage()}
    if go["trace"]:
        # the tracer slows the proxy's host code: counters are read over
        # the part of the window before it starts
        sleep_until(go["trace"]["mark"])
        usage["mid"] = observer.usage()
    sleep_until(t_end)
    usage["end"] = observer.usage()
    for t in tenants:
        m = t["child"].wait_line(r"^DONE (\{.*\})$", plan.drain_s + 120.0)
        t["done"] = json.loads(m.group(1))
        rc = t["child"].wait_exit(60.0)
        if rc != 0:
            raise RunFailed(f"{t['name']} exited rc={rc}:\n"
                            f"{t['child'].tail()}")
    (rundir / "tenants_done").write_text("1")
    snap = wait_file(rundir / "proxy_snap.json", 120.0, proxy)
    if "error" in snap:
        raise RunFailed(f"the proxy's side thread failed: {snap['error']}")
    observer.close()
    proxy.stop()                    # the chip is free again

    compiles = (snap["end"]["compile"]["requests"]
                - snap["begin"]["compile"]["requests"])
    note(f"proxy compile cache at window start: {snap['begin']['compile']}; "
         f"compile requests inside the window: {compiles}")
    if compiles:
        raise RunFailed(f"{compiles} compile request(s) inside the measured "
                        "window: a shape was not warmed")

    # 5. after the window: the reference (owns the chip now) and the trace
    check_in = {"config": config, "seed": seed, "seconds": seconds,
                "sample_requests": plan.sample_requests,
                "platform": plan.platform,
                "tenants": [{k: t[k] for k in ("name", "role", "entry",
                                               "index", "warm", "done")}
                            for t in tenants]}
    write_json(rundir / "check_in.json", check_in)
    checker = Child("check", [sys.executable, str(HERE / "check.py"),
                              str(rundir)], base_env(plan), rundir)
    reducer = None
    if snap["trace"]:
        reducer = Child("reduce", [sys.executable,
                                   str(HERE / "reduce_trace.py"),
                                   snap["trace"]["dir"],
                                   str(rundir / "trace.json")],
                        base_env(plan, JAX_PLATFORMS="cpu"), rundir)
    t_check = time.monotonic()
    if checker.wait_exit(plan.check_timeout_s) != 0:
        raise RunFailed(f"the reference check failed to run:\n"
                        f"{checker.tail()}")
    readings = json.loads((rundir / "check_out.json").read_text())
    note(f"reference check took {time.monotonic() - t_check:.1f}s "
         f"(not in setup_s)")
    trace = None
    if reducer is not None:
        if reducer.wait_exit(240.0) != 0:
            raise RunFailed(f"trace reduction failed:\n{reducer.tail()}")
        trace = json.loads((rundir / "trace.json").read_text())
        trace["window_s"] = snap["trace"]["stop"] - snap["trace"]["start"]
        trace["counted_s"] = go["trace"]["mark"] - t0
        trace["from_s"] = snap["trace"]["start"] - t0
        trace["to_s"] = snap["trace"]["stop"] - t0

    # 6. correct: every number compared, beside its limit
    checks, attempted, failed = [], 0, 0
    for t, ref in zip(tenants, readings["tenants"]):
        mod = load_module(HERE / "checks" / f"{t['role']}.py")
        counts = mod.counts(t)
        attempted += counts["attempted"]
        failed += counts["failed"]
        for name, value in mod.numbers(t, ref).items():
            checks.append({"name": f"{t['name']}.{name}", "number": name,
                           "value": value})
    correct = bool(checks)
    for c in checks:
        lim = loaded["limits"].get(c.pop("number"))
        if lim is None:
            raise RunFailed(f"no limit for the compared number {c['name']!r}"
                            f" in limits/{args.workload}.json")
        c["limit"] = lim["limit"]
        c["ok"] = c["value"] is not None and c["value"] <= lim["limit"]
        correct = correct and c["ok"]

    # 7. the metrics, each by its own reader
    peak = max(snap[k]["memory"].get("peak_bytes_in_use", 0)
               for k in ("end", "final"))
    record = {"workload": args.workload, "seed": seed, "seconds": seconds,
              "window_s": t_end - t0, "drain_s": plan.drain_s,
              "setup_s": setup_s, "config": config,
              "mix": mix, "peaks": peaks_table["kinds"][kind],
              "tenants": [{k: v for k, v in t.items() if k != "child"}
                          for t in tenants],
              "usage": usage, "proxy": snap, "trace": trace}
    group = "per_layer" if int(args.trace) else "end_to_end"
    metrics = {}
    for m in metrics_for(loaded["manifest"], group, args.workload):
        value = load_module(HERE / "metrics" / f"{m['name']}.py").read(record)
        if value is None:
            if group == "end_to_end":
                raise RunFailed(f"end-to-end metric {m['name']!r} found "
                                "nothing to read")
            continue        # a reader that finds nothing says nothing
        metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}

    device = {"platform": owner["platform"], "kind": kind,
              "count": int(chip["device"]["count"]),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": attempted, "failed": failed,
              "metrics": metrics, "device": device}
    if trace is not None:
        device["busy_s"] = trace["busy_s"]
        device["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"][:10],
                               "idle_gaps": trace["idle_gaps"][:10]}
    for t in tenants:
        mod = load_module(HERE / "checks" / f"{t['role']}.py")
        note(f"{t['name']}: {mod.summary(t, record)}")
    note(f"memory_peak_bytes {peak} of {chip_bytes} "
         f"({100.0 * peak / chip_bytes:.1f}%); setup_s {setup_s:.1f}")
    result["checks"] = checks       # last: what was compared, and its limit
    return result


def main(argv=None, plan: Plan | None = None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark/run.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)
    try:
        result = run(args, plan or Plan())
    except RunFailed as exc:
        print(f"benchmark run failed: {exc}", file=sys.stderr, flush=True)
        return 1
    sys.stdout.flush()
    for c in result["checks"]:
        print(f"compared {c['name']} = {c['value']} limit {c['limit']} "
              f"{'ok' if c['ok'] else 'NOT OK'}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
