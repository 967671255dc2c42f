"""The control of a cell's comparison, on the chip at the cell's own size.

    python benchmark/control.py --workload <name> --seeds <n> [<n> ...]

Not part of a benchmark run. For every tenant of the cell's mix it puts the
plain reference in the program's place, (a) computed in the nearest
precision below the one the configuration states
(``precision.control`` in the configuration's file) and (b) with each
fault the tenant's role can have, and prints what the run's own comparison
(``checks/<role>.py``) reads then: the upper readings a limit has to stay
under. ``tests/`` keep the same at a size a test run can hold.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))

from check import _load  # noqa: E402


def readings(manifest_root: Path, workload: str, seed: int, seconds: float,
             sample_requests: int = 32, quant: str | None = None) -> dict:
    manifest = json.loads((manifest_root / "BENCHMARK.json").read_text())
    cell = {w["name"]: w for w in manifest["workloads"]}[workload]
    config = json.loads((manifest_root / {
        c["name"]: c for c in manifest["configs"]}[cell["config"]]["file"])
        .read_text())
    bench = manifest_root / manifest["paths"][0]
    mix = json.loads((bench / "mixes" / f"{cell['traffic']}.json")
                     .read_text())
    quant = quant or {"int8": "int8", "float8_e4m3fn": "fp8"}[
        config["precision"]["control"]]
    ref = _load(manifest_root / config["reference"])
    spec = {"config": config, "seed": seed, "seconds": seconds,
            "sample_requests": sample_requests}
    out = {}
    for i, entry in enumerate(mix["tenants"]):
        t = {"entry": entry, "index": i, "name": entry["name"]}
        out[entry["name"]] = _load(
            bench / "checks" / f"{entry['role']}.py").control(
                ref, spec, t, quant)
    return out


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(prog="benchmark/control.py")
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seeds", type=int, nargs="+", required=True)
    parser.add_argument("--quant", default=None)
    args = parser.parse_args(argv)
    import os

    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    manifest = json.loads((REPO / "BENCHMARK.json").read_text())
    dev = jax.devices()[0]
    print(json.dumps({"device": [dev.platform, dev.device_kind]}), flush=True)
    for seed in args.seeds:
        got = readings(REPO, args.workload, seed,
                       float(manifest["run_seconds"]), quant=args.quant)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "readings": got}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
