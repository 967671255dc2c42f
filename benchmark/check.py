"""The reference check of one run, in a process of its own.

Runs once the window has closed, ``memory_peak_bytes`` has been read and
the proxy has given the chip back: this process then owns the chip, makes
the configuration's plain reference follow what each tenant did (from the
seed alone: it imports nothing of the program and is handed nothing the
program made but the numbers to compare), and writes the reference's
readings to ``<rundir>/check_out.json``. The parent compares.

    python benchmark/check.py <rundir>
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
REPO = HERE.parent
sys.path.insert(0, str(HERE))


def _load(path: Path):
    spec = importlib.util.spec_from_file_location("bench_" + path.stem, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def main(argv) -> None:
    rundir = Path(argv[1])
    spec = json.loads((rundir / "check_in.json").read_text())
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir",
                          str(REPO / ".jax_cache"))
    platform = jax.devices()[0].platform
    if platform != spec["platform"]:
        raise SystemExit(f"the reference runs on {platform!r}, the run "
                         f"needs {spec['platform']!r}")
    ref = _load(REPO / spec["config"]["reference"])
    out = {"tenants": [], "seconds": []}
    for t in spec["tenants"]:
        t0 = time.monotonic()
        out["tenants"].append(
            _load(HERE / "checks" / f"{t['role']}.py").reference(
                ref, spec, t))
        out["seconds"].append(time.monotonic() - t0)
    tmp = rundir / "check_out.json.tmp"
    tmp.write_text(json.dumps(out))
    os.replace(tmp, rundir / "check_out.json")


if __name__ == "__main__":
    main(sys.argv)
