"""Test-only plans and trees: the same ``run.py`` at a tiny size on the
CPU. Nothing here is reachable from an option of the benchmark."""

from __future__ import annotations

import importlib.util
import json
import os
import shutil
import sys
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]
REPO = BENCH.parent

TINY_CONFIG = {
    "model_type": "gpt2", "n_embd": 64, "n_head": 2, "n_layer": 2,
    "n_positions": 128, "n_ctx": 128, "vocab_size": 256,
    "layer_norm_epsilon": 1e-05, "activation_function": "gelu_new",
    "reference": "benchmark/reference/gpt2.py",
    "binding": "benchmark/models/gpt2.py",
    "counts": "benchmark/counts/gpt2.py",
    "precision": {"params": "float32", "matmul": "bfloat16",
                  "control": "int8"},
}


def tiny_params(cfg: dict) -> int:
    d, layers = cfg["n_embd"], cfg["n_layer"]
    vocab, seq = cfg["vocab_size"], cfg["n_positions"]
    per = 2 * d + 4 * d * d + 2 * d + (4 * d * d + 4 * d) + (4 * d * d + d)
    return vocab * d + seq * d + layers * per + 2 * d + d * vocab + vocab


def trainer(name, request, mem=0.4, role="train"):
    return {"name": name, "role": role, "attach": "proxy",
            "tpu_request": request, "tpu_limit": 1.0,
            "tpu_mem_fraction": mem, "batch": 4, "seq_len": 128,
            "lr": 0.001, "arrivals": {"process": "closed"}}


def scorer(name="scorer", role="score", rate=6.0):
    return {"name": name, "role": role, "attach": "proxy",
            "tpu_request": 0.3, "tpu_limit": 1.0, "tpu_mem_fraction": 0.2,
            "arrivals": {"process": "poisson", "rate_per_s": rate},
            "lengths": {"kind": "lognormal", "median": 40, "sigma": 0.7,
                        "min": 8, "max": 128},
            "buckets": [32, 64, 128]}


TINY_MIXES = {
    "tiny-pair": {"chips": 1, "mesh": None,
                  "tenants": [trainer("tenant-a", 0.5),
                              trainer("tenant-b", 0.5)]},
    "tiny-score-vs-train": {"chips": 1, "mesh": None,
                            "tenants": [scorer(), trainer("trainer", 0.7)]},
}

#: set for THIS size as the real ones are for theirs: above what sound
#: rehearsals read (loss 3-5e-5, norms 4-8e-3, scores 4e-4), below what the
#: int8 control (loss >= 3e-4) and the planted faults read
TINY_LIMITS = {"numbers": {"loss_gap": {"limit": 1.5e-4},
                           "grad_norm_gap": {"limit": 0.1},
                           "update_norm_gap": {"limit": 0.05},
                           "score_gap": {"limit": 5e-3},
                           "unanswered": {"limit": 0}}}


#: the real cell whose set of metrics each tiny cell reports
LIKE = {"tiny-pair": "gpt2s-pair-even",
        "tiny-score-vs-train": "gpt2m-score-vs-train"}


def make_tree(tmp: Path, mixes: dict | None = None,
              limits: dict | None = None, like: dict | None = None,
              config: dict | None = None) -> Path:
    """A checkout-shaped tree: a COPY of ``benchmark/`` (tests add files
    to it, never edit one), the program by symlink, and a manifest whose
    cells are the tiny mixes on the tiny configuration ``config``
    (default: GPT-2's family; its ``parameters_as_run`` given, or
    counted for that family)."""
    root = tmp / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    os.symlink(REPO / "kubeshare_tpu", root / "kubeshare_tpu")
    bench = root / "benchmark"
    cfg = dict(TINY_CONFIG if config is None else config)
    if "parameters_as_run" not in cfg:
        cfg["parameters_as_run"] = tiny_params(cfg)
    (bench / "configs" / "tiny.json").write_text(json.dumps(cfg))
    mixes = TINY_MIXES if mixes is None else mixes
    real = json.loads((REPO / "BENCHMARK.json").read_text())
    cells = []
    for name, mix in mixes.items():
        (bench / "mixes" / f"{name}.json").write_text(json.dumps(mix))
        (bench / "limits" / f"{name}.json").write_text(
            json.dumps(limits or TINY_LIMITS))
        cells.append({"name": name, "config": "tiny", "traffic": name,
                      "chips": 1, "why": "rehearsal"})
    manifest = dict(real, workloads=cells, configs=[
        {"name": "tiny", "source": "test", "file":
         "benchmark/configs/tiny.json", "reduced": [], "why": "rehearsal"}])
    like = LIKE if like is None else like
    for group in ("end_to_end", "per_layer"):
        manifest[group] = [
            dict(m, workloads=[c["name"] for c in cells
                               if like[c["name"]] in m["workloads"]])
            if "workloads" in m else m for m in real[group]]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    # the CPU stands in as a "chip" of the rehearsal only
    peaks = json.loads((bench / "peaks.json").read_text())
    peaks["kinds"]["cpu"] = dict(peaks["kinds"]["TPU v5 lite"],
                                 source="rehearsal stand-in")
    (bench / "peaks.json").write_text(json.dumps(peaks))
    return root


def forget_other_trees(root: Path) -> None:
    """A run is one process on one checkout; the tests drive many trees in
    one process. Drop the benchmark's own modules that another tree left
    in ``sys.modules`` (``readlib`` finds a configuration's files from
    where it was loaded), and look in this tree first."""
    bench = root / "benchmark"
    for name in ("readlib", "flops", "traffic", "check"):
        mod = sys.modules.get(name)
        if mod is not None and bench not in Path(mod.__file__).parents:
            del sys.modules[name]
    sys.path.insert(0, str(bench))


def add_to_lists(manifest: dict, cell: str, metrics) -> None:
    """What a PR that adds a cell does to the manifest's metrics: append
    the cell's name to the ``workloads`` list of each metric it reports."""
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            if m["name"] in metrics and "workloads" in m:
                m["workloads"].append(cell)


def load_run(root: Path):
    forget_other_trees(root)
    path = root / "benchmark" / "run.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_run_{abs(hash(str(root)))}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod
    spec.loader.exec_module(mod)
    return mod


def cpu_plan(run_mod, **over):
    return run_mod.Plan(**{**dict(
        platform="cpu", proxy_argv=("--platform", "cpu"),
        child_env={"JAX_PLATFORMS": "cpu"},
        chip_bytes=8 << 30, drain_s=20.0, sample_requests=6,
        check_timeout_s=240.0, trace_max_s=1.0), **over})


def rehearse(root: Path, workload: str, seed: int = 2_147_483_900,
             seconds: float = 3.0, trace: int = 0, capsys=None, **plan):
    """Drive ``run.py`` in-process with the CPU plan; returns
    ``(exit code, result or None)``."""
    import contextlib
    import io

    run_mod = load_run(root)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--workload", workload, "--seed", str(seed),
                           "--seconds", str(seconds), "--trace", str(trace)],
                          plan=cpu_plan(run_mod, **plan))
    lines = out.getvalue().strip().splitlines()
    result = json.loads(lines[-1]) if rc == 0 and lines else None
    return rc, result, out.getvalue()


def add_role(root: Path, role: str, base: str, patch: str) -> None:
    """A new tenant role in the tree, by ADDING two files: the base role's
    program with ``patch`` applied underneath it, and the base role's
    check. This is how the fault tests break the timed path."""
    bench = root / "benchmark"
    (bench / "tenants" / f"{role}.py").write_text(f'''
import sys
from pathlib import Path
sys.path.insert(0, str(Path(__file__).resolve().parent))
import {base} as base
{patch}
base.main(sys.argv)
''')
    (bench / "checks" / f"{role}.py").write_text(
        (bench / "checks" / f"{base}.py").read_text())
