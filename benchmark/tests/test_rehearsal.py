"""Each cell end to end on the CPU at a tiny size, through the same
``run.py`` (a test-only plan; no option of ``run.py`` selects it); a cell
added by data alone; a cell of another family of models added by files
alone; a cell that trains nothing; and every fault a cell can have,
planted underneath the timed path, seen to turn ``correct`` false."""

import copy
import json
import re
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import helpers  # noqa: E402


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    return helpers.make_tree(tmp_path_factory.mktemp("bench"))


def names(result):
    return {c["name"]: c for c in result["checks"]}


@pytest.mark.parametrize("trace", [0, 1])
def test_pair_cell_rehearses(tree, trace):
    rc, res, out = helpers.rehearse(tree, "tiny-pair", trace=trace)
    assert rc == 0, out
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert list(res)[-1] == "checks" and len(res["checks"]) == 6
    assert res["device"]["platform"] == "cpu"
    if trace:
        assert {"idle_attach_pct", "proxy_self_ms_per_exec",
                "gate_books_gap_pct", "step_mfu"} <= set(res["metrics"])
        # nothing ran on a TPU plane: the device readers say nothing
        assert "device_idle_pct" not in res["metrics"]
        assert "attn_roofline" not in res["metrics"]
        assert {"busy_s", "window_s"} <= set(res["device"])
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s",
                                       "worst_share_kept_pct", "setup_s"}
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0
        assert 50 < res["metrics"]["worst_share_kept_pct"]["value"] <= 100


def test_score_cell_rehearses(tree):
    rc, res, out = helpers.rehearse(tree, "tiny-score-vs-train")
    assert rc == 0, out
    assert res["correct"] is True and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "req_p95_ms",
                                   "setup_s"}
    got = names(res)
    assert got["scorer.unanswered"]["value"] == 0
    assert got["scorer.score_gap"]["value"] < got["scorer.score_gap"]["limit"]
    assert "generator late ms max" in out and "answered" in out


def snapshot(bench):
    return {p: p.read_bytes() for p in bench.rglob("*")
            if p.is_file() and "__pycache__" not in p.parts}


def test_a_cell_is_added_by_data_alone(tmp_path):
    """One configuration file, one mix file, one ``workloads`` entry with
    its name on the lists of the metrics it reports (and the cell's
    limits): no file that was there is edited."""
    root = helpers.make_tree(tmp_path)
    bench = root / "benchmark"
    before = snapshot(bench)
    manifest_before = json.loads((root / "BENCHMARK.json").read_text())
    cfg = dict(helpers.TINY_CONFIG, n_layer=1, n_embd=32, n_head=2)
    cfg["parameters_as_run"] = helpers.tiny_params(cfg)
    (bench / "configs" / "tinier.json").write_text(json.dumps(cfg))
    (bench / "mixes" / "solo.json").write_text(json.dumps(
        {"chips": 1, "mesh": None,
         "tenants": [helpers.trainer("only", 1.0, mem=0.9)]}))
    (bench / "limits" / "tinier-solo.json").write_text(
        json.dumps(helpers.TINY_LIMITS))
    manifest = copy.deepcopy(manifest_before)
    manifest["configs"].append({"name": "tinier", "source": "test",
                                "file": "benchmark/configs/tinier.json",
                                "reduced": [], "why": "data-only"})
    manifest["workloads"].append({"name": "tinier-solo", "config": "tinier",
                                  "traffic": "solo", "chips": 1,
                                  "why": "data-only"})
    helpers.add_to_lists(manifest, "tinier-solo",
                         {"train_tokens_per_s", "step_mfu"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    rc, res, out = helpers.rehearse(root, "tinier-solo")
    assert rc == 0, out
    assert res["correct"] is True
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


FAMILY2 = Path(__file__).resolve().parent / "data" / "family2"
FAMILY2_CONFIG = {
    "model_type": "family2", "hidden_size": 64, "num_hidden_layers": 2,
    "num_attention_heads": 2, "max_position_embeddings": 128,
    "vocab_size": 256, "parameters_as_run": helpers.tiny_params(
        helpers.TINY_CONFIG),           # the same mathematics and sizes
    "reference": "benchmark/reference/family2.py",
    "binding": "benchmark/models/family2.py",
    "counts": "benchmark/counts/family2.py",
    "precision": helpers.TINY_CONFIG["precision"],
}


def test_a_family_is_added_by_files_alone(tmp_path):
    """A configuration of another family (other key names, none of
    GPT-2's) with its binding, its counts and its reference: three files
    that the configuration names. The roles, the readers and the runner
    are the ones that were there."""
    pair = {"chips": 1, "mesh": None,
            "tenants": [helpers.trainer("tenant-a", 0.5),
                        helpers.trainer("tenant-b", 0.5)]}
    root = helpers.make_tree(tmp_path, mixes={"family2-pair": pair},
                             config=FAMILY2_CONFIG,
                             like={"family2-pair": "gpt2s-pair-even"})
    bench = root / "benchmark"
    before = snapshot(bench)
    for sub, name in (("models", "binding"), ("counts", "counts"),
                      ("reference", "reference")):
        assert not (bench / sub / "family2.py").exists()
        shutil.copy(FAMILY2 / f"{name}.py", bench / sub / "family2.py")
    rc, res, out = helpers.rehearse(root, "family2-pair", trace=1)
    assert rc == 0, out
    assert res["correct"] is True and res["failed"] == 0
    assert len(res["checks"]) == 6
    assert res["metrics"]["step_mfu"]["value"] > 0
    assert "gate_books_gap_pct" in res["metrics"]
    for path, content in before.items():
        assert path.read_bytes() == content, f"{path} was edited"


def test_a_cell_may_have_no_trainer(tmp_path):
    """Two scorers and nobody who trains: the cell appends itself to the
    lists of the metrics it reports, ``train_tokens_per_s`` is not asked
    for, and the run is a run."""
    root = helpers.make_tree(tmp_path, mixes={})
    bench = root / "benchmark"
    (bench / "mixes" / "two-scorers.json").write_text(json.dumps(
        {"chips": 1, "mesh": None,
         "tenants": [helpers.scorer("scorer-a"),
                     helpers.scorer("scorer-b")]}))
    (bench / "limits" / "tiny-two-scorers.json").write_text(
        json.dumps(helpers.TINY_LIMITS))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["workloads"] = [{"name": "tiny-two-scorers", "config": "tiny",
                              "traffic": "two-scorers", "chips": 1,
                              "why": "no trainer"}]
    helpers.add_to_lists(manifest, "tiny-two-scorers",
                         {"req_p95_ms", "gate_wait_ms.req"})
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    for trace, reported in ((0, {"req_p95_ms", "setup_s"}),
                            (1, {"gate_wait_ms.req"})):
        rc, res, out = helpers.rehearse(root, "tiny-two-scorers",
                                        trace=trace)
        assert rc == 0, out
        assert res["correct"] is True and res["failed"] == 0
        assert set(res["metrics"]) == reported
        assert {c["name"] for c in res["checks"]} == {
            "scorer-a.score_gap", "scorer-a.unanswered",
            "scorer-b.score_gap", "scorer-b.unanswered"}


FAMILY_WORDS = re.compile(
    r"\b(?:n_embd|n_head|n_layer|n_positions)\b"
    r"|^\s*(?:from|import)\s+kubeshare_tpu\.models", re.MULTILINE)
FAMILY_PLACES = ("models/gpt2.py", "counts/gpt2.py", "reference/gpt2.py")


def test_only_a_family_s_own_files_know_its_names():
    """Outside the GPT-2 family's three files, the configurations and the
    tests, no file of the benchmark holds one of GPT-2's key names or
    imports the program's models."""
    knows = []
    for path in sorted(helpers.BENCH.rglob("*")):
        rel = path.relative_to(helpers.BENCH).as_posix()
        if (not path.is_file() or "__pycache__" in path.parts
                or rel in FAMILY_PLACES
                or rel.startswith(("configs/", "tests/"))):
            continue
        if FAMILY_WORDS.search(path.read_text(errors="replace")):
            knows.append(rel)
    assert knows == []


STUCK = '''
import jax
def _stuck(loss_fn, optimizer):
    def step(params, opt_state, tokens, targets):
        return params, opt_state, loss_fn(params, (tokens, targets))
    return jax.jit(step)
base.make_step = _stuck
'''
HALF = '''
_make_step = base.make_step
def _half(loss_fn, optimizer):
    def loss_of_half(params, batch):
        n = batch[0].shape[0] // 2
        return loss_fn(params, (batch[0][:n], batch[1][:n]))
    return _make_step(loss_of_half, optimizer)
base.make_step = _half
'''
ALTERED = '''
_make_score = base.make_score
def _altered(logits_fn):
    score = _make_score(logits_fn)
    return lambda params, tokens, length: 1.01 * score(params, tokens, length)
base.make_score = _altered
'''


@pytest.mark.parametrize("fault,base,patch,caught_by", [
    ("state_unchanged", "train", STUCK, "update_norm_gap"),
    ("half_batch", "train", HALF, "grad_norm_gap"),
    ("answer_altered", "score", ALTERED, "score_gap"),
])
def test_a_fault_under_the_timed_path_turns_correct_false(
        tmp_path, fault, base, patch, caught_by):
    root = helpers.make_tree(tmp_path, mixes={})
    role = f"{base}_{fault}"
    helpers.add_role(root, role, base, patch)
    bench = root / "benchmark"
    tenants = ([helpers.scorer(role=role), helpers.trainer("trainer", 0.7)]
               if base == "score" else
               [helpers.trainer("tenant-a", 0.5, role=role),
                helpers.trainer("tenant-b", 0.5)])
    (bench / "mixes" / "faulty.json").write_text(json.dumps(
        {"chips": 1, "mesh": None, "tenants": tenants}))
    (bench / "limits" / "faulty.json").write_text(
        json.dumps(helpers.TINY_LIMITS))
    manifest = json.loads((root / "BENCHMARK.json").read_text())
    manifest["workloads"] = [{"name": "faulty", "config": "tiny",
                              "traffic": "faulty", "chips": 1, "why": "x"}]
    for group in ("end_to_end", "per_layer"):
        for m in manifest[group]:
            m.pop("workloads", None)
        manifest[group] = [m for m in manifest[group]
                           if m["name"] in ("train_tokens_per_s", "setup_s",
                                            "step_mfu")]
    (root / "BENCHMARK.json").write_text(json.dumps(manifest))
    rc, res, out = helpers.rehearse(root, "faulty")
    assert rc == 0, out
    assert res["correct"] is False
    broken = tenants[0]["name"]
    got = names(res)
    assert got[f"{broken}.{caught_by}"]["ok"] is False
    sound = [c for c in res["checks"] if not c["name"].startswith(broken)]
    assert sound and all(c["ok"] for c in sound)


def test_off_the_chip_there_is_no_result(tree):
    """The default plan wants platform 'tpu'; here JAX is held to the CPU:
    the run exits non-zero and prints no result line."""
    run_mod = helpers.load_run(tree)
    plan = helpers.cpu_plan(run_mod, platform="tpu")
    import contextlib
    import io
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = run_mod.main(["--workload", "tiny-pair", "--seed", "1",
                           "--seconds", "1", "--trace", "0"], plan=plan)
    assert rc != 0
    assert not any(line.startswith("{") for line in
                   out.getvalue().splitlines())


def test_a_device_kind_without_peaks_is_an_error(tmp_path):
    root = helpers.make_tree(tmp_path)
    peaks_path = root / "benchmark" / "peaks.json"
    peaks = json.loads(peaks_path.read_text())
    del peaks["kinds"]["cpu"]
    peaks_path.write_text(json.dumps(peaks))
    rc, res, out = helpers.rehearse(root, "tiny-pair")
    assert rc != 0 and res is None


def test_a_directory_with_only_the_benchmark_gives_no_result(tmp_path):
    root = helpers.make_tree(tmp_path)
    (root / "kubeshare_tpu").unlink()
    rc, res, out = helpers.rehearse(root, "tiny-pair")
    assert rc != 0 and res is None
