"""The LFM2 family's three files (binding, counts, reference) through the
same ``run.py`` at a tiny size on the CPU: a rehearsal tree built by
``helpers.make_tree`` with the family's configuration as its ``config``
argument, a one-tenant mix as ``solo-elastic`` has it; the counts against
the program's leaf shapes; the new reader's helper on a recorded trace."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import helpers  # noqa: E402

TINY_LFM2 = {
    "model_type": "lfm2_moe", "hidden_size": 64, "num_attention_heads": 8,
    "num_key_value_heads": 2, "intermediate_size": 160,
    "moe_intermediate_size": 48, "conv_L_cache": 3, "norm_eps": 1e-05,
    "layer_types": ["conv", "full_attention", "conv", "conv", "conv"],
    "num_hidden_layers": 5, "num_dense_layers": 1, "num_experts": 4,
    "num_experts_per_tok": 4, "routed_scaling_factor": 1,
    "vocab_size": 256, "max_position_embeddings": 128,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "published": {"num_experts": 16, "vocab_size": 2048},
    "deployment": {"first_expert": 4, "chips_sharing_a_layer": 4},
    "reference": "benchmark/reference/lfm2.py",
    "binding": "benchmark/models/lfm2.py",
    "counts": "benchmark/counts/lfm2.py",
    "precision": {"params": "float32", "matmul": "bfloat16",
                  "router": "float32", "control": "int8"},
}
#: as ``mixes/solo-elastic.json``, at the rehearsal's size
SOLO = {"chips": 1, "mesh": None,
        "tenants": [helpers.trainer("trainer", 0.5, mem=0.95)]}
#: for THIS size, as TINY_LIMITS is for GPT-2's: sound rehearsals read
#: loss 1e-4, norms 1e-2 (bfloat16 through five layers at width 64)
LIMITS = {"numbers": {"loss_gap": {"limit": 1e-3},
                      "grad_norm_gap": {"limit": 0.1},
                      "update_norm_gap": {"limit": 0.05}}}


def leaves(cfg):
    """The program's parameter count for ``cfg``, from shapes alone."""
    import jax
    import numpy as np
    helpers.forget_other_trees(helpers.REPO)
    import readlib
    shapes = jax.eval_shape(readlib.named(cfg, "binding").init(cfg),
                            jax.ShapeDtypeStruct((2,), np.uint32))
    return sum(int(np.prod(s.shape))
               for s in jax.tree_util.tree_leaves(shapes))


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    cfg = dict(TINY_LFM2, parameters_as_run=leaves(TINY_LFM2))
    return helpers.make_tree(
        tmp_path_factory.mktemp("lfm2"), mixes={"tiny-solo": SOLO},
        limits=LIMITS, config=cfg,
        like={"tiny-solo": "lfm2moe-solo-elastic"})


@pytest.mark.parametrize("trace", [0, 1])
def test_the_solo_cell_of_the_lfm2_family_rehearses(tree, trace):
    rc, res, out = helpers.rehearse(tree, "tiny-solo", trace=trace)
    assert rc == 0, out
    assert res["correct"] is True and res["failed"] == 0
    assert {c["name"] for c in res["checks"]} == {
        "trainer.loss_gap", "trainer.grad_norm_gap",
        "trainer.update_norm_gap"}
    if trace:
        assert {"step_mfu", "idle_attach_pct", "rpcs_per_exec"} <= set(
            res["metrics"])
        # nothing ran on a TPU plane: the device readers say nothing
        assert not {"moe_route_pct", "attn_roofline",
                    "device_idle_pct"} & set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
        assert res["metrics"]["train_tokens_per_s"]["value"] > 0


def test_counts_of_the_published_configuration():
    helpers.forget_other_trees(helpers.REPO)
    import readlib
    cfg = json.loads((helpers.BENCH / "configs" / "lfm2-24b-a2b.json")
                     .read_text())
    counts = readlib.named(cfg, "counts")
    assert readlib.sizes(cfg) == {"vocab": 8192, "positions": 128000}
    # 6 N a token over 8,192 tokens, N = 167.2 M parameters that surely
    # multiply (the held experts' matrices left out: a floor), plus six
    # causal attention matmuls in the one attention layer
    n = counts.multiplying_params(cfg)
    assert round(n / 1e6, 1) == 167.2
    attn = 2 * 2.0 * 4096 * 4096 * 2048 / 2
    assert counts.train_flops(cfg, 2, 4096) == 6 * n * 8192 + 6 * attn
    a = counts.attention_layer(cfg, 2, 4096, backward=False)
    assert a["bytes"] == 8192 * (2048 + 2 * 512) * 2 + 8192 * 2048 * 4


def test_the_scope_readers_split_a_recorded_trace(tmp_path):
    """``scopetime.by_scope`` on the recorded v5e trace of the tests: an
    event belongs to a scope whose name stands whole in its metadata, a
    scope nobody ran under is left out (its reader then says nothing),
    and the scopes asked for are those the readers declare."""
    helpers.forget_other_trees(helpers.REPO)
    import scopetime
    trace = str(helpers.BENCH / "tests" / "data" / "tiny_v5e.xplane.pb")
    got = scopetime.by_scope(trace, ("bench_attn", "bench_opt", "bench_moe"))
    assert got["bench_attn"]["events"] > 0 and got["bench_opt"]["seconds"] > 0
    assert "bench_moe" not in got
    # a name that only begins another's claims none of its events
    assert scopetime.by_scope(trace, ("bench_at", "bench_attn")) == {
        "bench_attn": got["bench_attn"]}
    assert scopetime.declared() == ("bench_moe_route",)
    run = {"trace": {"busy_s": 1.0}, "proxy": {"trace": {"dir": str(
        tmp_path / "gone")}}}
    assert scopetime.seconds(run, "bench_moe") is None
    assert scopetime.seconds({"trace": None, "proxy": {}}, "bench_moe") is None


def test_the_control_and_the_planted_faults_read_apart_from_the_reference(
        tree):
    """``control.py``'s readings on the rehearsal tree: the reference in
    int8 in the program's place, and each planted fault, read well above
    what a sound run of this size reads (3e-3 on the norms)."""
    import importlib.util
    helpers.forget_other_trees(tree)
    spec = importlib.util.spec_from_file_location(
        "bench_control_lfm2", tree / "benchmark" / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.readings(tree, "tiny-solo", 2**31 + 9, 3.0)["trainer"]
    assert got["control"]["grad_norm_gap"] > 0.02
    assert got["control"]["update_norm_gap"] > 0.005
    assert got["faults"]["half_batch"]["grad_norm_gap"] > 0.3
    assert got["faults"]["state_unchanged"]["update_norm_gap"] == 1.0
