"""The MiniCPM-SALA family's three files (binding, counts, reference) and
its readers through the same ``run.py`` at a tiny size on the CPU: a
rehearsal tree built by ``helpers.make_tree`` with the family's
configuration as its ``config`` argument and a two-scorer mix as
``chat-behind-docs`` has it (documents on both sides of ``dense_len``);
the program-by-program reader on the tests' recorded trace."""

import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parent))
import helpers  # noqa: E402

TINY_SALA = {
    "model_type": "minicpm_sala", "hidden_size": 64,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "lightning_nh": 4, "lightning_nkv": 4, "lightning_head_dim": 16,
    "intermediate_size": 160, "vocab_size": 256, "rms_norm_eps": 1e-06,
    "rope_theta": 10000, "scale_emb": 12, "scale_depth": 1.4,
    "dim_model_base": 256, "num_hidden_layers": 4,
    "mixer_types": ["minicpm4", "lightning-attn", "lightning-attn",
                    "lightning-attn"],
    "published": {"num_hidden_layers": 8, "vocab_size": 2048},
    "deployment": {"positions_as_run": 256},
    "assumed": {"kernel_size": 8, "kernel_stride": 4, "block_size": 8,
                "init_blocks": 1, "window_size": 16, "topk": 4,
                "dense_len": 64, "decay_exponent": 8, "scan_chunk": 16},
    "precision": {"params": "bfloat16", "matmul": "bfloat16",
                  "control": "float8_e4m3fn"},
    "reference": "benchmark/reference/minicpm_sala.py",
    "binding": "benchmark/models/minicpm_sala.py",
    "counts": "benchmark/counts/minicpm_sala.py",
}


def scorer(name, rate, median, low, high, buckets, mem):
    return {"name": name, "role": "score", "attach": "proxy",
            "tpu_request": 0.4, "tpu_limit": 1.0, "tpu_mem_fraction": mem,
            "arrivals": {"process": "poisson", "rate_per_s": rate},
            "lengths": {"kind": "lognormal", "median": median, "sigma": 0.6,
                        "min": low, "max": high},
            "buckets": buckets}


#: as ``mixes/chat-behind-docs.json``, at the rehearsal's size: documents
#: on both sides of ``dense_len`` (64), chat requests far below it
PAIR = {"chips": 1, "mesh": None,
        "tenants": [scorer("docs", 1.5, 110, 40, 256, [64, 128, 256], 0.5),
                    scorer("chat", 6.0, 20, 8, 32, [16, 32], 0.3)]}
#: for THIS size (bfloat16 through four layers at width 64, chat requests
#: of 8-32 tokens, block choices that differ included): sound rehearsals
#: read 7e-4 to 8.3e-3 over six seeds (4.0e-3 on the one rehearsed here),
#: the float8_e4m3 control 1.4e-2 to 4.6e-2 and an answer with its last
#: token left out 3.0e-2 to 7.7e-2 (`chat`, five seeds)
LIMITS = {"numbers": {"score_gap": {"limit": 1e-2},
                      "unanswered": {"limit": 0}}}


@pytest.fixture(scope="module")
def tree(tmp_path_factory):
    helpers.forget_other_trees(helpers.REPO)
    import readlib
    cfg = dict(TINY_SALA)
    cfg["parameters_as_run"] = readlib.named(cfg, "counts").parameters(cfg)
    return helpers.make_tree(
        tmp_path_factory.mktemp("sala"), mixes={"tiny-sala": PAIR},
        limits=LIMITS, config=cfg,
        like={"tiny-sala": "sala-chat-behind-docs"})


@pytest.mark.parametrize("trace", [0, 1])
def test_the_two_scorer_cell_of_the_family_rehearses(tree, trace):
    rc, res, out = helpers.rehearse(tree, "tiny-sala", trace=trace,
                                    seconds=4.0)
    assert rc == 0, out
    assert res["correct"] is True and res["failed"] == 0
    assert {c["name"] for c in res["checks"]} == {
        "docs.score_gap", "docs.unanswered", "chat.score_gap",
        "chat.unanswered"}
    if trace:
        assert {"score_mfu", "gate_wait_ms.req"} <= set(res["metrics"])
        assert res["metrics"]["score_mfu"]["value"] > 0
        # nothing ran on a TPU plane: the device readers say nothing
        assert not {"lin_attn_roofline", "sparse_attn_roofline",
                    "sparse_select_pct"} & set(res["metrics"])
    else:
        assert set(res["metrics"]) == {"req_p95_ms", "setup_s"}
        assert res["metrics"]["req_p95_ms"]["value"] > 0


def test_the_program_readers_on_a_recorded_trace(tmp_path):
    """``programtime.by_program`` on the recorded v5e trace of the tests
    (a trainer's: no ``ks.device`` bracket in it): no program is listed,
    and the readers say nothing (never 0) of a run without whole
    programs, of an untraced run and of a trace that is gone."""
    helpers.forget_other_trees(helpers.REPO)
    import programtime
    import readlib
    trace = str(helpers.BENCH / "tests" / "data" / "tiny_v5e.xplane.pb")
    assert programtime.by_program(trace) == {"programs": []}
    gone = {"trace": {"busy_s": 1.0, "from_s": 1.0},
            "proxy": {"trace": {"dir": str(tmp_path / "gone"),
                                "start": 5.0}}, "tenants": []}
    for name in ("lin_attn_roofline", "sparse_attn_roofline",
                 "sparse_select_pct"):
        assert readlib.reader(name).read(gone) is None
        assert readlib.reader(name).read({"trace": None, "proxy": {},
                                          "tenants": []}) is None


def test_a_programs_time_is_the_union_of_its_ops():
    """``programtime.assign``: an op belongs to the bracket it began in
    (the device's clock a little ahead of the host's), and a ``while``
    that holds its body's ops is not counted beside them: a 50 ms loop
    and the 50 ms of ops in it are 50 ms of the program, not 100."""
    helpers.forget_other_trees(helpers.REPO)
    import programtime
    ms = 1_000_000
    brackets = [{"session": "b", "lo": 200 * ms, "hi": 210 * ms,
                 "mono_s": 7.2},
                {"session": "a", "lo": 100 * ms, "hi": 160 * ms,
                 "mono_s": 7.1}]
    ops = [(100 * ms - 500_000, 10 * ms, []),          # before the loop
           (110 * ms, 50 * ms, []),                    # the loop itself
           (110 * ms, 20 * ms, ["bench_x"]), (130 * ms, 20 * ms, ["bench_x"]),
           (150 * ms, 10 * ms, ["bench_y"]),           # its body
           (180 * ms, 5 * ms, ["bench_x"]),            # in no bracket
           (201 * ms, 4 * ms, ["bench_x", "bench_y"])]
    a, b = programtime.assign(brackets, ops)
    assert (a["session"], b["session"]) == ("a", "b")
    assert a["device_s"] == pytest.approx(0.010 + 0.050)
    assert a["scopes"] == pytest.approx({"bench_x": 0.04, "bench_y": 0.01})
    assert a["end_mono_s"] - a["start_mono_s"] == pytest.approx(0.06)
    assert b["device_s"] == pytest.approx(0.004)
    assert b["scopes"] == pytest.approx({"bench_x": 0.004, "bench_y": 0.004})


def _joined(monkeypatch, programs, rows):
    """A traced run's record whose ``programtime.read`` gives ``programs``
    and whose tenants answered ``rows`` (by tenant name)."""
    import programtime
    import readlib
    t0 = 100.0
    for p in programs:
        p["start_mono_s"] += t0
        p["end_mono_s"] += t0
    monkeypatch.setattr(programtime, "read",
                        lambda run: {"programs": programs})
    monkeypatch.setattr(readlib, "requests",
                        lambda run, t: rows[t["name"]])
    return {"trace": {"from_s": 1.0, "busy_s": 2.0},
            "proxy": {"trace": {"start": t0 + 1.0}},
            "tenants": [{"name": n, "role": "score", "pod": f"{n}/pod-0"}
                        for n in rows]}


def test_programs_join_requests_by_session_and_time(monkeypatch):
    """``of_requests``: a program is joined to the request of ITS session
    that was answered next after its bracket closed; one that no answer
    follows in time is left out."""
    helpers.forget_other_trees(helpers.REPO)
    import programtime
    run = _joined(monkeypatch, [
        {"session": "docs/pod-0", "start_mono_s": 1.0, "end_mono_s": 1.5,
         "device_s": 0.45, "scopes": {"bench_x": 0.2}},
        {"session": "chat/pod-0", "start_mono_s": 1.5, "end_mono_s": 1.51,
         "device_s": 0.008, "scopes": {"bench_x": 0.001}},
        {"session": "chat/pod-0", "start_mono_s": 2.5, "end_mono_s": 2.6,
         "device_s": 0.09, "scopes": {}}],
        {"docs": [{"idx": 0, "done_s": 1.502, "bucket": 128}],
         "chat": [{"idx": 0, "done_s": 0.4, "bucket": 16},
                  {"idx": 1, "done_s": 1.512, "bucket": 32},
                  {"idx": 2, "done_s": 3.4, "bucket": 16}]})
    got = programtime.of_requests(run)
    assert [(t["name"], r["idx"], p["scopes"].get("bench_x"), p["device_s"])
            for t, r, p in got] == [("docs", 0, 0.2, 0.45),
                                    ("chat", 1, 0.001, 0.008)]


#: one document's program by bucket as the chip ran it (PERF.md section 5):
#: device seconds, and those under the two sparse scopes and the scan's
LONG = {"device_s": 0.6666, "scopes": {"bench_sparse_attn": 0.0761,
                                       "bench_sparse_select": 0.0489,
                                       "bench_lin_attn": 0.0088}}
MID = {"device_s": 0.3041, "scopes": {"bench_sparse_attn": 0.0212,
                                      "bench_sparse_select": 0.0128,
                                      "bench_lin_attn": 0.0042}}


@pytest.mark.parametrize("held", ["long", "long+mid", "mid"])
def test_the_sparse_readers_read_one_stated_bucket(monkeypatch, held):
    """``sparse_attn_roofline`` and ``sparse_select_pct`` are of the
    longest bucket's programs alone, the selection over THEIR device time:
    a 16,384-token document beside the 32,768 one moves neither, and a
    window with the shorter one alone says nothing. The scan's reader
    takes every program."""
    helpers.forget_other_trees(helpers.REPO)
    import readlib
    cfg = json.loads((helpers.BENCH / "configs" / "minicpm-sala.json")
                     .read_text())
    peaks = json.loads((helpers.BENCH / "peaks.json").read_text())
    programs, rows, at = [], [], 1.0
    for name in held.split("+"):
        took, bucket = (LONG, 32768) if name == "long" else (MID, 16384)
        programs.append(dict(took, session="docs/pod-0", start_mono_s=at,
                             end_mono_s=at + took["device_s"]))
        at += took["device_s"] + 0.01
        rows.append({"idx": len(rows), "done_s": at - 0.005,
                     "bucket": bucket})
    run = _joined(monkeypatch, programs, {"docs": rows})
    run.update(config=cfg, peaks=peaks["kinds"]["TPU v5 lite"])
    attn = readlib.reader("sparse_attn_roofline").read(run)
    select = readlib.reader("sparse_select_pct").read(run)
    scan = readlib.reader("lin_attn_roofline").read(run)
    if "long" in held:
        need = readlib.count(cfg, "sparse_attention_layer")(cfg, 32768)
        least = readlib.flops.least_seconds(need, run["peaks"])[0]
        assert attn == pytest.approx(100 * least / 0.0761)
        assert 10 < attn < 20
        assert select == pytest.approx(100 * 0.0489 / 0.6666)
    else:
        assert attn is None and select is None
    assert 30 < scan < 100


@pytest.mark.parametrize("seed", [5, 2**31 + 9, 77])
def test_the_control_comes_out_not_correct(tree, seed):
    """``control.py`` on the rehearsal tree, as a cell's limits are set:
    the reference in the configuration's ``precision.control`` in the
    program's place, and an answer with its last token left out, both FAIL
    the comparison against the tree's limit by ``chat.score_gap`` (one of
    the cell's numbers, as the contract asks: ``docs`` averages over ten
    times the tokens and parts less), on every seed."""
    import importlib.util
    helpers.forget_other_trees(tree)
    spec = importlib.util.spec_from_file_location(
        "bench_control_sala", tree / "benchmark" / "control.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    got = mod.readings(tree, "tiny-sala", seed, 4.0, sample_requests=8)
    limit = LIMITS["numbers"]["score_gap"]["limit"]
    assert got["chat"]["control"]["score_gap"] > limit, got
    assert got["chat"]["faults"]["answer_altered"]["score_gap"] > limit, got
    for tenant in ("docs", "chat"):
        assert got[tenant]["control"]["unanswered"] == 0
        assert got[tenant]["control"]["score_gap"] > 1e-4
        assert got[tenant]["faults"]["answer_altered"]["score_gap"] > 1e-4


def test_the_cells_limit_lies_between_its_readings():
    """``limits/sala-chat-behind-docs.json`` against the readings it
    records: the limit has room on both sides, ``upper`` is the SMALLEST
    failing reading of the tenant that holds the precision (not one picked
    to fit), every seed of that tenant's control and planted fault reads
    above the limit and every sound run under it; the control it names is
    the configuration's own."""
    cfg = json.loads((helpers.BENCH / "configs" / "minicpm-sala.json")
                     .read_text())
    gap = json.loads((helpers.BENCH / "limits" / "sala-chat-behind-docs.json")
                     .read_text())["numbers"]["score_gap"]
    assert cfg["precision"]["control"] == "float8_e4m3fn"
    chat = gap["by_tenant"]["chat"]
    control, fault = chat["float8_e4m3_control"], chat["last_token_left_out"]
    assert gap["lower"] == pytest.approx(chat["sound"]["largest"], rel=1e-2)
    assert gap["upper"] == pytest.approx(
        min(control["smallest"], fault["smallest"]), rel=1e-2)
    assert 1.3 * gap["lower"] < gap["limit"] < gap["upper"] / 1.3
    for failing in (control, fault):
        assert failing["seeds"] >= 8
        assert failing["above_the_limit"] == failing["seeds"]
        assert failing["smallest"] > gap["limit"]
    assert chat["sound"]["runs"] >= 8
    # what the file says the limit does not part is said with its count
    assert chat["int8"]["smallest"] < gap["limit"] < chat["int8"]["largest"]
    assert chat["int8"]["above_the_limit"] < chat["int8"]["seeds"]
    for name, read in gap["by_tenant"]["docs"].items():
        if isinstance(read, dict):
            assert read["largest"] < gap["limit"], name
