"""The arrival schedule, the length draw and the token batches are a pure
function of the seed, and every seed offers the same work."""

import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import traffic  # noqa: E402

ARR = {"process": "poisson", "rate_per_s": 20.0}
LEN = {"kind": "lognormal", "median": 300, "sigma": 0.7, "min": 64,
       "max": 1024}
BUCKETS = [128, 256, 512, 1024]
BIG = 2**31 + 12345         # more than 32 signed bits hold


def sched(seed, seconds=30.0):
    return traffic.request_schedule(seed, 0, ARR, LEN, BUCKETS, seconds)


def test_schedule_is_a_pure_function_of_the_seed():
    assert sched(BIG) == sched(BIG)
    assert sched(BIG) != sched(BIG + 1)


def test_every_seed_offers_the_same_lengths_and_gaps():
    a, b = sched(7), sched(BIG)
    assert len(a) == len(b) == 600
    assert sorted(r["length"] for r in a) == sorted(r["length"] for r in b)
    gaps = lambda s: np.sort(np.diff([0.0] + [r["due_s"] for r in s]))
    np.testing.assert_allclose(gaps(a), gaps(b), rtol=0, atol=1e-9)
    assert [r["due_s"] for r in a] == sorted(r["due_s"] for r in a)
    assert 0 < a[0]["due_s"] and a[-1]["due_s"] < 30.0


def test_lengths_follow_the_stated_distribution():
    lens = np.array([r["length"] for r in sched(3)])
    assert lens.min() >= 64 and lens.max() <= 1024
    assert 280 <= np.median(lens) <= 320
    assert all(r["bucket"] >= r["length"] for r in sched(3))
    assert {r["bucket"] for r in sched(3)} == set(BUCKETS)


def test_token_batches_differ_by_row_step_tenant_and_seed():
    t, y = traffic.token_batch(BIG, 0, 0, 8, 1024, 50257)
    assert t.shape == y.shape == (8, 1024) and t.dtype == np.int32
    assert (t[:, 1:] == y[:, :-1]).all()
    assert len({row.tobytes() for row in t}) == 8
    again = traffic.token_batch(BIG, 0, 0, 8, 1024, 50257)[0]
    assert (t == again).all()
    for other in (traffic.token_batch(BIG, 0, 1, 8, 1024, 50257)[0],
                  traffic.token_batch(BIG, 1, 0, 8, 1024, 50257)[0],
                  traffic.token_batch(BIG + 1, 0, 0, 8, 1024, 50257)[0]):
        assert (t != other).any()
    assert 0 <= t.min() and t.max() < 50257


def test_request_tokens_pad_past_the_length():
    toks = traffic.request_tokens(BIG, 0, 5, 100, 128, 50257)
    assert toks.shape == (1, 128) and (toks[0, 100:] == 0).all()
    assert (toks == traffic.request_tokens(BIG, 0, 5, 100, 128, 50257)).all()


def test_key_words_take_large_seeds_and_differ_by_stream():
    a, b = traffic.key_words(BIG, 0), traffic.key_words(BIG, 1)
    assert a.dtype == np.uint32 and a.shape == (2,) and (a != b).any()
    assert (a == traffic.key_words(BIG, 0)).all()


def test_sample_holds_the_longest_finished_request():
    s = sched(11)
    finished = {r["idx"] for r in s[:400]}
    pick = traffic.sample_requests(11, 0, s, finished, 32)
    assert len(pick) == 32 and set(pick) <= finished
    longest = max((r for r in s if r["idx"] in finished),
                  key=lambda r: r["length"])
    assert longest["length"] == max(r["length"] for r in s
                                    if r["idx"] in pick)
    assert pick == traffic.sample_requests(11, 0, s, finished, 32)


@pytest.mark.parametrize("bad", [{"process": "bursty", "rate_per_s": 1}])
def test_an_unknown_arrival_process_is_an_error_that_names_it(bad):
    with pytest.raises(ValueError, match="bursty"):
        traffic.request_schedule(1, 0, bad, LEN, BUCKETS, 10.0)


def test_an_unknown_length_distribution_is_an_error_that_names_it():
    with pytest.raises(ValueError, match="zipf"):
        traffic.request_lengths(10, {"kind": "zipf"})
