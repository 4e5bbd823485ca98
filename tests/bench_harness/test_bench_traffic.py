"""The traffic generator and the window's arithmetic, with no device."""
import collections
import json
import os

import numpy as np
import pytest

from conftest import BENCH, ROOT

from benchmarks import harness, traffic
from benchmarks.drivers import closed_loop_decode as cld


@pytest.fixture(scope="module")
def chat():
    with open(os.path.join(BENCH, "traffic", "chat-c16.json")) as f:
        return json.load(f)


def test_any_two_seeds_same_lengths_other_order_and_ids(chat):
    a = traffic.Schedule(chat, 50257, 11)
    b = traffic.Schedule(chat, 50257, 3000000019)   # past 2**31, as the driver's
    n = len(chat["table"])
    table = collections.Counter(map(tuple, chat["table"]))
    for cycle in range(3):
        ks = range(cycle * n, (cycle + 1) * n)
        la, lb = [a.lengths(k) for k in ks], [b.lengths(k) for k in ks]
        assert collections.Counter(la) == collections.Counter(lb) == table
        assert la != lb
    assert [a.lengths(k) for k in range(n)] \
        != [a.lengths(k) for k in range(n, 2 * n)]      # reshuffled per cycle
    (pa, oa), (pa2, _) = a.request(5), a.request(5)
    assert np.array_equal(pa, pa2) and (len(pa), oa) == a.lengths(5)
    pb, _ = b.request(5)
    assert len(pb) != len(pa) or not np.array_equal(pa, pb)
    assert pa.dtype == np.int32 and pa.min() >= 0 and pa.max() < 50257


def test_blocks_keep_the_load_even_along_a_cycle(chat):
    """Whatever the seed, any 8 consecutive requests of a cycle hold one
    prompt of each eighth of the prompts and one output of each eighth of
    the outputs."""
    n = len(chat["table"])
    eighth = [{v: i * 8 // n for i, v in enumerate(sorted(
        e[c] for e in chat["table"]))} for c in (0, 1)]
    for seed in (1, 2999999999):
        s = traffic.Schedule(chat, 50257, seed)
        for at in range(0, 2 * n, 8):
            got = [s.lengths(k) for k in range(at, at + 8)]
            for c in (0, 1):
                # a value on an eighth's border may rank in either
                assert len({eighth[c][e[c]] for e in got}) >= 7
    with pytest.raises(ValueError, match="exactly once"):
        traffic.Schedule(dict(chat, blocks=[[0, 0, 1]]), 50257, 1)


def test_chat_table_is_what_the_mix_file_says_it_is(chat):
    from statistics import NormalDist
    import math
    nd, n = NormalDist(), len(chat["table"])

    def column(d):
        return [int(min(d["max"], max(d["min"], round(d["median"] * math.exp(
            d["sigma"] * nd.inv_cdf((i + 0.5) / n)))))) for i in range(n)]
    prompts = column(chat["lengths"]["prompt"])
    outputs = column(chat["lengths"]["output"])
    assert sorted(e[0] for e in chat["table"]) == prompts
    assert sorted(e[1] for e in chat["table"]) == outputs
    # the published means, to a tenth of a token
    for col, d in ((prompts, "prompt"), (outputs, "output")):
        assert abs(sum(col) / n - chat["lengths"][d]["mean_published"]) < 0.1
    for i in range(8):
        for j in range(8):
            p, o = chat["table"][8 * i + j]
            assert p in prompts[8 * i:8 * i + 8]
            assert o in outputs[8 * j:8 * j + 8]
    assert chat["blocks"] == [[8 * i + (i + b) % 8 for i in range(8)]
                              for b in range(8)]
    assert chat["table"][-1] == [224, 512]
    assert chat["clients"] == chat["max_slots"] == 16


def test_no_coincidence_of_phases_can_grow_the_cache_in_the_window(chat):
    """The longest request alone, and the longest request while a top
    chunk runs for another row, need the same bucket of the program's
    length ladder — and set-up's priming requests reach it."""
    from hetu_tpu.serving.executor import default_buckets
    ladder = default_buckets(chat["max_len"])
    longest = max(p + o for p, o in chat["table"])
    last_row = longest - 2                      # the last cache row written

    def bucket(need):
        return next(b for b in ladder if b > need)
    assert bucket(last_row) == bucket(last_row + chat["max_chunk"] - 1)
    prime = chat["prime"]
    assert prime["prompt"] + prime["output"] == longest
    assert bucket(prime["prompt"] + prime["output"] - 2) == bucket(last_row)


def test_mlm_batches_fixed_work_seeded_content():
    mix = {"batch": 4, "seq_len": 64, "mask_frac": 0.15, "pool": 3}
    a = traffic.mlm_batches(mix, 1000, 1)
    b = traffic.mlm_batches(mix, 1000, 2)
    assert len(a) == 3
    for x, y in zip(a, b):
        assert not np.array_equal(x["input_ids"], y["input_ids"])
        for batch in (x, y):
            assert (batch["attention_mask"] == 1).all()
            masked = batch["masked_lm_labels"] >= 0
            assert (masked.sum(1) == round(0.15 * 64)).all()
            assert np.array_equal(batch["masked_lm_labels"][masked],
                                  batch["input_ids"][masked])
            rows = {r.tobytes() for r in batch["input_ids"]}
            assert len(rows) == 4                      # rows all differ
            assert set(np.unique(batch["token_type_ids"])) == {0, 1}
    again = traffic.mlm_batches(mix, 1000, 1)
    assert all(np.array_equal(x[k], y[k]) for x, y in zip(a, again) for k in x)


def _req(t_submit, times, failed=False):
    r = cld._Request(0, 0, np.zeros(3, np.int32), 8)
    r.t_submit, r.times, r.failed = t_submit, list(times), failed
    return r


def test_window_counts_unfinished_streams_and_every_submitted_request():
    t0, t1 = 10.0, 20.0
    reqs = [
        _req(5.0, [8.0, 9.5, 10.5, 11.5]),       # began before, ends inside
        _req(12.0, [13.0, 14.0, 19.9, 20.5]),    # unfinished at t1
        _req(19.0, [22.0]),                      # first token owed after t1
        _req(15.0, [], failed=True),             # refused
        _req(21.0, [21.5]),                      # after the window
    ]
    red = cld.reduce_window(reqs, t0, t1)
    assert red["emitted"] == 2 + 3               # tokens inside, any stream
    assert red["attempted"] == 3 and red["failed"] == 1
    assert sorted(red["ttft"]) == [1.0, 3.0]     # every request submitted
    assert sorted(round(g, 6) for g in red["gaps"]) == [1.0, 1.0, 1.0, 5.9]
    assert cld.percentile(list(range(100)), 95) == 95


def _two_populations(slow_share, n=140_000):
    """Gaps of a chat window, in seconds: one-token steps about 5.24 ms,
    chunked steps about 10.5 ms (my chip runs, PR 35)."""
    rng = np.random.default_rng(35)
    slow = int(round(slow_share * n))
    return list(np.concatenate([rng.normal(5.24e-3, 0.15e-3, n - slow),
                                rng.normal(10.5e-3, 0.3e-3, slow)]))


def test_a_percentile_is_steady_only_inside_one_population_of_gaps():
    """With the slow share at 4.5 % on one seed and 6.3 % on the next
    (ISSUE 35's table) the 95th percentile jumps from one population to the
    other; the 90th lies in the fast one and the 99th in the slow one on
    both, so each moves by far less than any bound."""
    few = cld.gap_profile(_two_populations(0.045))
    many = cld.gap_profile(_two_populations(0.063))
    assert few["slow_pct"] == pytest.approx(4.5, abs=0.05)
    assert many["slow_pct"] == pytest.approx(6.3, abs=0.05)
    assert few["p95"] < 6.0 and many["p95"] > 10.0      # the edge
    assert many["p95"] - few["p95"] > 0.8 * (10.5 - 5.24)
    bound = _judged_itl()["bound"]
    for steady in ("p50", "p90", "p99"):
        assert abs(many[steady] / few[steady] - 1) < bound / 2, steady
    assert few["p90"] < 6.0 and few["p99"] > 10.0
    # the slowest twentieth's mean follows the share itself
    assert many["slowest5_mean"] / few["slowest5_mean"] - 1 > bound
    assert cld.percentile(_two_populations(0.045), 95) * 1e3 == few["p95"]


def _judged_itl():
    [m] = [m for m in harness.Files(ROOT).bench["end_to_end"]
           if m["name"].startswith("itl_")]
    return m


def test_window_names_the_judged_gap_statistic_and_keeps_the_others():
    reqs = [_req(0.5, [1.0 + 0.01 * i for i in range(400)])]
    red = cld.reduce_window(reqs, 1.0, 6.0)
    profile = cld.gap_profile(red["gaps"])
    assert set(profile) == {"p50", "p90", "p95", "p99", "slowest5_mean",
                            "slow_pct"}
    assert profile["p50"] == pytest.approx(10.0) and profile["slow_pct"] == 0
    judged = _judged_itl()
    assert (judged["unit"], judged["better"], judged["source"]) \
        == ("ms", "lower", "host_clock")
    assert judged["name"] == "itl_p90_ms" and "p90" in profile
