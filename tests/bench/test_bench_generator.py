"""The traffic generators: one seed, one schedule; lengths inside their
clips; every request fits the engine; seeds reorder the same work, and
each block of the schedule carries the same work."""

import json
import pathlib

import numpy as np
import pytest

from bench import generator

ROOT = pathlib.Path(__file__).resolve().parents[2]
OLMO = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())
MIXES = sorted(p.stem for p in (ROOT / "bench/traffic").glob("*.json")
               if "prompt" in json.loads(p.read_text()))


def mix(name):
    return json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("name", MIXES)
def test_same_seed_same_schedule(name):
    a = generator.schedule(mix(name), 2**33 + 7, 30, 50304)
    b = generator.schedule(mix(name), 2**33 + 7, 30, 50304)
    assert [(r.due_s, r.max_new_tokens) for r in a] == \
        [(r.due_s, r.max_new_tokens) for r in b]
    assert all(np.array_equal(x.prompt, y.prompt) for x, y in zip(a, b))
    c = generator.schedule(mix(name), 2**33 + 8, 30, 50304)
    assert [r.due_s for r in a] != [r.due_s for r in c]


@pytest.mark.parametrize("name", MIXES)
def test_lengths_inside_their_clips_and_fit_the_engine(name):
    t = mix(name)
    reqs = generator.schedule(t, 3, 30, 50304)
    max_len = OLMO["engine"]["max_len"]
    for r in reqs:
        assert t["prompt"]["min"] <= len(r.prompt) <= t["prompt"]["max"]
        assert t["output"]["min"] <= r.max_new_tokens <= t["output"]["max"]
        assert len(r.prompt) + r.max_new_tokens <= max_len
        assert r.prompt.dtype == np.int32
        assert 0 <= r.prompt.min() and r.prompt.max() < 50304
    dues = [r.due_s for r in reqs]
    assert dues == sorted(dues)
    assert sum(d == 0.0 for d in dues) == t.get("backlog", 0)


@pytest.mark.parametrize("name", MIXES)
def test_seeds_share_the_work(name):
    """Two seeds give the same multiset of lengths and of gaps."""
    t = mix(name)
    a = generator.schedule(t, 1, 30, 50304)
    b = generator.schedule(t, 2, 30, 50304)
    assert sorted(len(r.prompt) for r in a) == sorted(len(r.prompt) for r in b)
    assert sorted(r.max_new_tokens for r in a) == \
        sorted(r.max_new_tokens for r in b)
    assert a[-1].due_s == pytest.approx(b[-1].due_s)


def test_stratified_lengths_follow_the_distribution():
    x = generator.lognormal_quantiles(1001, 1020, 0.6, 1, 10**9)
    assert np.median(x) == 1020
    # the 84th percentile of a lognormal is median * e^sigma
    assert np.percentile(x, 84.13) == pytest.approx(1020 * np.exp(0.6),
                                                    rel=0.01)
    gaps = generator.exponential_quantiles(10000, 2.0)
    assert gaps.mean() == pytest.approx(0.5, rel=0.01)


def test_request_count_covers_the_window():
    t = mix("chat")
    reqs = generator.schedule(t, 4, 51, 50304)
    assert len(reqs) % t["block"] == 0
    assert reqs[-1].due_s >= t["ramp_s"] + 51


@pytest.mark.parametrize("name", MIXES)
def test_each_block_carries_the_same_lengths(name):
    """Any ``block`` consecutive requests hold one length from each
    stratum, whatever the seed."""
    t = mix(name)
    a = generator.schedule(t, 2**35 + 1, 51, 50304)
    b = generator.schedule(t, 2**35 + 2, 51, 50304)
    B = t["block"]
    for reqs in (a, b):
        outs = np.array([r.max_new_tokens for r in reqs])
        strata = np.sort(outs).reshape(B, -1)
        for blk in outs.reshape(-1, B):
            hits = [int(np.sum((s[0] <= blk) & (blk <= s[-1])))
                    for s in strata]
            assert min(hits) >= 1
    # the same blocks of work in another order
    assert [r.max_new_tokens for r in a] != [r.max_new_tokens for r in b]


def test_balanced_takes_one_value_per_stratum():
    rng = np.random.default_rng(0)
    out = generator.balanced(np.arange(24), 4, rng)
    assert sorted(out) == list(range(24))
    for blk in out.reshape(-1, 4):
        assert sorted(v // 6 for v in blk) == [0, 1, 2, 3]
    with pytest.raises(ValueError):
        generator.balanced(np.arange(10), 4, rng)


def test_generators_are_found_by_name():
    with pytest.raises(KeyError, match="open_loop"):
        generator.schedule({"generator": "no_such"}, 1, 1.0, 10)
