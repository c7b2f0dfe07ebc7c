"""The ``sessions`` generator: one set of lengths and gaps for every
seed, totals within the bounds the warm-up reads, each system prompt
recurring exactly in the shared mix, and no page shared in the
unshared one."""

import json
import pathlib

import numpy as np
import pytest

from bench import generator

ROOT = pathlib.Path(__file__).resolve().parents[2]
PAGE = 16
SEEDS = (2**33 + 31, 2**40 + 37, 5)


def mix(name):
    return json.loads((ROOT / "bench/traffic" / f"{name}.json").read_text())


def lengths_and_gaps(reqs):
    dues = [r.due_s for r in reqs]
    return (sorted(len(r.prompt) for r in reqs),
            sorted(r.max_new_tokens for r in reqs),
            sorted(np.round(np.diff(dues), 12)))


@pytest.mark.parametrize("name", ["sessions", "unshared"])
def test_every_seed_gets_the_same_lengths_and_gaps(name):
    t = mix(name)
    runs = [generator.schedule(t, s, 51, 50304) for s in SEEDS]
    assert all(lengths_and_gaps(r) == lengths_and_gaps(runs[0])
               for r in runs[1:])
    assert [len(r.prompt) for r in runs[0]] != \
        [len(r.prompt) for r in runs[1]]
    for reqs in runs:
        assert all(t["prompt"]["min"] <= len(r.prompt) <= t["prompt"]["max"]
                   for r in reqs)


def test_the_two_mixes_differ_only_in_the_system_tokens():
    a = generator.schedule(mix("sessions"), SEEDS[0], 51, 50304)
    b = generator.schedule(mix("unshared"), SEEDS[0], 51, 50304)
    assert [(r.due_s, len(r.prompt), r.max_new_tokens) for r in a] == \
        [(r.due_s, len(r.prompt), r.max_new_tokens) for r in b]


def common_prefix(prompts):
    n = min(len(p) for p in prompts)
    same = np.all([p[:n] == prompts[0][:n] for p in prompts], axis=0)
    return n if same.all() else int(np.argmin(same))


@pytest.mark.parametrize("seed", SEEDS)
def test_each_system_prompt_recurs_exactly(seed):
    """Requests fall into four groups by their first page; a group's
    members share exactly its system prompt, and each block of 8 holds
    each group twice."""
    t = mix("sessions")
    reqs = generator.schedule(t, seed, 51, 50304)
    groups = {}
    for k, r in enumerate(reqs):
        groups.setdefault(tuple(r.prompt[:PAGE]), []).append(k)
    assert len(groups) == t["system"]["count"]
    shared = sorted(common_prefix([reqs[k].prompt for k in ks])
                    for ks in groups.values())
    assert shared == [512, 683, 853, 1024]
    of = {k: g for g, ks in enumerate(groups.values()) for k in ks}
    block = t["block"]
    for start in range(0, len(reqs), block):
        counts = np.bincount([of[k] for k in range(start, start + block)],
                             minlength=4)
        assert list(counts) == [2, 2, 2, 2]


@pytest.mark.parametrize("seed", SEEDS)
def test_no_two_unshared_requests_share_a_page(seed):
    reqs = generator.schedule(mix("unshared"), seed, 51, 50304)
    pages = [tuple(r.prompt[i:i + PAGE])
             for r in reqs for i in range(0, len(r.prompt) - PAGE + 1, PAGE)]
    assert len(set(pages)) == len(pages)
