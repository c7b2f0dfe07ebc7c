"""Paged KV cache + prefix sharing: the layout-invariance contract.

The acceptance bar for ``EngineConfig.kv_layout="paged"`` (ISSUE 9): a
request's emitted tokens AND its compensated logit-norm telemetry are
bitwise identical (a) under the paged layout vs the dense oracle, (b)
whether its pages happen to be contiguous or scattered across the pool,
and (c) whether its prompt prefix was prefilled privately or admitted by
reference from the radix prefix cache — for every registered
compensation scheme. Around the contract: the allocator/lifecycle
guards (reserve-all admission, FIFO page-exhaustion stalls, fail-fast
impossible requests), hygiene (freed pages return pristine-zero; the
free list returns to its initial size under sustained mixed traffic),
the compile-count guard (page placement is a traced operand — programs
scale with the tail-bucket set, never with placement), and the
footprint claim the paper's data-traffic analysis motivates (live KV
bytes scale with live tokens, not with ``max_slots * max_len``).
"""

import jax
import numpy as np
import pytest

from repro.configs.base import ArchConfig, SSMConfig, XLSTMConfig
from repro.kernels.schemes import Policy
from repro.models import build_model
from repro.serve import (
    EngineConfig,
    InferenceEngine,
    PageAllocator,
    RadixPrefixTree,
    Request,
    SamplingParams,
)
from repro.serve.engine import prefill_program_bound
from repro.serve.paging import NULL_PAGE, pages_for


def _tiny_cfg(**kw):
    return ArchConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      param_dtype="float32", compute_dtype="float32",
                      loss_chunk=64, **kw)


@pytest.fixture(scope="module")
def tiny_model():
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _requests(cfg, spec, seed=0, temperature=0.5):
    """spec: [(prompt_len, max_new), ...] -> deterministic requests."""
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32),
                sampling=SamplingParams(temperature=temperature,
                                        max_new_tokens=n),
                request_id=i)
        for i, (p, n) in enumerate(spec)
    ]


def _run(cfg, ec, model, params, requests, arrivals=None):
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    out = eng.run(requests, arrivals)
    return {r: (tuple(h.tokens), tuple(h.telemetry))
            for r, h in out.items()}, eng


def _ec(**kw):
    kw.setdefault("max_slots", 2)
    kw.setdefault("max_len", 16)
    kw.setdefault("prefill_chunk", 4)
    kw.setdefault("track_stats", True)
    return EngineConfig(**kw)


def _paged(**kw):
    kw.setdefault("kv_layout", "paged")
    kw.setdefault("page_size", 4)
    return _ec(**kw)


def _pool_leaves(eng):
    for leaf, s in zip(jax.tree.leaves(eng.slots.cache),
                       jax.tree.leaves(eng.slots.page_axes)):
        if s >= 0:
            yield leaf


# ---------------------------------------------------------------------------
# The headline contract: paged vs the dense oracle, every scheme
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
def test_paged_vs_dense_bitwise(tiny_model, scheme):
    """Tokens AND telemetry bitwise-identical under either layout, over
    a staggered mixed trace — the dense ``SlotKVCache`` is the oracle."""
    cfg, model, params = tiny_model
    pol = Policy(scheme=scheme, unroll=2)
    reqs = _requests(cfg, [(5, 3), (9, 2), (3, 4)], seed=len(scheme))
    arr = [0, 1, 2]
    dense, _ = _run(cfg, _ec(policy=pol), model, params, reqs, arr)
    paged, eng = _run(cfg, _paged(policy=pol), model, params, reqs, arr)
    assert eng.kv_layout == "paged"
    assert dense == paged, f"{scheme}: paged trace diverges from dense"
    # pool hygiene rides along: the drained trace returned every page
    assert eng.pages.free_count == eng.num_pages


def test_scattered_vs_contiguous_bitwise(tiny_model):
    """Page placement cannot reach the numerics: a request whose pages
    come back scattered (after fragmenting frees) matches the same
    request served contiguously in a fresh pool — and the same compiled
    programs serve both (the table is a traced operand)."""
    cfg, model, params = tiny_model
    reqs = _requests(cfg, [(4, 2), (9, 3), (9, 3)], seed=3)
    ec = _paged()

    # fresh engine: request 2 alone gets the lowest (contiguous) pages
    solo, _ = _run(cfg, ec, model, params, [reqs[2]])

    # fragmenting trace: 0 and 1 start together, short 0 frees its low
    # pages first, and 2 arrives while 1 still pins the middle of the
    # pool — its reservation straddles the hole
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    scattered = False
    served = {}
    for _t, _events in eng.stream(reqs, [0, 0, 1], _sink=served):
        for lease in eng._leases.values():
            pages = list(lease.table[:lease.n_pages])
            if any(b - a != 1 for a, b in zip(pages, pages[1:])):
                scattered = True
    assert scattered, "trace never produced a scattered page table"
    assert (tuple(served[2].tokens), tuple(served[2].telemetry)) == solo[2]


def test_solo_vs_interleaved_bitwise_paged(tiny_model):
    """The serving contract's solo-replay half still holds under the
    paged layout (slot AND page placement both differ between runs)."""
    cfg, model, params = tiny_model
    reqs = _requests(cfg, [(5, 3), (8, 2), (3, 4)], seed=11)
    ec = _paged()
    served, _ = _run(cfg, ec, model, params, reqs, [0, 1, 1])
    for req in reqs:
        solo, _ = _run(cfg, ec, model, params, [req])
        assert solo[req.request_id] == served[req.request_id]


# ---------------------------------------------------------------------------
# Prefix cache: shared vs private, copy-on-write, accounting
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
def test_shared_vs_private_bitwise(tiny_model, scheme):
    """A request admitted by reference (prompt prefix resident in the
    radix tree) emits the same bits as a private prefill of the same
    request — for every scheme."""
    cfg, model, params = tiny_model
    pol = Policy(scheme=scheme, unroll=2)
    rng = np.random.default_rng(29)
    base = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)

    def mk(tail, rid):
        return Request(prompt=np.concatenate([base, tail]).astype(np.int32),
                       sampling=SamplingParams(temperature=0.5,
                                               max_new_tokens=3, seed=rid),
                       request_id=rid)

    donor = mk(rng.integers(0, cfg.vocab_size, (3,)).astype(np.int32), 0)
    benef = mk(rng.integers(0, cfg.vocab_size, (2,)).astype(np.int32), 1)

    priv, _ = _run(cfg, _paged(policy=pol), model, params, [benef])
    eng = InferenceEngine(cfg, _paged(policy=pol, prefix_cache=True),
                          model=model, params=params)
    eng.run([donor])
    assert eng.page_stats()["prefix_cached_pages"] > 0
    served = eng.run([benef])
    assert eng.prefix_hit_tokens > 0, "beneficiary never hit the prefix"
    assert (tuple(served[1].tokens), tuple(served[1].telemetry)) == priv[1]


def test_copy_on_write_partial_page(tiny_model):
    """Scan-body prefix sharing extends INTO the first divergent page:
    the donor page is duplicated (copy-on-write), the resume offset
    lands mid-page, and the donor's own bits survive untouched — a
    donor replay after the beneficiary still matches its first run."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(31)
    base = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)  # 1.5 pages

    def mk(tail, rid, seed):
        return Request(prompt=np.concatenate([base, tail]).astype(np.int32),
                       sampling=SamplingParams(temperature=0.5,
                                               max_new_tokens=3, seed=seed),
                       request_id=rid)

    donor = mk([3, 5, 9], 0, 0)     # diverges from benef inside page 1
    benef = mk([7, 2, 8], 1, 1)

    priv, _ = _run(cfg, _paged(), model, params, [benef])
    eng = InferenceEngine(cfg, _paged(prefix_cache=True),
                          model=model, params=params)
    first_donor = eng.run([donor])
    served = eng.run([benef])
    # 1 full shared page (4 tokens) + 2 copy-on-write overlap tokens
    assert eng.prefix_hit_tokens == 6
    assert (tuple(served[1].tokens), tuple(served[1].telemetry)) == priv[1]
    # the donor's pages were never written by the beneficiary
    donor_replay = eng.run([mk([3, 5, 9], 2, 0)])
    assert tuple(donor_replay[2].tokens) == tuple(first_donor[0].tokens)
    assert tuple(donor_replay[2].telemetry) == tuple(
        first_donor[0].telemetry)


def test_prompt_pages_shared_while_the_donor_decodes(tiny_model):
    """A prompt's full pages enter the prefix tree when its prefill
    completes: a request arriving while the donor still decodes admits
    by reference, emits its private bits, and the donor's pages stay
    resident (referenced) until both finish."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(67)
    base = rng.integers(0, cfg.vocab_size, (9,)).astype(np.int32)

    def mk(tail, rid, n):
        return Request(prompt=np.concatenate([base, tail]).astype(np.int32),
                       sampling=SamplingParams(temperature=0.5,
                                               max_new_tokens=n, seed=rid),
                       request_id=rid)

    donor = mk(rng.integers(0, cfg.vocab_size, (3,)), 0, 10)
    benef = mk(rng.integers(0, cfg.vocab_size, (2,)), 1, 3)
    priv, _ = _run(cfg, _paged(max_len=32), model, params, [donor, benef],
                   [0, 2])
    eng = InferenceEngine(cfg, _paged(max_len=32, prefix_cache=True),
                          model=model, params=params)
    served = {}
    for t, _events in eng.stream([donor, benef], [0, 2], _sink=served):
        if t == 2:
            # admitted at step 2: 2 shared pages, then 1 token copied on
            # write from the donor's third page
            assert not served[0].done and eng.prefix_hit_tokens == 9
            assert eng.prefix.referenced_pages == 3
    assert {r: (tuple(h.tokens), tuple(h.telemetry))
            for r, h in served.items()} == priv
    st = eng.page_stats()
    assert eng.prefix.referenced_pages == 0
    assert st["free_pages"] + st["prefix_pages"] == eng.num_pages


def test_prefix_hit_full_prompt_resumes_at_last_position(tiny_model):
    """A fully-resident prompt still re-prefills at least one position
    (the final chunk's logits emit token 0) — and emits the same bits
    as its private run."""
    cfg, model, params = tiny_model
    rng = np.random.default_rng(37)
    prompt = rng.integers(0, cfg.vocab_size, (8,)).astype(np.int32)

    def mk(rid):
        return Request(prompt=prompt, sampling=SamplingParams(
            temperature=0.5, max_new_tokens=3, seed=5), request_id=rid)

    priv, _ = _run(cfg, _paged(), model, params, [mk(0)])
    eng = InferenceEngine(cfg, _paged(prefix_cache=True),
                          model=model, params=params)
    eng.run([mk(0)])
    served = eng.run([mk(1)])
    assert (tuple(served[1].tokens), tuple(served[1].telemetry)) == \
        (priv[0][0], priv[0][1])
    # resume capped at prompt_len - 1: 7 of 8 positions by reference
    assert eng.prefix_hit_tokens == 7


def test_prefix_eviction_reclaims_cached_pages(tiny_model):
    """Pool pressure evicts refs-0 cached prefix pages (oldest first),
    zero-resets them, and the disjoint newcomer is served; the
    tree+free accounting stays exact throughout."""
    cfg, model, params = tiny_model
    ec = _paged(max_slots=1, num_pages=4, prefix_cache=True)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    reqs = _requests(cfg, [(7, 2), (13, 3)], seed=41)
    eng.run([reqs[0]])                       # leaves 1 cached page, 3 free
    assert eng.page_stats()["prefix_cached_pages"] == 1
    eng.run([reqs[1]])                       # needs all 4: must evict
    st = eng.page_stats()
    assert st["free_pages"] + st["prefix_pages"] == eng.num_pages
    assert st["prefix_pages"] == 3           # req 1's pages replaced req 0's


# ---------------------------------------------------------------------------
# Paged + flash chunk body + prefix cache against dense + flash
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_flash():
    """The tiny model with ``kahan_attention``: flash prefill chunks run
    the engine's chunk flash kernel, as the served olmo-1b does."""
    cfg = _tiny_cfg(kahan_attention=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _sessions(cfg, head_len, tails, seed):
    """Requests that open with one shared ``head_len``-token system
    prompt, each followed by its own part of ``tails[i]`` tokens."""
    rng = np.random.default_rng(seed)
    head = rng.integers(0, cfg.vocab_size, (head_len,))
    return [Request(prompt=np.concatenate([
        head, rng.integers(0, cfg.vocab_size, (n,))]).astype(np.int32),
        sampling=SamplingParams(temperature=0.5, max_new_tokens=3,
                                seed=i),
        request_id=i) for i, n in enumerate(tails)]


def _flash(**kw):
    return _ec(max_len=48, prefill_chunk=8, prefill_mode="flash", **kw)


# the first request finishes (and donates its prompt pages) before the
# rest arrive; the last two arrive together and share the tree's pages
ARRIVE = [0, 6, 9, 9]


@pytest.mark.parametrize("head_len, tails, resume", [
    (24, [5, 9, 3, 12], 24),      # 3 whole chunks of 8: resume at 24
    (21, [6, 11, 2, 14], 16),     # 5 pages of 4, 2 chunks of 8 plus 5
], ids=["aligned", "unaligned"])
@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
def test_paged_flash_prefix_vs_dense_flash_bitwise(tiny_flash, scheme,
                                                   head_len, tails, resume):
    """The served configuration's path: paged KV, the flash chunk body
    and the prefix cache give the tokens and telemetry of dense + flash
    bit for bit, for every scheme, while the later requests resume from
    the donor's pages at the offset both the page and the chunk grid
    hold (an unaligned system prompt's hit is floored to it)."""
    cfg, model, params = tiny_flash
    pol = Policy(scheme=scheme, unroll=2)
    reqs = _sessions(cfg, head_len, tails, seed=len(scheme) + head_len + 46)
    dense, _ = _run(cfg, _flash(policy=pol), model, params, reqs, ARRIVE)
    eng = InferenceEngine(cfg, _flash(policy=pol, kv_layout="paged",
                                      page_size=4, prefix_cache=True),
                          model=model, params=params)
    assert eng.kv_layout == "paged" and eng.prefill_body == "flash"
    resumes, served = {}, {}
    for _t, _e in eng.stream(reqs, ARRIVE, _sink=served):
        resumes.update({rid: lease.resume
                        for rid, lease in eng._leases.items()})
    assert resumes == {0: 0, 1: resume, 2: resume, 3: resume}
    assert eng.prefix_hit_tokens == 3 * resume
    paged = {r: (tuple(h.tokens), tuple(h.telemetry))
             for r, h in served.items()}
    assert dense == paged, f"{scheme}: paged+flash+prefix diverges"
    st = eng.page_stats()
    assert st["free_pages"] + st["prefix_pages"] == eng.num_pages


# ---------------------------------------------------------------------------
# Lifecycle: exhaustion stalls, fail-fast, leaks, hygiene
# ---------------------------------------------------------------------------

def test_page_exhaustion_stalls_fifo(tiny_model):
    """A pool that fits one request at a time serializes admission —
    strict FIFO (completion order == submission order), stalls counted,
    every request completes, and the free list drains back to full."""
    cfg, model, params = tiny_model
    ec = _paged(num_pages=4)                 # each request needs 4 pages
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    reqs = _requests(cfg, [(12, 3), (12, 3), (12, 3)], seed=43)
    finish_order = []
    served = {}
    for _t, events in eng.stream(reqs, _sink=served):
        finish_order += [e.request_id for e in events if e.done]
    assert finish_order == [0, 1, 2]
    assert eng.page_stalls > 0
    assert all(h.done for h in served.values())
    assert eng.pages.free_count == eng.num_pages


def test_impossible_request_fails_fast_at_submit(tiny_model):
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, _paged(num_pages=3), model=model,
                          params=params)
    with pytest.raises(ValueError, match="pages"):
        eng.submit(Request(prompt=list(range(12)),
                           sampling=SamplingParams(max_new_tokens=4)))


def test_sustained_traffic_leaks_no_pages(tiny_model):
    """The leak guard: waves of mixed traffic (staggered arrivals, slot
    churn, ``pop_finished`` draining) return the free list to its
    initial size — and with the prefix cache on, free + tree-owned
    always equals the pool."""
    cfg, model, params = tiny_model
    for prefix in (False, True):
        eng = InferenceEngine(cfg, _paged(prefix_cache=prefix),
                              model=model, params=params)
        for wave in range(3):
            reqs = _requests(cfg, [(5, 3), (9, 2), (3, 4), (6, 2)],
                             seed=wave)
            eng.run(reqs, [0, 0, 1, 2])
            eng.pop_finished()
            st = eng.page_stats()
            assert st["free_pages"] + st["prefix_pages"] == eng.num_pages
            assert not eng._leases
        if not prefix:
            assert eng.pages.free_count == eng.num_pages


def test_freed_pages_are_pristine(tiny_model):
    """Eviction hygiene, page-granular: after a drained no-prefix trace
    every pool leaf is all-zeros again — freed pages re-enter the free
    list with exactly the pristine bits the zero-fill gather promises."""
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, _paged(), model=model, params=params)
    eng.run(_requests(cfg, [(5, 3), (9, 2)], seed=47), [0, 1])
    assert eng.pages.free_count == eng.num_pages
    leaves = list(_pool_leaves(eng))
    assert leaves, "paged engine has no pool leaves"
    for leaf in leaves:
        assert not np.asarray(leaf).any(), "freed page carries stale bits"


def test_compile_count_guard_paged(tiny_model):
    """Page placement is a traced operand: a mixed-length paged trace
    compiles at most the tail-bucket program set (the same
    ``prefill_program_bound`` the dense engine honors), regardless of
    how many distinct placements/tables it served."""
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, _paged(), model=model, params=params)
    eng.run(_requests(cfg, [(3, 2), (5, 2), (7, 2), (9, 2), (11, 2)],
                      seed=53), [0, 0, 1, 2, 3])
    bound = prefill_program_bound(4, needs_begin=False)
    assert len(eng.prefill_programs) <= bound
    assert len(eng._fns._prefill) <= bound


# ---------------------------------------------------------------------------
# Config validation + layout resolution
# ---------------------------------------------------------------------------

def test_config_validation():
    with pytest.raises(ValueError, match="power of two"):
        EngineConfig(kv_layout="paged", page_size=6)
    with pytest.raises(ValueError, match="multiple"):
        EngineConfig(kv_layout="paged", page_size=32, max_len=48)
    with pytest.raises(ValueError, match="kv_layout"):
        EngineConfig(kv_layout="ragged")
    with pytest.raises(ValueError, match="prefix_cache"):
        EngineConfig(prefix_cache=True)
    with pytest.raises(ValueError, match="num_pages"):
        EngineConfig(kv_layout="paged", num_pages=0)


def test_recurrent_families_fall_back_dense():
    """Families with no position-addressed KV leaf (xLSTM recurrence;
    all-window hybrids, whose ring buffers carry the kv_ring
    pageable=False flag) resolve to the dense layout — reported, not
    erroring."""
    for name, kw in (
        ("xl", dict(xlstm=XLSTMConfig(slstm_every=2))),
        ("hyb", dict(sliding_window=8, global_attn_layers=(),
                     ssm=SSMConfig(d_state=4, d_conv=2))),
    ):
        cfg = ArchConfig(name=name, family="dense", n_layers=2, d_model=32,
                         n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                         param_dtype="float32", compute_dtype="float32",
                         loss_chunk=64, **kw)
        model = build_model(cfg)
        params, _ = model.init(jax.random.key(0))
        eng = InferenceEngine(cfg, _paged(), model=model, params=params)
        assert eng.kv_layout == "dense"
        with pytest.raises(RuntimeError, match="dense"):
            eng.page_stats()


def test_mixed_hybrid_pages_global_layers_only(tiny_model):
    """A hybrid with one global-attention layer pages THAT leaf and
    keeps ring/SSM leaves dense — and stays bitwise with its own dense
    oracle."""
    cfg = ArchConfig(name="hyb-mix", family="hybrid", n_layers=2,
                     d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                     vocab_size=128, sliding_window=8,
                     global_attn_layers=(0,),
                     ssm=SSMConfig(d_state=4, d_conv=2),
                     param_dtype="float32", compute_dtype="float32",
                     loss_chunk=64)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    reqs = _requests(cfg, [(9, 2), (4, 3)], seed=59)
    dense, _ = _run(cfg, _ec(), model, params, reqs, [0, 1])
    paged, eng = _run(cfg, _paged(), model, params, reqs, [0, 1])
    assert eng.kv_layout == "paged"
    assert dense == paged


# ---------------------------------------------------------------------------
# Footprint: live bytes scale with live tokens
# ---------------------------------------------------------------------------

def test_live_footprint_scales_with_live_tokens(tiny_model):
    """The paged layout's point: KV bytes in use track the live trace
    (reserved pages), not the dense ``max_slots * max_len`` envelope."""
    cfg, model, params = tiny_model
    ec = _paged(max_slots=4, max_len=16, num_pages=16)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    peak_small = 0
    for _t, _e in eng.stream(_requests(cfg, [(2, 3)], seed=61)):
        peak_small = max(peak_small, eng.page_stats()["pages_in_use"])
    eng.pop_finished()
    peak_big = 0
    for _t, _e in eng.stream(_requests(cfg, [(13, 3), (13, 3)], seed=62),
                             [0, 0]):
        peak_big = max(peak_big, eng.page_stats()["pages_in_use"])
    assert peak_small == pages_for(2 + 3 - 1, 4)
    assert peak_big == 2 * pages_for(13 + 3 - 1, 4)
    assert peak_small < peak_big <= eng.num_pages
    # bytes accounting is pages * per-page footprint
    assert eng.page_stats()["kv_bytes_in_use"] == (
        eng.page_stats()["pages_in_use"] * eng.slots.page_bytes)


# ---------------------------------------------------------------------------
# Unit coverage: allocator + radix tree (plain-Python determinism)
# ---------------------------------------------------------------------------

def test_page_allocator_deterministic_lowest_first():
    a = PageAllocator(6)
    assert a.alloc(3) == [1, 2, 3]
    assert a.alloc(2) == [4, 5]
    a.free([2, 4])
    assert a.alloc(2) == [2, 4]          # lowest-first, sorted re-entry
    with pytest.raises(RuntimeError, match="exhausted"):
        a.alloc(3)
    with pytest.raises(ValueError, match="double free"):
        a.free([6, 6])
    with pytest.raises(ValueError, match="cannot free"):
        a.free([NULL_PAGE])


def test_radix_tree_match_insert_evict():
    t = RadixPrefixTree(4)
    adopted, dups = t.insert(list(range(10)), 2, [5, 9])
    assert adopted == [5, 9] and dups == []
    # first insert wins; a duplicate page run is returned for freeing
    adopted2, dups2 = t.insert(list(range(10)), 2, [5, 7])
    assert adopted2 == [] and dups2 == [7]
    path = t.match(list(range(10)))
    assert [n.page for n in path] == [5, 9]
    assert t.match([9, 9, 9, 9]) == []
    # refs pin nodes against eviction, leaf-first oldest-first otherwise
    t.acquire(path)
    assert t.evict(2) == []
    t.release(path)
    assert t.evict(1) == [9]             # leaf before parent
    assert t.evict(2) == [5]
    assert t.total_pages == 0
    with pytest.raises(RuntimeError, match="underflow"):
        t.release(path)
