"""Continuous-batching engine: the bitwise serving contract.

The acceptance bar for request-level serving (the serving analogue of
the kernels' batched-vs-loop guarantee): a request's emitted tokens AND
its compensated logit-norm telemetry are bitwise identical (a) whether
it runs alone or interleaved with arbitrary other traffic under a
staggered-arrival trace, and (b) whether its prompt is prefilled
one-shot or in chunks of any width/budget — for every registered
compensation scheme, across slot reuse after (and during) eviction,
per-request sampling seeds, and heterogeneous ``max_new_tokens``. The
compile-count guard pins the other half of the chunked-prefill fix: the
compiled prefill program set scales with the tail-bucket set, not with
the number of distinct prompt lengths in the trace.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import (
    ArchConfig,
    EncoderConfig,
    SSMConfig,
    VisionStubConfig,
)
from repro.kernels.schemes import Policy
from repro.models import build_model
from repro.serve import (
    EngineConfig,
    InferenceEngine,
    Request,
    SamplingParams,
)


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


def _requests(cfg, spec, seed=0, temperature=0.0):
    """spec: [(prompt_len, max_new), ...] -> deterministic requests."""
    rng = np.random.default_rng(seed)
    return [
        Request(prompt=rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32),
                sampling=SamplingParams(temperature=temperature,
                                        max_new_tokens=n),
                request_id=i)
        for i, (p, n) in enumerate(spec)
    ]


def _solo_replay(cfg, ec, model, params, req):
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    return eng.run([req])[req.request_id]


def _assert_bitwise(cfg, ec, model, params, requests, arrivals):
    """Serve the trace interleaved, then replay each request alone in a
    fresh engine over the SAME weights; tokens and telemetry must match
    to the bit."""
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    served = eng.run(requests, arrivals)
    for req in requests:
        solo = _solo_replay(cfg, ec, model, params, req)
        rid = req.request_id
        assert solo.tokens == served[rid].tokens, (
            f"request {rid}: tokens diverge solo vs interleaved")
        # telemetry values are exact fp32 bits round-tripped via float()
        assert solo.telemetry == served[rid].telemetry, (
            f"request {rid}: telemetry diverges solo vs interleaved")
    return served


# ---------------------------------------------------------------------------
# The headline contract, swept over EVERY registered scheme
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
def test_solo_vs_interleaved_bitwise(tiny_model, scheme):
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=2, max_len=16, track_stats=True,
                      policy=Policy(scheme=scheme, unroll=2))
    served = _assert_bitwise(
        cfg, ec, model, params,
        _requests(cfg, [(5, 3), (8, 2), (3, 4)], seed=len(scheme)),
        arrivals=[0, 1, 2])
    for h in served.values():
        assert len(h.telemetry) == len(h.tokens)
        assert all(np.isfinite(v) and v > 0 for v in h.telemetry)


@pytest.mark.slow  # extra tick/admit compiles for the one-off scheme
def test_runtime_registered_scheme_serves_bitwise(tiny_model):
    """Any scheme in the registry rides the contract — including one
    registered after import (the registry's extension guarantee extends
    to the serving layer)."""
    from repro.kernels import schemes

    cfg, model, params = tiny_model
    toy = schemes.CompensationScheme(
        name="toy-serve",
        update=lambda s, c, x, step: (s + x, c),
        instruction_mix=schemes.InstructionMix(adds=1, muls=1),
        error_bound=lambda n, cond, eps=schemes.EPS32: n * eps * cond)
    schemes.register(toy)
    try:
        ec = EngineConfig(max_slots=2, max_len=16, track_stats=True,
                          policy=Policy(scheme="toy-serve", unroll=2))
        _assert_bitwise(cfg, ec, model, params,
                        _requests(cfg, [(4, 2), (6, 3)]), arrivals=[0, 1])
    finally:
        schemes.unregister("toy-serve")


# ---------------------------------------------------------------------------
# The batched tick: slot placement, and the per-slot scan oracle
# ---------------------------------------------------------------------------

def _serve_in_slot(cfg, ec, model, params, req, slot, full):
    """Serve ``req`` in ``slot`` of a fresh engine: fillers submitted
    first take the slots below it (lowest free slot first). ``full``
    keeps every slot decoding beside it; otherwise the fillers finish at
    their first token, inside the admitting step, and ``req`` decodes
    alone. Returns its handle."""
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    fill = req.sampling.max_new_tokens + 2 if full else 1
    rng = np.random.default_rng(100 + slot)
    fillers = [Request(prompt=rng.integers(0, cfg.vocab_size, (4 + i,))
                       .astype(np.int32),
                       sampling=SamplingParams(max_new_tokens=fill),
                       request_id=1000 + i)
               for i in range(ec.max_slots - 1)]
    for f in fillers[:slot]:
        eng.submit(f)
    h = eng.submit(req)
    for f in fillers[slot:] if full else ():
        eng.submit(f)
    occupancy = set()
    while not h.done:
        eng.step()
        if not h.done and h.slot is not None:
            occupancy.add(len(eng.scheduler.running))
    assert occupancy == {ec.max_slots if full else 1}, occupancy
    return h, eng


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_slot_placement_bitwise(tiny_model, layout):
    """One request served in every slot of a 4-slot engine, alone and
    beside three neighbours, under either KV layout: its tokens and
    telemetry are bitwise those of slot 0 alone. The batched tick gives
    every slot the same program; only the row index moves."""
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=4, max_len=32, track_stats=True,
                      prefill_chunk=4, kv_layout=layout, page_size=4,
                      policy=Policy(scheme="kahan", unroll=2))
    req = _requests(cfg, [(7, 6)], seed=11, temperature=0.7)[0]
    ref, eng = _serve_in_slot(cfg, ec, model, params, req, 0, full=False)
    assert eng.kv_layout == layout
    assert len(ref.tokens) == 6 and all(np.isfinite(ref.telemetry))
    for full in (False, True):
        for slot in range(ec.max_slots):
            h, _ = _serve_in_slot(cfg, ec, model, params, req, slot, full)
            where = f"slot {slot}, {'full' if full else 'alone'}"
            assert h.tokens == ref.tokens, f"{where}: tokens differ"
            assert h.telemetry == ref.telemetry, f"{where}: telemetry differs"


#: batched tick against the per-slot scan oracle, float32 tiny model: the
#: largest gap either way, relative to the oracle's largest magnitude
ORACLE_RTOL = 1e-5


def test_batched_tick_matches_scan_oracle(tiny_model):
    """The engine's tick against the per-slot scan tick it replaced, on
    the same mid-flight cache with two live slots of four: tokens exact,
    the logits, the compensated |logits|^2 and the written cache within
    ``ORACLE_RTOL``, and the dead slots' rows bitwise unchanged."""
    from scan_tick_oracle import scan_tick, tick_logits

    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=4, max_len=16, track_stats=True,
                      policy=Policy(scheme="kahan", unroll=2))
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    for req in _requests(cfg, [(5, 8), (9, 8)], seed=5, temperature=0.7):
        eng.submit(req)
    for _ in range(3):
        eng.step()
    running = eng.scheduler.running
    assert sorted(running) == [0, 1]
    b = ec.max_slots
    tokens, pos, seeds, eidx = (np.zeros((b,), np.int32) for _ in range(4))
    temps, live = np.zeros((b,), np.float32), np.zeros((b,), bool)
    for slot, h in running.items():
        tokens[slot], pos[slot], seeds[slot] = h.tokens[-1], h.pos, h.seed
        eidx[slot] = h.emitted
        temps[slot] = h.request.sampling.temperature
        live[slot] = True
    args = tuple(jnp.asarray(a) for a in (tokens, pos, seeds, eidx, temps,
                                          live))
    cache = jax.tree.map(jnp.copy, eng.slots.cache)
    want_cache, want_tok, want_norms, want_logits = scan_tick(eng)(
        params, cache, *args)
    got_logits = tick_logits(eng._fns.tick, params, cache, *args)
    got_cache, got_tok, got_norms = eng._fns.tick(
        params, jax.tree.map(jnp.copy, cache), *args)

    np.testing.assert_array_equal(np.asarray(got_tok)[live],
                                  np.asarray(want_tok)[live])

    def close(got, want):
        got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
        scale = max(float(np.max(np.abs(want))), 1e-30)
        assert float(np.max(np.abs(got - want))) <= ORACLE_RTOL * scale

    vocab = cfg.vocab_size
    close(np.asarray(got_logits)[live, :vocab],
          np.asarray(want_logits)[live, :vocab])
    close(np.asarray(got_norms)[live], np.asarray(want_norms)[live])
    jax.tree.map(close, got_cache, want_cache)
    dead = [s for s in range(b) if not live[s]]
    jax.tree.map(
        lambda g, o, a: np.testing.assert_array_equal(
            np.take(np.asarray(g), dead, axis=a),
            np.take(np.asarray(o), dead, axis=a)),
        got_cache, cache, eng.slots.batch_axes)


# ---------------------------------------------------------------------------
# Slot reuse after eviction
# ---------------------------------------------------------------------------

def test_slot_reuse_after_eviction(tiny_model):
    """More requests than slots: finished requests free their slot,
    queued requests are prefilled into the reused slot mid-flight, and
    every request still matches its solo replay bitwise."""
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=2, max_len=16, track_stats=True,
                      policy=Policy(scheme="kahan", unroll=2))
    reqs = _requests(cfg, [(5, 2), (7, 3), (4, 2), (6, 3), (3, 2)], seed=3)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    served = eng.run(reqs)                      # all arrive at step 0
    # with 5 requests and 2 slots, at least 3 admissions reused a slot
    assert all(h.done for h in served.values())
    assert eng.scheduler.occupancy == 0 and eng.scheduler.queued == 0
    for req in reqs:
        solo = _solo_replay(cfg, ec, model, params, req)
        assert solo.tokens == served[req.request_id].tokens
        assert solo.telemetry == served[req.request_id].telemetry


def test_occupancy_never_exceeds_slots_and_arrivals_respected(tiny_model):
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=2, max_len=16)
    reqs = _requests(cfg, [(4, 3), (4, 3), (4, 3), (4, 3)], seed=5)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    first_emit = {}
    for t, events in eng.stream(reqs, arrivals=[0, 0, 1, 3]):
        assert eng.scheduler.occupancy <= ec.max_slots
        for e in events:
            first_emit.setdefault(e.request_id, t)
    for rid, arrival in zip(range(4), [0, 0, 1, 3]):
        assert first_emit[rid] >= arrival


# ---------------------------------------------------------------------------
# Per-request sampling seeds
# ---------------------------------------------------------------------------

def test_per_request_seeds(tiny_model):
    """Same prompt, temperature > 0: distinct seeds give distinct
    streams, equal seeds give identical streams — and a sampled request
    is still bitwise-stable solo vs interleaved."""
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=3, max_len=16, track_stats=True,
                      policy=Policy(scheme="kahan", unroll=2))
    rng = np.random.default_rng(11)
    prompt = rng.integers(0, cfg.vocab_size, (6,)).astype(np.int32)
    mk = lambda rid, seed: Request(
        prompt=prompt, request_id=rid,
        sampling=SamplingParams(temperature=0.9, max_new_tokens=6,
                                seed=seed))
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    served = eng.run([mk(0, seed=7), mk(1, seed=8), mk(2, seed=7)])
    assert served[0].tokens == served[2].tokens      # same stream
    assert served[0].tokens != served[1].tokens      # different stream
    solo = _solo_replay(cfg, ec, model, params, mk(0, seed=7))
    assert solo.tokens == served[0].tokens
    assert solo.telemetry == served[0].telemetry


# ---------------------------------------------------------------------------
# max_new_tokens heterogeneity
# ---------------------------------------------------------------------------

def test_max_new_tokens_heterogeneity(tiny_model):
    """Requests with different output budgets finish at different steps;
    each emits exactly max_new_tokens (the first from prefill logits —
    a 1-token request never enters the decode batch)."""
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=4, max_len=16, track_stats=True,
                      policy=Policy(scheme="kahan", unroll=2))
    spec = [(4, 1), (4, 2), (4, 4), (4, 6)]
    reqs = _requests(cfg, spec, seed=9)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    served = eng.run(reqs)
    for (plen, n), req in zip(spec, reqs):
        h = served[req.request_id]
        assert len(h.tokens) == n and len(h.telemetry) == n
        solo = _solo_replay(cfg, ec, model, params, req)
        assert solo.tokens == h.tokens and solo.telemetry == h.telemetry
    # the 6-token request keeps decoding after everyone else finished:
    # emit 0 rides its admit step, emits 1..5 take five decode ticks
    assert eng.t == 5


# ---------------------------------------------------------------------------
# Chunked prefill: bitwise chunked-vs-one-shot + bounded program set
# ---------------------------------------------------------------------------

def _ec(scheme="kahan", **kw):
    return EngineConfig(max_slots=2, max_len=16, track_stats=True,
                        policy=Policy(scheme=scheme, unroll=2), **kw)


@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
def test_chunked_vs_oneshot_bitwise(tiny_model, scheme):
    """The chunked half of the serving contract, per scheme: the same
    staggered mixed-length trace served with one-shot admit, chunk-4
    prefill, and chunk-4 prefill under a 1-chunk-per-step budget yields
    bitwise-identical tokens AND telemetry per request (the chunk
    schedule is a pure function of the request's own prompt, so neither
    the chunk width nor the budget's step placement can touch a
    request's bits) — and the chunked engine still matches its solo
    replay."""
    cfg, model, params = tiny_model
    reqs = _requests(cfg, [(5, 3), (8, 2), (3, 4)], seed=len(scheme))
    arrivals = [0, 1, 2]

    def serve(**kw):
        eng = InferenceEngine(cfg, _ec(scheme, **kw), model=model,
                              params=params)
        return eng.run(reqs, arrivals), eng

    oneshot, eng_one = serve(prefill_chunk=None)
    for kw in ({"prefill_chunk": 4},
               {"prefill_chunk": 4, "prefill_budget": 1}):
        served, eng = serve(**kw)
        for req in reqs:
            rid = req.request_id
            assert served[rid].tokens == oneshot[rid].tokens, (
                f"request {rid}: tokens diverge chunked {kw} vs one-shot")
            assert served[rid].telemetry == oneshot[rid].telemetry, (
                f"request {rid}: telemetry diverges chunked {kw} vs "
                "one-shot")
    # chunked solo replay == chunked interleaved (slot-placement + budget
    # independence compose with the chunk schedule)
    ec4 = _ec(scheme, prefill_chunk=4)
    served4, _ = serve(prefill_chunk=4)
    for req in reqs:
        solo = _solo_replay(cfg, ec4, model, params, req)
        assert solo.tokens == served4[req.request_id].tokens
        assert solo.telemetry == served4[req.request_id].telemetry
    # one-shot compiled one program per distinct prompt length; chunked
    # drew every width from the bucket set
    assert {w for w, _ in eng_one.prefill_programs} == {5, 8, 3}
    assert {w for w, _ in eng.prefill_programs} <= {1, 2, 4}


def test_eviction_resets_slot_to_pristine_row(tiny_model):
    """Eviction hygiene behind the chunked contract: after a request
    finishes, its freed slot row reads back bitwise equal to the model's
    pristine init row — which is what lets the next admission's first
    chunk start from the in-slot row directly."""
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=16,
                                            prefill_chunk=4),
                          model=model, params=params)
    eng.run(_requests(cfg, [(6, 2)], seed=41))
    pristine, _ = model.init_cache(1, 16)
    got = eng.slots.read(0)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(pristine)):
        assert bool(jax.numpy.array_equal(a, b))


def test_chunked_slot_reuse_mid_prefill(tiny_model):
    """A request prefilled in chunks into a slot another request just
    vacated — while a third keeps decoding in the neighbouring slot —
    still matches its solo replay bitwise (slot reset + the tick's
    running-rows-only update keep mid-prefill rows pristine)."""
    cfg, model, params = tiny_model
    ec = _ec(prefill_chunk=2, prefill_budget=1)
    reqs = _requests(cfg, [(3, 2), (6, 6), (7, 3), (5, 2)], seed=13)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    served = eng.run(reqs)                      # 4 requests, 2 slots
    assert all(h.done for h in served.values())
    for req in reqs:
        solo = _solo_replay(cfg, ec, model, params, req)
        assert solo.tokens == served[req.request_id].tokens
        assert solo.telemetry == served[req.request_id].telemetry


def test_prefill_program_set_bounded(tiny_model):
    """THE compile-count regression guard: a trace with many distinct
    prompt lengths needs O(#buckets) prefill programs when chunked —
    and one per distinct length under one-shot admit (the recompile
    pathology the chunking fixes)."""
    cfg, model, params = tiny_model
    lengths = [3, 5, 6, 7, 9, 11, 13]
    spec = [(p, 1) for p in lengths]

    eng = InferenceEngine(cfg, _ec(prefill_chunk=4), model=model,
                          params=params)
    eng.run(_requests(cfg, spec, seed=17))
    widths = {w for w, _ in eng.prefill_programs}
    assert widths <= {1, 2, 4}, (
        f"chunk-4 prefill must draw every program width from the bucket "
        f"set {{1, 2, 4}}, got {sorted(widths)}")
    assert len(eng.prefill_programs) <= 3

    one = InferenceEngine(cfg, _ec(prefill_chunk=None), model=model,
                          params=params)
    one.run(_requests(cfg, spec, seed=17))
    assert {w for w, _ in one.prefill_programs} == set(lengths), (
        "one-shot admit compiles one prefill program per distinct "
        "prompt length — the pathology the guard documents")


def test_prefill_budget_bounds_head_of_line(tiny_model):
    """The head-of-line fix: with a 1-chunk budget, a long prompt
    prefills across steps while the already-running request keeps
    emitting a token EVERY step; one-shot admit lands the long prompt's
    whole prefill in its arrival step. Both engines emit identical
    tokens (the budget only moves work across steps)."""
    cfg, model, params = tiny_model
    short = Request(prompt=np.arange(2, dtype=np.int32) + 1, request_id=0,
                    sampling=SamplingParams(max_new_tokens=10))
    long = Request(
        prompt=(np.arange(9, dtype=np.int32) % cfg.vocab_size) + 3,
        request_id=1, sampling=SamplingParams(max_new_tokens=2))

    def drive(ec):
        eng = InferenceEngine(cfg, ec, model=model, params=params)
        per_step = {}
        for t, events in eng.stream([short, long], arrivals=[0, 1]):
            per_step[t] = [e.request_id for e in events]
        return per_step, eng

    budgeted, eng_b = drive(_ec(prefill_chunk=2, prefill_budget=1))
    # long prompt = chunks (2,2,2,2,1) at steps 1..5 -> first token at 5
    first_long = min(t for t, rids in budgeted.items() if 1 in rids)
    assert first_long == 5
    # the short request never starves during the long prefill
    for t in range(1, first_long + 1):
        assert budgeted[t].count(0) == 1, (
            f"step {t}: running request stalled behind the long prefill")

    oneshot, eng_o = drive(_ec(prefill_chunk=None))
    assert min(t for t, rids in oneshot.items() if 1 in rids) == 1
    assert eng_b.handles[0].tokens == eng_o.handles[0].tokens
    assert eng_b.handles[1].tokens == eng_o.handles[1].tokens


# ---------------------------------------------------------------------------
# Parallel (flash) prefill: the multi-token chunk body
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tiny_flash():
    """Flash-capable twin of ``tiny_model``: ``kahan_attention=True``
    routes the parallel chunk body through the engine's chunk flash
    kernel (the scan body and decode are untouched — they stay the
    oracle)."""
    cfg = _tiny_cfg(kahan_attention=True)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(4))
    return cfg, model, params


# Pinned tolerance for scan-vs-flash chunk bodies: the two bodies
# compute the same function but reassociate differently (per-position
# scan accumulates one KV row at a time; the fused chunk body folds
# block_k-wide online-softmax partials), so agreement is allclose, not
# bitwise. 1e-5 on an fp32 tiny config leaves ~two decades of headroom
# over the observed ~1e-7 drift.
_SCAN_VS_FLASH_TOL = dict(rtol=1e-5, atol=1e-5)


def _drive_chunks(model, params, prompt, max_len, body, chunks, extras=None):
    """Replay the engine's chunk schedule against one model body.

    ``chunks``: [(width, nvalid), ...] — width is the padded (bucketed)
    program width, nvalid the real token count, exactly what the
    scheduler hands the chunk program."""
    fn = (model.prefill_chunk if body == "scan"
          else model.prefill_chunk_parallel)
    cache, _ = model.init_cache(1, max_len)
    if extras and hasattr(model, "prefill_begin"):
        cache = model.prefill_begin(
            params, {"tokens": jnp.zeros((1, 1), jnp.int32), **extras},
            cache)
    logits, off = None, 0
    for width, nvalid in chunks:
        padded = np.zeros((width,), np.int32)
        padded[:nvalid] = prompt[off:off + nvalid]
        batch = {"tokens": jnp.asarray(padded[None]), **(extras or {})}
        logits, cache = fn(params, batch, cache, jnp.int32(off),
                           jnp.int32(nvalid))
        off += nvalid
    return logits, cache


@pytest.mark.parametrize("scheme", ["naive", "kahan", "pairwise", "dot2"])
@pytest.mark.parametrize("chunks", [
    [(4, 4), (4, 4)],          # full-width chunks only
    [(4, 4), (4, 3)],          # power-of-two-bucketed tail (pad row live)
], ids=["full", "tail"])
def test_parallel_chunk_body_matches_scan_body(tiny_flash, scheme, chunks):
    """THE promoted scan-vs-flash gate, per registered scheme and for
    both full-chunk and bucketed-tail widths: the parallel chunk body
    (one fused forward per chunk, flash kernel at a traced offset) must
    compute the same function as the per-position scan oracle — logits
    and every cache row within tolerance, and cache rows past
    offset+nvalid BITWISE pristine (bucket padding must never write)."""
    from repro.kernels import use_policy

    cfg, model, params = tiny_flash
    plen = sum(nv for _, nv in chunks)
    rng = np.random.default_rng(plen + len(scheme))
    prompt = rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32)
    with use_policy(Policy(scheme=scheme, unroll=2)):
        ref_logits, ref_cache = _drive_chunks(model, params, prompt, 16,
                                              "scan", chunks)
        par_logits, par_cache = _drive_chunks(model, params, prompt, 16,
                                              "flash", chunks)
    np.testing.assert_allclose(np.asarray(par_logits),
                               np.asarray(ref_logits),
                               **_SCAN_VS_FLASH_TOL)
    pristine, _ = model.init_cache(1, 16)
    for got, want, init in zip(jax.tree.leaves(par_cache),
                               jax.tree.leaves(ref_cache),
                               jax.tree.leaves(pristine)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   **_SCAN_VS_FLASH_TOL)
        if got.ndim == 5:                      # [L, B, max_len, KV, dh]
            assert np.array_equal(np.asarray(got[:, :, plen:]),
                                  np.asarray(init[:, :, plen:])), (
                f"{scheme}: bucket-pad rows past offset+nvalid were "
                "written — the exact gather+select cache write regressed")


@pytest.mark.parametrize("scheme", [
    "kahan",
    pytest.param("naive", marks=pytest.mark.slow),
    pytest.param("pairwise", marks=pytest.mark.slow),
    pytest.param("dot2", marks=pytest.mark.slow),
])
def test_flash_prefill_solo_vs_interleaved_bitwise(tiny_flash, scheme):
    """Flash mode carries the headline serving contract UNCHANGED: a
    request's tokens and telemetry are bitwise identical solo vs
    interleaved (the chunk schedule, offsets and program widths are a
    pure function of the request's own prompt, and the fused body is
    deterministic per program)."""
    cfg, model, params = tiny_flash
    ec = _ec(scheme, prefill_chunk=4, prefill_mode="flash")
    _assert_bitwise(cfg, ec, model, params,
                    _requests(cfg, [(5, 3), (8, 2), (3, 4)],
                              seed=len(scheme)),
                    arrivals=[0, 1, 2])


@pytest.mark.parametrize("scheme", [
    "kahan",
    pytest.param("naive", marks=pytest.mark.slow),
    pytest.param("pairwise", marks=pytest.mark.slow),
    pytest.param("dot2", marks=pytest.mark.slow),
])
def test_flash_vs_scan_mode_tokens_exact_telemetry_close(tiny_flash, scheme):
    """Chunked-vs-one-shot across BODIES: flash-mode serving emits
    exactly the scan-mode tokens; telemetry agrees to the pinned
    tolerance (NOT bitwise — the fused chunk body reassociates the
    softmax folds, see _SCAN_VS_FLASH_TOL). The program set stays drawn
    from the bucket family and the engine reports the resolved body."""
    cfg, model, params = tiny_flash
    reqs = _requests(cfg, [(5, 3), (8, 2), (3, 4)], seed=len(scheme))
    arrivals = [0, 1, 2]

    def serve(**kw):
        eng = InferenceEngine(cfg, _ec(scheme, prefill_chunk=4, **kw),
                              model=model, params=params)
        return eng.run(reqs, arrivals), eng

    scan_served, eng_scan = serve()
    flash_served, eng_flash = serve(prefill_mode="flash")
    assert eng_scan.prefill_body == "scan"
    assert eng_flash.prefill_body == "flash"
    assert {w for w, _ in eng_flash.prefill_programs} <= {1, 2, 4}
    for req in reqs:
        rid = req.request_id
        assert flash_served[rid].tokens == scan_served[rid].tokens, (
            f"request {rid}: tokens diverge flash vs scan body")
        np.testing.assert_allclose(flash_served[rid].telemetry,
                                   scan_served[rid].telemetry,
                                   **_SCAN_VS_FLASH_TOL)


@pytest.mark.slow  # widest-chunk flash bitwise sweep (8-wide fused programs)
def test_flash_prefill_widest_chunk_bitwise(tiny_flash):
    """The widest chunk the tiny cache admits (8): solo-vs-interleaved
    stays bitwise and the 8-token prompt runs as ONE fused program."""
    cfg, model, params = tiny_flash
    ec = _ec("kahan", prefill_chunk=8, prefill_mode="flash")
    _assert_bitwise(cfg, ec, model, params,
                    _requests(cfg, [(5, 3), (8, 2), (3, 4)], seed=8),
                    arrivals=[0, 1, 2])


def test_parallel_chunk_body_vlm_and_encdec_match_scan():
    """Family coverage for the parallel body: the VLM vision splice at
    traced chunk positions and the encdec decoder (self-attention
    through the chunk flash kernel, cross-attention over the
    ``prefill_begin``-cached memory) both match their scan oracles."""
    rng = np.random.default_rng(53)
    prompt = rng.integers(0, 128, (7,)).astype(np.int32)
    chunks = [(4, 4), (4, 3)]

    vcfg = ArchConfig(name="tiny-vlm-flash", family="vlm", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=128, vision=VisionStubConfig(n_patches=4),
                      kahan_attention=True, param_dtype="float32",
                      compute_dtype="float32", loss_chunk=64)
    vmodel = build_model(vcfg)
    vparams, _ = vmodel.init(jax.random.key(6))
    vex = {"vision_embeds": jnp.asarray(rng.standard_normal((1, 4, 32)),
                                        jnp.float32)}
    vref, _ = _drive_chunks(vmodel, vparams, prompt, 16, "scan", chunks,
                            extras=vex)
    vpar, _ = _drive_chunks(vmodel, vparams, prompt, 16, "flash", chunks,
                            extras=vex)
    np.testing.assert_allclose(np.asarray(vpar), np.asarray(vref),
                               **_SCAN_VS_FLASH_TOL)

    ecfg = ArchConfig(name="tiny-encdec-flash", family="encdec", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab_size=128,
                      encoder=EncoderConfig(n_layers=1, n_frames=6),
                      kahan_attention=True, param_dtype="float32",
                      compute_dtype="float32", loss_chunk=64)
    emodel = build_model(ecfg)
    eparams, _ = emodel.init(jax.random.key(7))
    eex = {"frames": jnp.asarray(rng.standard_normal((1, 6, 32)),
                                 jnp.float32)}
    eref, _ = _drive_chunks(emodel, eparams, prompt, 16, "scan", chunks,
                            extras=eex)
    epar, _ = _drive_chunks(emodel, eparams, prompt, 16, "flash", chunks,
                            extras=eex)
    np.testing.assert_allclose(np.asarray(epar), np.asarray(eref),
                               **_SCAN_VS_FLASH_TOL)


def test_flash_mode_falls_back_per_position_when_unsupported(tiny_model):
    """Configs the parallel body cannot serve (here: a sliding-window
    ring cache, whose wrap-around write has no chunk-at-offset form)
    resolve to the scan body under ``prefill_mode="flash"`` — same
    programs, same bits, and ``engine.prefill_body`` says so."""
    cfg = _tiny_cfg(sliding_window=8)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(5))
    reqs = _requests(cfg, [(5, 2), (9, 2)], seed=47)  # 9 wraps the ring

    def serve(mode):
        eng = InferenceEngine(cfg, _ec(prefill_chunk=4, prefill_mode=mode),
                              model=model, params=params)
        return eng.run(reqs), eng

    scan_served, _ = serve("scan")
    flash_served, eng = serve("flash")
    assert eng.prefill_body == "scan"
    for req in reqs:
        rid = req.request_id
        assert flash_served[rid].tokens == scan_served[rid].tokens
        assert flash_served[rid].telemetry == scan_served[rid].telemetry


def test_chunk_scan_prefill_matches_parallel_prefill(tiny_model):
    """Semantic guard against the chunk body and the one-shot path being
    identically wrong: the shared per-position prefill body must compute
    the same function as the families' PARALLEL ``model.prefill`` (up to
    reassociation), including the VLM vision splice at traced positions.
    """
    cfg, model, params = tiny_model
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab_size, (7,)).astype(np.int32)
    batch = {"tokens": jnp.asarray(prompt[None])}
    cache, _ = model.init_cache(1, 16)
    ref_logits, _ = model.prefill(params, batch, cache)
    cache2, _ = model.init_cache(1, 16)
    logits, _ = model.prefill_chunk(params, batch, cache2,
                                    jnp.int32(0), jnp.int32(7))
    np.testing.assert_allclose(np.asarray(logits), np.asarray(ref_logits),
                               rtol=1e-4, atol=1e-4)

    vcfg = ArchConfig(name="tiny-vlm", family="vlm", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                      vocab_size=128, vision=VisionStubConfig(n_patches=4),
                      param_dtype="float32", compute_dtype="float32",
                      loss_chunk=64)
    vmodel = build_model(vcfg)
    vparams, _ = vmodel.init(jax.random.key(1))
    vbatch = {"tokens": jnp.asarray(prompt[None]),
              "vision_embeds": jnp.asarray(rng.standard_normal(
                  (1, 4, 32)), jnp.float32)}
    vc, _ = vmodel.init_cache(1, 16)
    vref, _ = vmodel.prefill(vparams, vbatch, vc)
    vc2, _ = vmodel.init_cache(1, 16)
    vlog, _ = vmodel.prefill_chunk(vparams, vbatch, vc2,
                                   jnp.int32(0), jnp.int32(7))
    np.testing.assert_allclose(np.asarray(vlog), np.asarray(vref),
                               rtol=1e-4, atol=1e-4)

    # encdec: prefill and the chunked path share ONE prefill_begin
    # (encode + cross-K/V fill); the last-position logits must agree
    ecfg = ArchConfig(name="tiny-encdec", family="encdec", n_layers=2,
                      d_model=32, n_heads=4, n_kv_heads=4, d_ff=64,
                      vocab_size=128, encoder=EncoderConfig(n_layers=1,
                                                            n_frames=6),
                      param_dtype="float32", compute_dtype="float32",
                      loss_chunk=64)
    emodel = build_model(ecfg)
    eparams, _ = emodel.init(jax.random.key(2))
    ebatch = {"tokens": jnp.asarray(prompt[None]),
              "frames": jnp.asarray(rng.standard_normal((1, 6, 32)),
                                    jnp.float32)}
    ec1, _ = emodel.init_cache(1, 16)
    eref, _ = emodel.prefill(eparams, ebatch, ec1)
    ec2, _ = emodel.init_cache(1, 16)
    ec2 = emodel.prefill_begin(eparams, ebatch, ec2)
    elog, _ = emodel.prefill_chunk(eparams, ebatch, ec2,
                                   jnp.int32(0), jnp.int32(7))
    np.testing.assert_allclose(np.asarray(elog), np.asarray(eref),
                               rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Finished-handle hygiene (the sustained-traffic memory leak)
# ---------------------------------------------------------------------------

def test_finished_handle_eviction_and_run_returns_driven(tiny_model):
    """``max_finished`` bounds the retained FINISHED handles;
    ``run`` still returns every handle of the trace IT drove (captured
    at submission, surviving eviction); an evicted request_id may be
    resubmitted."""
    cfg, model, params = tiny_model
    ec = EngineConfig(max_slots=2, max_len=16, max_finished=1)
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    reqs = _requests(cfg, [(4, 2), (5, 2), (3, 2)], seed=29)
    served = eng.run(reqs)
    assert sorted(served) == [0, 1, 2]
    assert all(h.done and len(h.tokens) == 2 for h in served.values())
    assert len(eng.handles) == 1                 # bounded retention
    drained = eng.pop_finished()
    assert len(drained) == 1 and not eng.handles
    # an evicted id is free for reuse — the engine no longer leaks ids
    again = eng.run([reqs[0]])
    assert again[0].done and len(again[0].tokens) == 2


def test_pop_finished_drains_default_retention(tiny_model):
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, EngineConfig(max_slots=2, max_len=16),
                          model=model, params=params)
    eng.run(_requests(cfg, [(4, 1), (5, 2)], seed=31))
    assert sorted(eng.pop_finished()) == [0, 1]
    assert eng.handles == {} and eng.pop_finished() == {}


# ---------------------------------------------------------------------------
# Hybrid family: ring-buffer KV + recurrent SSM state in the slot cache
# ---------------------------------------------------------------------------

@pytest.mark.slow  # full hybrid compile: ring KV + SSM + global attn
def test_hybrid_ring_and_ssm_state_bitwise():
    """The slot cache carries ring-buffer KV and SSM recurrent state;
    the pinned batched decode tick keeps the contract for them: a
    request's bits do not depend on its slot or its neighbours (an
    unpinned vmap tick once drifted by an ulp here, the measured hybrid
    failure mode). Chunked prefill rides the same contract: the 9-token
    prompt wraps the window-8 ring buffer mid-chunk and must still match
    one-shot admit bitwise."""
    cfg = ArchConfig(name="tiny-hybrid", family="hybrid", n_layers=2,
                     d_model=32, n_heads=4, n_kv_heads=2, d_ff=64,
                     vocab_size=128, sliding_window=8,
                     global_attn_layers=(0,),
                     ssm=SSMConfig(d_state=4, d_conv=2),
                     param_dtype="float32", compute_dtype="float32",
                     loss_chunk=64)
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    ec = EngineConfig(max_slots=2, max_len=16, track_stats=True,
                      policy=Policy(scheme="kahan", unroll=2),
                      prefill_chunk=None)
    reqs = _requests(cfg, [(4, 3), (9, 2), (3, 3)], seed=2)
    served = _assert_bitwise(cfg, ec, model, params, reqs,
                             arrivals=[0, 1, 2])
    chunked = InferenceEngine(
        cfg, EngineConfig(max_slots=2, max_len=16, track_stats=True,
                          policy=Policy(scheme="kahan", unroll=2),
                          prefill_chunk=4, prefill_budget=1),
        model=model, params=params).run(reqs, arrivals=[0, 1, 2])
    for req in reqs:
        rid = req.request_id
        assert chunked[rid].tokens == served[rid].tokens
        assert chunked[rid].telemetry == served[rid].telemetry


# ---------------------------------------------------------------------------
# API boundary validation
# ---------------------------------------------------------------------------

def test_submit_validation(tiny_model):
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, EngineConfig(max_slots=1, max_len=8),
                          model=model, params=params)
    with pytest.raises(ValueError, match="max_len"):
        eng.submit(Request(prompt=np.arange(8, dtype=np.int32),
                           sampling=SamplingParams(max_new_tokens=4)))
    # an empty or mis-shaped prompt fails HERE, not as an opaque shape
    # error deep inside the prefill trace
    with pytest.raises(ValueError, match="non-empty"):
        eng.submit(Request(prompt=np.zeros((0,), np.int32)))
    with pytest.raises(ValueError, match="1-D"):
        eng.submit(Request(prompt=np.zeros((2, 3), np.int32)))
    eng.submit(Request(prompt=np.arange(4, dtype=np.int32), request_id=7,
                       sampling=SamplingParams(max_new_tokens=2)))
    with pytest.raises(ValueError, match="already submitted"):
        eng.submit(Request(prompt=np.arange(4, dtype=np.int32),
                           request_id=7,
                           sampling=SamplingParams(max_new_tokens=2)))
    with pytest.raises(ValueError, match="max_slots"):
        InferenceEngine(cfg, EngineConfig(max_slots=0), model=model,
                        params=params)


def test_engine_config_validation():
    """The serving knobs validate in ``__post_init__`` — bad values fail
    at construction, not mid-trace."""
    with pytest.raises(ValueError, match="max_len"):
        EngineConfig(max_len=0)
    with pytest.raises(ValueError, match="prefill_chunk"):
        EngineConfig(prefill_chunk=0)
    with pytest.raises(ValueError, match="prefill_budget"):
        EngineConfig(prefill_budget=0)
    with pytest.raises(ValueError, match="max_finished"):
        EngineConfig(max_finished=-1)
    with pytest.raises(ValueError, match="prefill_mode"):
        EngineConfig(prefill_mode="bogus")
    # the None sentinels (and both chunk bodies) stay legal
    EngineConfig(prefill_chunk=None, prefill_budget=None, max_finished=None)
    EngineConfig(max_finished=0)
    EngineConfig(prefill_mode="flash")


def test_release_invariant_is_a_real_exception(tiny_model):
    """The slot-ownership invariant survives ``python -O``: releasing a
    handle that does not own its slot raises, it does not assert."""
    cfg, model, params = tiny_model
    eng = InferenceEngine(cfg, EngineConfig(max_slots=1, max_len=16),
                          model=model, params=params)
    served = eng.run(_requests(cfg, [(4, 1)], seed=37))
    with pytest.raises(RuntimeError, match="does not own slot"):
        eng.scheduler.release(served[0])        # already released


def test_parse_trace_validation():
    """The trace parser enforces the API-boundary contract for every
    cell field (the holes used to surface as jit shape errors)."""
    from repro.launch.serve import parse_trace

    assert parse_trace("0:4:2,1:3:1:0.5", 0.25) == [
        (0, 4, 2, 0.25), (1, 3, 1, 0.5)]
    with pytest.raises(ValueError, match="arrival"):
        parse_trace("-1:4:2", 0.0)
    with pytest.raises(ValueError, match="prompt_len"):
        parse_trace("0:0:2", 0.0)
    with pytest.raises(ValueError, match="new_tokens"):
        parse_trace("0:4:0", 0.0)
    with pytest.raises(ValueError, match="temperature"):
        parse_trace("0:4:2:-0.5", 0.0)
    with pytest.raises(ValueError, match="want arrival"):
        parse_trace("0:4", 0.0)


def test_launcher_build_serving_honours_flags_and_cfg_override():
    """``build_serving`` is the one engine construction the launcher and
    ``chip_smoke.py`` share: flags reach the EngineConfig, the trace
    fits max_len, the weights come from ``--seed``, and a passed config
    (here ``kahan_attention=True``) replaces the ``--arch`` one."""
    from repro.configs import get_smoke
    from repro.launch import serve as launch

    argv = ["--arch", "olmo-1b", "--smoke", "--trace", "0:5:3,2:9:2",
            "--max-slots", "2", "--stats", "--prefill-chunk", "4",
            "--prefill-mode", "flash", "--seed", "3"]
    sv = launch.build_serving(launch.build_parser().parse_args(argv))
    ec = sv.engine.ec
    assert (ec.max_slots, ec.max_len, ec.track_stats, ec.prefill_chunk) == (
        2, 11, True, 4)
    assert sv.arrivals == [0, 2] and [len(r.prompt) for r in sv.requests] \
        == [5, 9]
    assert not sv.cfg.kahan_attention
    cfg = get_smoke("olmo-1b").replace(kahan_attention=True)
    again = launch.build_serving(launch.build_parser().parse_args(argv),
                                 cfg=cfg)
    assert again.cfg is cfg and again.engine.prefill_body == "flash"
    for x, y in zip(jax.tree.leaves(sv.engine.params),
                    jax.tree.leaves(again.engine.params)):
        assert np.array_equal(np.asarray(x), np.asarray(y))
    with pytest.raises(ValueError, match="--prefill-mode"):
        launch.build_serving(launch.build_parser().parse_args(
            argv[:-4] + ["--prefill-mode", "fused"]))


def test_compile_cache_helper_prefers_env_dir(monkeypatch):
    """The entry points' cache helper: ``JAX_COMPILATION_CACHE_DIR``
    wins and nothing else is set; otherwise a fixed ``.jax_cache/`` at
    the checkout root. ``jax.config.update`` is stubbed, so the cache
    stays off in the test process."""
    from repro.launch import compile_cache

    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda *a: calls.append(a))
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert calls == []
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    path = compile_cache.enable_compile_cache()
    root = compile_cache.DEFAULT_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "src" / "repro" / "launch" / "compile_cache.py").exists()
    assert calls == [("jax_compilation_cache_dir", path)]
