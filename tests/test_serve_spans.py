"""The serving engine's host spans and the counters they carry.

A small engine serves a staggered trace under ``jax.profiler.trace``;
the test reads the ``.xplane.pb`` back with ``jax.profiler.ProfileData``
and checks the spans' names, their nesting in ``serve.step``, and their
arguments against what the engine did. The spans change nothing the
engine computes: tokens and telemetry are bitwise the same with and
without a profiler session.
"""

import glob

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serve import EngineConfig, InferenceEngine, Request, SamplingParams

NAMES = {"serve.step", "serve.admit", "serve.prefill",
         "serve.prefill.dispatch", "serve.prefill.readback",
         "serve.tick.inputs", "serve.tick.dispatch", "serve.tick.readback",
         "serve.record", "serve.submit"}
TICK = ("serve.tick.inputs", "serve.tick.dispatch", "serve.tick.readback",
        "serve.record")


def _tiny_cfg():
    return ArchConfig(name="tiny", family="dense", n_layers=2, d_model=32,
                      n_heads=4, n_kv_heads=2, d_ff=64, vocab_size=128,
                      param_dtype="float32", compute_dtype="float32",
                      loss_chunk=64)


@pytest.fixture(scope="module")
def tiny():
    cfg = _tiny_cfg()
    model = build_model(cfg)
    params, _ = model.init(jax.random.key(0))
    return cfg, model, params


def _requests(cfg):
    # 20 tokens at chunk 8: chunks of 8, 8 and a 4-wide tail
    spec = [(20, 4), (5, 6), (9, 3), (3, 5)]
    rng = np.random.default_rng(7)
    return [Request(prompt=rng.integers(0, cfg.vocab_size, (p,)).astype(np.int32),
                    sampling=SamplingParams(temperature=0.5, max_new_tokens=n,
                                            seed=i),
                    request_id=10 + i)
            for i, (p, n) in enumerate(spec)]


EC = EngineConfig(max_slots=2, max_len=32, track_stats=True, prefill_chunk=8,
                  prefill_budget=2)
ARRIVALS = [0, 0, 1, 3]


def _serve(tiny, kv_layout="dense"):
    """Serve the trace step by step; per step, the tokens each request
    emitted, whether its prefill finished, and the chunks run."""
    cfg, model, params = tiny
    ec = EngineConfig(**{**EC.__dict__, "kv_layout": kv_layout})
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    reqs = _requests(cfg)
    steps = []
    pending = list(zip(ARRIVALS, reqs))
    while pending or eng.scheduler.busy:
        while pending and pending[0][0] <= eng.t:
            eng.submit(pending.pop(0)[1])
        before = {rid: len(h.tokens) for rid, h in eng.handles.items()}
        events = eng.step()
        emitted = {}
        for e in events:
            emitted[e.request_id] = emitted.get(e.request_id, 0) + 1
        first = {rid for rid in emitted if before[rid] == 0}
        steps.append({"emitted": emitted, "first": first,
                      "chunks": list(eng.last_chunks),
                      "finished": sum(e.done for e in events)})
    out = {rid: (tuple(h.tokens), tuple(h.telemetry))
           for rid, h in eng.handles.items()}
    return out, steps


def _host_spans(trace_dir):
    path = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    assert path, "the profiler wrote no trace"
    spans = []
    for plane in ProfileData.from_file(path[0]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns,
                                  {k: v for k, v in e.stats
                                   if not k.startswith("_")}))
    return sorted(spans, key=lambda s: s[1])


@pytest.fixture(scope="module", params=["dense", "paged"])
def traced(tiny, tmp_path_factory, request):
    # compile outside the session, so the traced run is the steady one
    plain, _ = _serve(tiny, request.param)
    d = tmp_path_factory.mktemp(f"trace_{request.param}")
    with jax.profiler.trace(str(d)):
        served, steps = _serve(tiny, request.param)
    return plain, served, steps, _host_spans(d), request.param


def test_spans_are_named_and_nest_in_the_step(traced):
    _, _, steps, spans, kv_layout = traced
    # page reservation exists on the paged path alone
    want = NAMES | {"serve.reserve"} if kv_layout == "paged" else NAMES
    assert {s[0] for s in spans} == want
    admits = [s for s in spans if s[0] == "serve.admit"]
    for _, a, b, _ in (s for s in spans if s[0] == "serve.reserve"):
        assert any(sa <= a and b <= sb for _, sa, sb, _ in admits)
    step_spans = [s for s in spans if s[0] == "serve.step"]
    assert [s[3]["step_num"] for s in step_spans] == list(range(len(steps)))
    for name, a, b, _ in spans:
        if name in TICK or name.startswith("serve.prefill") \
                or name == "serve.admit":
            assert any(sa <= a and b <= sb for _, sa, sb, _ in step_spans), name
    # submissions happen between steps, never inside one
    for _, a, b, _ in (s for s in spans if s[0] == "serve.submit"):
        assert not any(sa < a < sb for _, sa, sb, _ in step_spans)


def _per_step(spans, name):
    """step index -> the spans of that name inside it."""
    step_spans = [s for s in spans if s[0] == "serve.step"]
    out = {}
    for k, (_, sa, sb, _) in enumerate(step_spans):
        out[k] = [s for s in spans if s[0] == name and sa <= s[1]
                  and s[2] <= sb]
    return out


def test_counters_match_what_the_engine_did(traced):
    _, _, steps, spans, _ = traced
    dispatch = _per_step(spans, "serve.tick.dispatch")
    record = _per_step(spans, "serve.record")
    prefill = _per_step(spans, "serve.prefill")
    for k, st in enumerate(steps):
        live = sum(st["emitted"].values()) - len(st["first"])
        assert len(dispatch[k]) == (1 if live else 0), k
        assert [(s[3]["request_id"], s[3]["width"]) for s in prefill[k]] \
            == [(rid, w) for rid, w, _ in st["chunks"]], k
        if live:
            args = dispatch[k][0][3]
            assert args["live"] == live, k
            assert args["chunks"] == len(st["chunks"]), k
            assert record[k][0][3]["finished"] == st["finished"], k
    assert any(s[3]["chunks"] > 0 for d in dispatch.values() for s in d)
    assert any(s[3]["admitted"] > 0 for d in dispatch.values() for s in d)


def test_request_ids_follow_a_request(traced):
    _, served, steps, spans, _ = traced
    submits = [s[3] for s in spans if s[0] == "serve.submit"]
    assert sorted(a["request_id"] for a in submits) == sorted(served)
    lens = {a["request_id"]: a["prompt_len"] for a in submits}
    assert lens == {10: 20, 11: 5, 12: 9, 13: 3}
    chunks = [s[3] for s in spans if s[0] == "serve.prefill"]
    for rid, plen in lens.items():
        mine = [(a["offset"], a["width"]) for a in chunks
                if a["request_id"] == rid]
        assert mine[0][0] == 0 and sum(min(w, plen - o) for o, w in mine) \
            == plen, rid
    # the engine first runs each chunk program once
    seen = set()
    for a in chunks:
        assert a["new_program"] == int(a["width"] not in seen)
        seen.add(a["width"])
    # one readback per request, on its final chunk
    assert sum(s[0] == "serve.prefill.readback" for s in spans) == len(lens)


def test_profiling_changes_no_bit(traced):
    plain, served, _, _, _ = traced
    assert served == plain


def test_launcher_profile_writes_the_spans(tmp_path, monkeypatch):
    """``--profile DIR`` traces the launcher's run: a request is found by
    its id in ``serve.submit`` and ``serve.prefill``."""
    from repro.launch import serve as launch

    # tests keep the persistent compile cache off
    monkeypatch.setattr(launch, "enable_compile_cache", lambda: None)
    launch.main(["--arch", "olmo-1b", "--smoke", "--trace", "0:5:2,1:3:2",
                 "--max-slots", "2", "--prefill-chunk", "4",
                 "--profile", str(tmp_path)])
    spans = _host_spans(tmp_path)
    assert {s[3]["request_id"] for s in spans if s[0] == "serve.submit"} \
        == {0, 1}
    assert [s[3]["request_id"] for s in spans if s[0] == "serve.prefill"] \
        == [0, 0, 1]


def _serve_sessions(tiny):
    """A paged engine with the prefix cache and a pool too small for
    every request at once: a shared 16-token head admits by reference,
    and the queue's head stalls for pages."""
    cfg, model, params = tiny
    ec = EngineConfig(**{**EC.__dict__, "kv_layout": "paged",
                         "page_size": 4, "num_pages": 8,
                         "prefix_cache": True, "prefill_budget": None})
    eng = InferenceEngine(cfg, ec, model=model, params=params)
    rng = np.random.default_rng(11)
    head = rng.integers(0, cfg.vocab_size, (16,))
    # the last request shares nothing: it takes the head's cached pages
    heads = [head] * 4 + [rng.integers(0, cfg.vocab_size, (16,))]
    reqs = [Request(prompt=np.concatenate(
        [h, rng.integers(0, cfg.vocab_size, (n,))]).astype(np.int32),
        sampling=SamplingParams(temperature=0.5, max_new_tokens=4, seed=i),
        request_id=i) for i, (h, n) in enumerate(zip(heads, (3, 7, 2, 9, 8)))]
    served = eng.run(reqs, [0, 3, 3, 3, 4])
    return eng, {rid: (tuple(h.tokens), tuple(h.telemetry))
                 for rid, h in served.items()}


def test_reserve_spans_agree_with_the_counters(tiny, tmp_path):
    """``serve.reserve``: one span per attempt to admit the queue's head;
    the admitted spans' ``hit_tokens`` sum to ``prefix_hit_tokens`` and
    the stalled ones count ``page_stalls``; pages add up, and profiling
    changes no bit on the paged path."""
    _, plain = _serve_sessions(tiny)
    with jax.profiler.trace(str(tmp_path)):
        eng, served = _serve_sessions(tiny)
    assert served == plain
    args = [s[3] for s in _host_spans(tmp_path) if s[0] == "serve.reserve"]
    admitted = [a for a in args if a["admitted"] == 1]
    stalled = [a for a in args if a["admitted"] == 0]
    assert sorted(a["request_id"] for a in admitted) == list(range(5))
    assert sum(a["hit_tokens"] for a in admitted) == eng.prefix_hit_tokens > 0
    assert len(stalled) == eng.page_stalls > 0
    assert any(a["evicted_pages"] > 0 for a in args)
    for a in admitted:
        need = -(-(a["prompt_len"] + 4 - 1) // 4)
        assert a["shared_pages"] + a["new_pages"] == need
        assert a["hit_tokens"] <= 4 * a["shared_pages"] + 3
    for a in stalled:
        assert a["hit_tokens"] == a["shared_pages"] == a["new_pages"] == 0
