"""Serving cells: a model served by ``repro.serve.InferenceEngine`` under
an open-loop request schedule.

Set-up builds the engine as the configuration file states (settings it
leaves out take the program's defaults), with the benchmark's own random
weights from the seed, and warms up the decode tick and every prefill
width the cell's prompts can produce. The window then submits each
request when it is due and calls ``step()`` while any request is in the
engine. A token is delivered when the ``step()`` that made it returns.

End-to-end (host clock, over the whole window):

* ``tokens_per_s``: tokens delivered in the window / window seconds;
* ``itl_p95_ms``: 95th percentile of every gap between two consecutive
  tokens of one request whose later token lands in the window;
* ``ttft_p95_ms``: 95th percentile, over the requests due in the window,
  of the first token's time minus the time the request was due (one
  with no token at the close enters with its wait so far).

``correct``: once the window has closed and the engine is freed, a
sample of the finished requests drawn from the seed, the longest among
them, with at least ``check_tokens`` served tokens, is run through the
plain reference that the configuration names (``reference``: the module
``bench/reference/<name>.py``, which also makes the weights) over its
prompt and served tokens. The widest gap by which a served token's
reference logit lies below the reference's best at that position, and
the largest relative error of the served telemetry (the compensated
squared logit norm), are held to the limits in the configuration file.

The control (``cell.control``) puts the reference's lower-precision
forward in the program's place: at each position of the same sample, the
token it puts first and its norm are held to the same limits, and the
run comes out not correct.
"""

from __future__ import annotations

import contextlib
import importlib
import time
from typing import Any, Dict, List, Tuple

import numpy as np

from bench import generator
from bench.harness import Cell, Outcome, log, memory_peak

#: config "model" keys -> repro ArchConfig fields they must equal
ARCH_FIELDS = {"n_layers": "n_layers", "d_model": "d_model",
               "n_heads": "n_heads", "n_kv_heads": "n_kv_heads",
               "d_ff": "d_ff", "vocab_size": "vocab_size", "norm": "norm",
               "mlp": "mlp", "tie_embeddings": "tie_embeddings",
               "rope_theta": "rope_theta", "dtype": "param_dtype"}


def reference_of(conf: Dict[str, Any]):
    """The configuration's plain reference, ``bench/reference/<name>.py``:
    ``make_weights``, ``program_params`` and ``logits_at``."""
    return importlib.import_module(f"bench.reference.{conf['reference']}")


def build(conf: Dict[str, Any], seed: int):
    """(engine, weights): the engine as the file states, over the
    benchmark's weights (the reference's ``make_weights``) from ``seed``."""
    import jax

    from repro.configs import get_config
    from repro.models import build_model
    from repro.serve import EngineConfig, InferenceEngine

    cfg = get_config(conf["arch"]).replace(**conf.get("arch_overrides", {}))
    m = conf["model"]
    for key, field in ARCH_FIELDS.items():
        if key in m and getattr(cfg, field) != m[key]:
            raise ValueError(f"{conf['arch']}: {field}={getattr(cfg, field)!r}"
                             f" but the configuration file states {m[key]!r}")
    if cfg.compute_dtype != m["dtype"]:
        raise ValueError(f"compute dtype {cfg.compute_dtype} != {m['dtype']}")
    model = build_model(cfg)
    ref = reference_of(conf)
    want = jax.eval_shape(lambda k: model.init(k)[0], jax.random.key(0))
    weights = ref.make_weights(
        m, weight_key(seed), want["embed"]["table"].shape[0],
        getattr(jax.numpy, m["dtype"]))
    params = ref.program_params(weights)
    got = jax.tree.map(lambda x: (x.shape, x.dtype), params)
    if got != jax.tree.map(lambda x: (x.shape, x.dtype), want):
        raise ValueError(f"the program's parameter tree is not the one "
                         f"{ref.__name__}.program_params lays out")
    engine = InferenceEngine(cfg, EngineConfig(**conf["engine"]),
                             model=model, params=params)
    return engine, weights


def weight_key(seed: int):
    """A JAX key from any whole number (the seed may pass 32 bits)."""
    import jax

    words = np.random.SeedSequence(seed).generate_state(2)
    return jax.random.fold_in(jax.random.key(int(words[0] >> 1)),
                              int(words[1] >> 1))


def chunk_pieces(offset: int, end: int, chunk: int) -> List[Tuple[int, int, int]]:
    """(offset, nvalid, width) of each prefill chunk over [offset, end):
    full chunks, then the tail padded to a power of two (at most chunk)."""
    out = []
    while offset < end:
        n = min(chunk, end - offset)
        width = chunk if n == chunk else min(chunk, 1 << (n - 1).bit_length())
        out.append((offset, n, width))
        offset += n
    return out


def warm_up(engine, traffic: Dict[str, Any]) -> List[int]:
    """Serve one short request per prefill width the cell's prompts can
    produce (and so compile or load the tick, the reset and each width);
    returns the widths."""
    from repro.serve import Request, SamplingParams

    c = engine.ec.prefill_chunk
    p = traffic["prompt"]
    widths = sorted({w for plen in range(p["min"], p["max"] + 1)
                     for _, _, w in chunk_pieces(0, plen, c)})
    for w in widths:
        plen = c + (w // 2 + 1 if w > 1 else 1) if w < c else c
        engine.submit(Request(prompt=np.ones(plen, np.int32),
                              sampling=SamplingParams(max_new_tokens=2)))
    while engine.scheduler.busy:
        engine.step()
    engine.pop_finished()
    want = {(w, False) for w in widths}
    if set(engine.prefill_programs) != want:
        raise RuntimeError(f"warm-up ran prefill programs "
                           f"{engine.prefill_programs}, expected {sorted(want)}")
    return widths


def drive(cell: Cell, engine, reqs: List[generator.Req]):
    """The open loop. Returns (per-request records, per-step records, the
    steps inside the traced part, the window's bounds, the generator's
    largest lateness)."""
    from repro.serve import Request, SamplingParams

    tr = cell.traffic
    start = time.perf_counter()
    ws = start + tr.get("ramp_s", 0.0)
    we = ws + cell.seconds
    trace_to = ws + min(cell.seconds, tr["trace_seconds"])
    recs: Dict[int, Dict[str, Any]] = {}
    steps: List[Dict[str, Any]] = []
    active: Dict[int, Any] = {}           # request id -> unfinished handle
    i, late = 0, 0.0
    tracing = contextlib.ExitStack()
    state, first, last = "before" if cell.trace else "done", 0, 0
    while True:
        now = time.perf_counter()
        if state == "on" and (now >= trace_to or now >= we):
            tracing.close()
            state, last = "done", len(steps)
        if now >= we:
            break
        if state == "before" and now >= ws:
            tracing.enter_context(cell.traced())
            state, first = "on", len(steps)
        with cell.span("bench.submit"):
            while i < len(reqs) and start + reqs[i].due_s <= now:
                r = reqs[i]
                h = engine.submit(Request(
                    prompt=r.prompt, request_id=i,
                    sampling=SamplingParams(max_new_tokens=r.max_new_tokens)))
                recs[i] = {"due": start + r.due_s, "tokens": [], "handle": h}
                late = max(late, now - (start + r.due_s))
                active[i] = h
                i += 1
        if not engine.scheduler.busy:
            wake = min(start + reqs[i].due_s if i < len(reqs) else we, we,
                       trace_to if state == "on" else we,
                       ws if state == "before" else we)
            with cell.span("bench.idle"):
                time.sleep(max(0.0, wake - now))
            continue
        before = {rid: h.prefill_pos for rid, h in active.items()}
        with cell.span("bench.step"):
            t_begin = time.perf_counter()
            events = engine.step()
            t_end = time.perf_counter()
        with cell.span("bench.readback"):
            prefill, decode = [], []
            for rid, pos0 in before.items():
                end = active[rid].prefill_pos
                if end > pos0:
                    prefill += chunk_pieces(pos0, end, engine.ec.prefill_chunk)
            for ev in events:
                rec = recs[ev.request_id]
                if rec["tokens"]:          # not the prefill's first token
                    decode.append(rec["handle"].prompt_len
                                  + len(rec["tokens"]) - 1)
                rec["tokens"].append(t_end)
                if ev.done:
                    active.pop(ev.request_id, None)
            steps.append({"t0": t_begin, "t1": t_end, "prefill": prefill,
                          "decode": decode,
                          "occupancy": engine.scheduler.occupancy,
                          "queued": engine.scheduler.queued})
    return recs, steps, steps[first:last], (start, ws, we), late


def p95(xs: List[float]) -> float:
    return float(np.percentile(np.asarray(xs, np.float64), 95))


def end_to_end(recs, ws: float, we: float) -> Tuple[Dict[str, float], int]:
    tokens, itl, ttft, due_in = 0, [], [], 0
    for rec in recs.values():
        ts = rec["tokens"]
        tokens += sum(ws <= t <= we for t in ts)
        itl += [b - a for a, b in zip(ts, ts[1:]) if ws <= b <= we]
        if ws <= rec["due"] < we:
            due_in += 1
            first = ts[0] if ts and ts[0] <= we else we
            ttft.append(first - rec["due"])
    seconds = we - ws
    return {"tokens_per_s": tokens / seconds,
            "itl_p95_ms": 1e3 * p95(itl) if itl else float("nan"),
            "ttft_p95_ms": 1e3 * p95(ttft) if ttft else float("nan")}, due_in


def sample(recs, seed: int, check_tokens: int) -> List[Any]:
    """Finished requests drawn from the seed: the longest, then others
    until at least ``check_tokens`` served tokens."""
    done = [r["handle"] for r in recs.values() if r["handle"].done]
    if not done:
        return []
    longest = max(done, key=lambda h: (h.prompt_len + len(h.tokens),
                                       h.request_id))
    rng = np.random.default_rng(np.random.SeedSequence([seed, 1]))
    rest = [done[k] for k in rng.permutation(len(done))
            if done[k] is not longest]
    out = [longest]
    served = len(longest.tokens)
    for h in rest:
        if served >= check_tokens:
            break
        out.append(h)
        served += len(h.tokens)
    return out


def compare(weights, handles, conf: Dict[str, Any], positions: int,
            control: bool = False) -> Dict[str, Dict[str, float]]:
    """The numbers compared, under ``"program"``: the widest logit gap
    and the largest telemetry error of the served tokens under the
    reference. With ``control`` also under ``"control"``: the same two
    numbers for the reference's lower-precision forward put in the
    program's place (the gap of the token it puts first, and its norm).
    ``positions`` (at least the longest output) fixes the shape the
    reference compiles to, so that each run loads it from the cache."""
    import jax.numpy as jnp

    ref = reference_of(conf)
    m = conf["model"]
    S = conf["engine"]["max_len"]
    P = positions
    zero = {"max_logit_gap": 0.0, "max_telemetry_rel_err": 0.0}
    out = {"program": dict(zero)}
    if control:
        out["control"] = dict(zero)

    def worst(side, gaps, norms, want):
        o = out[side]
        o["max_logit_gap"] = max(o["max_logit_gap"], float(gaps.max()))
        o["max_telemetry_rel_err"] = max(
            o["max_telemetry_rel_err"],
            float(np.max(np.abs(norms - want) / want)))

    for h in handles:
        seq = np.concatenate([np.asarray(h.request.prompt),
                              np.asarray(h.tokens[:-1], np.int32)])
        n = len(h.tokens)
        toks = np.zeros(S, np.int32)
        toks[:len(seq)] = seq
        at = np.zeros(P, np.int32)
        at[:n] = np.arange(h.prompt_len - 1, h.prompt_len - 1 + n)
        logits = ref.logits_at(weights, jnp.asarray(toks), jnp.asarray(at),
                               vocab=m["vocab_size"])
        exact = np.asarray(logits, np.float64)[:n]
        best = exact.max(axis=1)
        norm = np.sum(exact * exact, axis=1)
        rows = np.arange(n)
        served = np.asarray(h.tokens)
        worst("program", best - exact[rows, served],
              np.asarray(h.telemetry, np.float64) if h.telemetry else norm,
              norm)
        if control:
            low = np.asarray(ref.logits_at(
                weights, jnp.asarray(toks), jnp.asarray(at),
                vocab=m["vocab_size"], lowp=True), np.float64)[:n]
            worst("control", best - exact[rows, low.argmax(axis=1)],
                  np.sum(low * low, axis=1), norm)
    return out


def run(cell: Cell) -> Outcome:
    """One run; with ``cell.control`` the control's numbers are the ones
    held to the limits (the program's go to the notes)."""
    import gc

    import jax

    conf, tr = cell.config, cell.traffic
    engine, weights = build(conf, cell.seed)
    widths = warm_up(engine, tr)
    reqs = generator.schedule(tr, cell.seed, cell.seconds,
                              conf["model"]["vocab_size"])
    programs, misses = cell.compiles.reset() if cell.compiles else (0, 0)
    setup_s = time.perf_counter() - cell.t0
    log(f"setup_s={setup_s:.3f} programs={programs} cache_misses={misses} "
        f"prefill_widths={widths} requests={len(reqs)}")
    recs, steps, traced, (start, ws, we), late = drive(cell, engine, reqs)
    in_window = cell.compiles.reset() if cell.compiles else (0, 0)
    e2e, due_in = end_to_end(recs, ws, we)
    e2e["setup_s"] = setup_s
    peak = memory_peak(jax.devices()[:cell.chips])
    handles = sample(recs, cell.seed, conf["check_tokens"])
    stats = conf["engine"].get("track_stats", False)
    failed = sum(len(h.tokens) != h.request.sampling.max_new_tokens
                 or (stats and len(h.telemetry) != len(h.tokens))
                 or not np.all(np.isfinite(h.telemetry)) for h in handles)
    del engine                          # the reference runs in its room
    gc.collect()
    t = time.perf_counter()
    found = compare(weights, handles, conf, tr["output"]["max"],
                    control=cell.control)
    judged = found["control" if cell.control else "program"]
    checks = {k: (judged[k], lim) for k, lim in cell.limits.items()}
    if not handles:
        checks["finished_requests"] = (0.0, -1.0)
    ttft_all = [r["tokens"][0] - r["due"] for r in recs.values() if r["tokens"]]
    notes = {
        "window_s": we - ws, "ramp_s": ws - start,
        "generator_late_ms": 1e3 * late,
        "programs_in_window": in_window[0], "compiles_in_window": in_window[1],
        "requests_due_in_window": due_in,
        "requests_finished": sum(r["handle"].done for r in recs.values()),
        "queued_at_close": steps[-1]["queued"] if steps else 0,
        "steps": len(steps),
        "ttft_p95_ms": e2e["ttft_p95_ms"],
        "ttft_p50_ms_all": 1e3 * float(np.median(ttft_all)) if ttft_all else None,
        "check_requests": len(handles),
        "check_tokens": sum(len(h.tokens) for h in handles),
        "check_s": time.perf_counter() - t,
        "memory_peak_bytes": peak,
    }
    window = [s for s in steps if ws <= s["t0"] and s["t1"] <= we]
    if window:
        notes["queued_min_in_window"] = min(s["queued"] for s in window)
        notes["occupancy_min_in_window"] = min(s["occupancy"] for s in window)
    if cell.control:
        notes.update({f"program_{k}": v for k, v in found["program"].items()})
    records = {"traced_steps": traced, "window_steps": window,
               "window_s": we - ws, "model": conf["model"]}
    return Outcome(e2e=e2e, checks=checks, attempted=due_in, failed=failed,
                   records=records, memory_peak_bytes=peak, notes=notes)
