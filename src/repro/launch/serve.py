"""Serving launcher: request-trace driver over the continuous-batching
engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2.5-3b --smoke \
        --trace 0:32:16,1:8:4,3:24:8 [--max-slots 4] [--stats] \
        [--prefill-chunk 64] [--prefill-budget 1] \
        [--scheme kahan] [--unroll 8] [--compute-dtype float32]

``--trace`` replays a staggered-arrival request trace through
``repro.serve.InferenceEngine``: a comma-separated list of
``arrival:prompt_len:new_tokens[:temperature]`` cells, one per request
(arrival measured in engine steps). Mixed prompt lengths and output
lengths are the point — finished requests free their decode slot
mid-flight and queued requests are prefilled into the gap. Trace cells
are validated at the parse boundary (negative arrivals, zero lengths and
negative temperatures fail fast with the offending cell, not as an
opaque shape error inside a jit trace).

``--prefill-chunk`` splits every prompt into fixed-size chunks (partial
tails round up to power-of-two buckets), so a mixed-length trace
compiles O(#buckets) prefill programs instead of one per distinct prompt
length; ``0`` selects the legacy one-shot admit (bitwise-identical
output, one compiled program per length). ``--prefill-budget`` caps the
prefill chunks run per engine step (0 = unbounded): with a budget set, a
long prompt prefills across steps while the occupied slots keep
decoding every step — no head-of-line blocking. Without ``--trace``, a
uniform batch is synthesized from ``--batch`` / ``--prompt-len`` /
``--new-tokens``.

``--stats`` turns on the compensated telemetry path: per-request squared
logit norms computed with the engine's batched (batch, steps) Pallas grid
(``models.layers.activation_sq_norm`` — the ``(s, c)`` accumulator
contract with the deterministic two-sum merge), one launch per decode
tick for the whole slot batch. A request's token AND telemetry trace are
bitwise identical however the trace interleaves it with other traffic.

``--scheme`` picks any registered compensation scheme (naive / kahan /
pairwise / dot2 / plugins) — the launcher builds ONE
``repro.kernels.Policy`` and hands it to ``EngineConfig.policy``.

``--kv-layout paged`` re-homes the pageable KV leaves into a fixed page
pool addressed through per-request page tables (``--page-size`` /
``--num-pages`` size it; live KV memory then scales with live tokens),
and ``--prefix-cache`` keeps finished prompts' pages in a radix prefix
tree so shared prompt prefixes admit by reference. Both are
bitwise-neutral: the dense layout is the oracle and every token and
telemetry value matches it exactly. With the paged layout the per-step
log line carries the pool counters (pages in use / free, prefix-hit
tokens, admission stalls on page exhaustion).

``--profile DIR`` writes a ``jax.profiler`` trace of the whole run under
``DIR`` (``plugins/profile/<time>/*.xplane.pb``; open it in TensorBoard
or Perfetto, or read it with ``jax.profiler.ProfileData``), with the
Python tracer off and host spans at level 1, as the benchmark traces.
The engine's host spans land on the device's timeline. To follow one
request, take its id from the per-step log: ``serve.submit`` carries
``request_id`` and ``prompt_len``; each of its prefill chunks is a
``serve.prefill`` span with the same ``request_id`` (and ``width``,
``offset``, ``new_program``) inside a ``serve.step`` whose ``step_num``
is the log's step number; from the step that logs its first token on,
it is one of the ``live`` slots of each ``serve.tick.dispatch``, and
the ``jit_tick`` run that follows that span on the device computed its
token. The ``serve.record`` span of the step that logs its last token
(``*``) counts it under ``finished``.
"""

import argparse
import contextlib
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

from repro.configs import ArchConfig, get_config, get_smoke
from repro.kernels import Policy, schemes
from repro.launch.compile_cache import enable_compile_cache
from repro.serve import EngineConfig, InferenceEngine, Request, SamplingParams


def parse_trace(spec: str, default_temp: float,
                ) -> List[Tuple[int, int, int, float]]:
    """'arrival:prompt_len:new_tokens[:temperature],...' -> tuples.

    Validates every cell at the parse boundary (the engine's fail-fast
    convention): a bad cell names itself here instead of surfacing as an
    opaque shape error deep inside the prefill trace."""
    cells = []
    for cell in spec.split(","):
        parts = cell.strip().split(":")
        if len(parts) not in (3, 4):
            raise ValueError(
                f"trace cell {cell!r}: want arrival:prompt_len:new_tokens"
                "[:temperature]")
        arrival, plen, new = (int(p) for p in parts[:3])
        temp = float(parts[3]) if len(parts) == 4 else default_temp
        if arrival < 0:
            raise ValueError(
                f"trace cell {cell!r}: arrival must be >= 0 (engine "
                f"steps), got {arrival}")
        if plen < 1:
            raise ValueError(
                f"trace cell {cell!r}: prompt_len must be >= 1, got "
                f"{plen} (an empty prompt has no prefill logits to "
                "sample the first token from)")
        if new < 1:
            raise ValueError(
                f"trace cell {cell!r}: new_tokens must be >= 1, got {new}")
        if temp < 0:
            raise ValueError(
                f"trace cell {cell!r}: temperature must be >= 0 "
                f"(0 = greedy), got {temp}")
        cells.append((arrival, plen, new, temp))
    return cells


def build_parser() -> argparse.ArgumentParser:
    """The launcher's command line (also parsed by ``chip_smoke.py``)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--trace", default="",
                    help="request trace: arrival:prompt_len:new_tokens"
                         "[:temperature], comma-separated; empty -> a "
                         "uniform batch from --batch/--prompt-len/"
                         "--new-tokens, all arriving at step 0")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--max-slots", type=int, default=4,
                    help="decode batch width (concurrent requests)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-slot cache capacity; 0 -> fit the trace")
    ap.add_argument("--prefill-chunk", type=int, default=64,
                    help="prompt-chunk width for chunked prefill "
                         "(compiled prefill programs = chunk + power-of-"
                         "two tail buckets, independent of how many "
                         "distinct prompt lengths the trace has); 0 -> "
                         "legacy one-shot admit (one program per length)")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="max prefill chunks per engine step across all "
                         "admitting requests (bounds how long a long "
                         "prompt can stall running requests' decode); "
                         "0 -> unbounded (admits finish in their step)")
    ap.add_argument("--prefill-mode", default="scan",
                    help="chunk body: 'scan' (per-position oracle) or "
                         "'flash' (parallel multi-token chunk through the "
                         "engine's chunk flash kernel — prefill tokens/s "
                         "scales with chunk width; families whose "
                         "recurrence forces per-position stepping fall "
                         "back to scan). Validated at the parse boundary")
    ap.add_argument("--kv-layout", default="dense",
                    help="KV cache layout: 'dense' (fixed max_len row "
                         "per slot) or 'paged' (fixed page pool + traced "
                         "per-request page tables; live KV memory scales "
                         "with live tokens, bitwise-identical output). "
                         "Validated at the parse boundary")
    ap.add_argument("--page-size", type=int, default=16,
                    help="positions per KV page (power of two; max_len "
                         "is rounded up to a multiple). Paged layout only")
    ap.add_argument("--num-pages", type=int, default=0,
                    help="page-pool capacity; 0 -> dense parity "
                         "(max_slots * max_len / page_size). A smaller "
                         "pool admits by page availability (FIFO stalls "
                         "on exhaustion). Paged layout only")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="keep finished prompts' full pages in a "
                         "refcounted radix tree: requests sharing a "
                         "prompt prefix admit by reference and resume "
                         "prefill at the shared boundary (requires "
                         "--kv-layout paged)")
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the prompt contents and of the random "
                         "weights")
    ap.add_argument("--stats", action="store_true",
                    help="print compensated per-request logit norms")
    ap.add_argument("--scheme", default="kahan",
                    help="compensation scheme for the telemetry reductions "
                         f"(registered: {', '.join(sorted(schemes.names()))}"
                         "; runtime-registered schemes accepted — unknown "
                         "names fail fast with the menu)")
    ap.add_argument("--unroll", type=int, default=8,
                    help="accumulator-group count of the Pallas kernels")
    ap.add_argument("--profile", default="", metavar="DIR",
                    help="write a jax.profiler trace of the run under DIR "
                         "(the engine's serve.* host spans and the device's "
                         "programs on one timeline)")
    ap.add_argument("--compute-dtype", default="float32",
                    help="accumulate dtype for the compensated kernels "
                         "(float32 | bfloat16 | float64 — f64 needs x64 "
                         "and is refused on a TPU; unsupported dtypes fail "
                         "fast with the menu)")
    return ap


class Serving(NamedTuple):
    """What ``build_serving`` makes from the command line."""

    cfg: ArchConfig
    cells: List[Tuple[int, int, int, float]]
    requests: List[Request]
    arrivals: List[int]
    engine: InferenceEngine


def build_serving(args: argparse.Namespace,
                  cfg: Optional[ArchConfig] = None) -> Serving:
    """Validate the parsed flags and build the requests and the engine.

    ``cfg`` overrides the config that ``--arch`` / ``--smoke`` select
    (e.g. the same widths with ``kahan_attention=True``). The weights are
    random, drawn from ``--seed``.
    """
    if args.prefill_mode not in ("scan", "flash"):
        # parse-boundary validation, same convention as the trace cells:
        # the bad flag names itself here, not inside EngineConfig or a
        # jit trace
        raise ValueError(
            f"--prefill-mode must be 'scan' or 'flash', "
            f"got {args.prefill_mode!r}")
    if args.kv_layout not in ("dense", "paged"):
        raise ValueError(
            f"--kv-layout must be 'dense' or 'paged', "
            f"got {args.kv_layout!r}")
    if args.prefix_cache and args.kv_layout != "paged":
        raise ValueError(
            "--prefix-cache requires --kv-layout paged (prefix sharing "
            "is page-granular)")

    if args.trace:
        cells = parse_trace(args.trace, args.temperature)
    else:
        cells = [(0, args.prompt_len, args.new_tokens, args.temperature)
                 for _ in range(args.batch)]

    policy = Policy(scheme=args.scheme, unroll=args.unroll,
                    compute_dtype=args.compute_dtype)
    if cfg is None:
        cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    max_len = args.max_len or max(p + n for _, p, n, _ in cells)
    if args.kv_layout == "paged" and max_len % args.page_size:
        # EngineConfig requires max_len % page_size == 0; a fitted
        # max_len just rounds up to the next page boundary
        max_len += args.page_size - max_len % args.page_size

    rng = np.random.default_rng(args.seed)
    requests, arrivals = [], []
    for arrival, plen, new, temp in cells:
        extras = {}
        if cfg.vision is not None:
            extras["vision_embeds"] = rng.standard_normal(
                (cfg.vision.n_patches, cfg.d_model)).astype(np.float32)
        if cfg.encoder is not None:
            extras["frames"] = rng.standard_normal(
                (cfg.encoder.n_frames, cfg.d_model)).astype(np.float32)
        # request_id pinned to the trace-cell index: submission order is
        # arrival-sorted, so auto-assigned ids would misalign the final
        # per-request report with its cell for out-of-order traces.
        requests.append(Request(
            prompt=rng.integers(0, cfg.vocab_size, (plen,)).astype(np.int32),
            sampling=SamplingParams(temperature=temp, max_new_tokens=new),
            request_id=len(requests), extras=extras or None))
        arrivals.append(arrival)

    engine = InferenceEngine(
        cfg, EngineConfig(max_slots=args.max_slots, max_len=max_len,
                          track_stats=args.stats, policy=policy,
                          prefill_chunk=args.prefill_chunk or None,
                          prefill_budget=args.prefill_budget or None,
                          prefill_mode=args.prefill_mode,
                          kv_layout=args.kv_layout,
                          page_size=args.page_size,
                          num_pages=args.num_pages or None,
                          prefix_cache=args.prefix_cache),
        seed=args.seed)
    return Serving(cfg, cells, requests, arrivals, engine)


def profiled(trace_dir: str):
    """A ``jax.profiler`` trace into ``trace_dir`` (nothing when empty),
    with the Python tracer off and host spans at level 1."""
    if not trace_dir:
        return contextlib.nullcontext()
    import jax

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    opts.enable_hlo_proto = False
    return jax.profiler.trace(trace_dir, profiler_options=opts)


def main(argv: Optional[Sequence[str]] = None) -> None:
    args = build_parser().parse_args(argv)
    enable_compile_cache()
    cfg, cells, requests, arrivals, engine = build_serving(args)
    if args.kv_layout == "paged" and engine.kv_layout == "dense":
        print(f"# kv-layout 'paged' requested but family {cfg.family!r} "
              f"has no pageable KV leaf (recurrent/ring state only) — "
              f"running the dense layout")
    if engine.prefill_body != args.prefill_mode:
        print(f"# prefill-mode {args.prefill_mode!r} requested but family "
              f"{cfg.family!r} runs the {engine.prefill_body!r} body "
              f"(per-position fallback — recurrent state or unsupported "
              f"config)")
    paged = engine.kv_layout == "paged"
    with profiled(args.profile):
        for t, events in engine.stream(requests, arrivals):
            chunks = " ".join(f"r{rid}+{w}/{body}"
                              for rid, w, body in engine.last_chunks)
            emitted = ", ".join(
                f"r{e.request_id}:{e.token}{'*' if e.done else ''}"
                for e in events)
            pages = ""
            if paged:
                st = engine.page_stats()
                pages = (f" pages={st['pages_in_use']}/{st['num_pages']}"
                         f" stalls={st['page_stalls']}")
                if args.prefix_cache:
                    pages += (f" prefix-hit={st['prefix_hit_tokens']}tok"
                              f" cached={st['prefix_cached_pages']}pg")
            print(f"# step {t:3d} occupancy={engine.scheduler.occupancy} "
                  f"prefilling={len(engine.scheduler.prefilling)} "
                  f"queued={engine.scheduler.queued}{pages}"
                  f"{'  chunks: ' + chunks if chunks else ''}  {emitted}")
    print(f"# compiled prefill programs (width, runs_setup): "
          f"{list(engine.prefill_programs)} body={engine.prefill_body}")
    if paged:
        st = engine.page_stats()
        print(f"# kv-layout=paged page_size={args.page_size} "
              f"pool={st['num_pages']} free={st['free_pages']} "
              f"prefix_pages={st['prefix_pages']} "
              f"prefix_hit_tokens={st['prefix_hit_tokens']} "
              f"page_stalls={st['page_stalls']} "
              f"kv_bytes_in_use={st['kv_bytes_in_use']}")

    for rid, h in sorted(engine.handles.items()):
        arrival, plen, new, temp = cells[rid]
        print(f"request {rid} (arrived t={arrival}, prompt={plen}, "
              f"new={new}, temp={temp}): {h.tokens}")
        if args.stats and h.telemetry:
            print(f"request {rid}: |logits|^2 ({args.scheme}) "
                  f"first={h.telemetry[0]:.6e} last={h.telemetry[-1]:.6e}")


if __name__ == "__main__":
    main()
