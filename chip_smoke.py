"""Bring-up run of the main path on a TPU, through the user entry points.

    python chip_smoke.py [--seed 0]      # one chip: kernel + serving phases
    python chip_smoke.py --chips 4       # four chips: sharded reductions only

Kernel phase (the paper's path): ``ops.dot`` and ``ops.asum`` for every
registered scheme at n = 2**26 f32 (256 MiB per operand, HBM-resident),
``ops.matmul`` at 2048**3 and ``kernels.flash_attention`` at
16 x 2048 x 128 for naive and kahan. Each result is checked against an
f64 host reference within the scheme's a-priori ``error_bound`` (flash
within ``FLASH_TOL``, which also bounds its distance from the jnp oracle
``ref.flash_attention_ref``, compiled by XLA on the chip). Each compiled
call must contain a Mosaic kernel (``tpu_custom_call``), and on one
ill-conditioned GenDot summation input kahan's error must be no larger
than naive's.

Serving phase: olmo-1b at its published widths with random weights from
``--seed``, built by the serving launcher's own ``build_serving``. The
same staggered four-request trace is served three times on 4 slots: with
the scan prefill body, with ``kahan_attention=True, prefill_mode="flash"``,
and with flash prefill from the paged KV pool. A 16-request trace is then
served with flash prefill on 16 slots, the serving cells' width, in both
layouts. Every request must finish with its token count and finite
telemetry. In each run one request, which decoded in another slot with
every slot busy beside it, is replayed solo in slot 0 and must give
bitwise the tokens and telemetry of the interleaved run: the batched
decode tick's rows do not depend on their slot or their neighbours.
Every request served from the paged pool must give bitwise the tokens
and telemetry of the dense flash run at the same width. The two bodies'
prefill logits at each request's last prompt position must agree within
``LOGITS_TOL``.

``--chips 4``: ``collectives.sharded_{asum,dot,matmul}`` on a 4-device
mesh. Each result must equal, bitwise, ``merge_accumulators`` of the same
per-shard grids computed on one chip, lie within the bound of the f64
reference, and come from a compiled program that all-gathers across the
4 devices and holds no all-reduce.

Everything runs in this one process (a chip belongs to one process at a
time). Without a TPU the script exits non-zero before any phase: nothing
falls back to the CPU. A failed check raises, so the run exits non-zero.
Seconds printed here are smoke timings of one cold run, not metrics. The
last line of standard output is one JSON object naming the device.
"""

from __future__ import annotations

import argparse
import functools
import json
import pathlib
import re
import sys
import time

import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent / "src"))

N_VEC = 1 << 26                 # dot / asum length: 256 MiB per f32 operand
MM = 2048                       # matmul M = N = K
FLASH = (16, 2048, 128)         # flash (BH, S, dh): olmo-1b heads x ctx x dh
# ill-conditioned GenDot summation input (n, requested cond), summed with
# unroll=1: its summands are exact, and the kernel's chains are long
GENDOT = (1 << 20, 1e2)
# staggered arrivals (engine steps) : prompt length : new tokens. The
# prompt lengths need only the 64-wide chunk and its 32-wide tail bucket.
TRACE = "0:17:32,1:96:32,2:31:32,4:192:32"
SOLO = 3                        # request replayed alone (the longest)
# the serving cells' width: 16 requests at once, one prefill chunk each,
# so request SOLO16 decodes in slot SOLO16 with every slot busy beside it.
# Their longest row is 80 positions, a whole number of 16-position pages,
# so the dense and the paged engine fit the same max_len.
SLOTS16 = 16
SOLO16 = 11
TRACE16 = ",".join(f"0:{17 + 3 * i}:{24 if i == SOLO16 else 18}"
                   + (":0.7" if i == SOLO16 else "")
                   for i in range(SLOTS16))

# flash vs the f64 host reference: largest absolute error on outputs of
# magnitude O(1) (an average of unit normals)
FLASH_TOL = 1e-4
# scan vs flash prefill logits, bf16 model: largest absolute difference
# relative to the largest |logit| of the scan body
LOGITS_TOL = 5e-2


class CheckFailed(AssertionError):
    """A smoke check did not hold."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise CheckFailed(what)


def log(msg: str) -> None:
    print(msg, flush=True)


def compile_and_run(fn, *args):
    """(output, compiled HLO text, compile s, run s) of one jitted call."""
    import jax

    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    t1 = time.perf_counter()
    out = jax.block_until_ready(compiled(*args))
    return out, compiled.as_text(), t1 - t0, time.perf_counter() - t1


def mosaic_count(text: str, what: str) -> int:
    n = text.count("tpu_custom_call")
    check(n > 0, f"{what}: no Mosaic kernel (tpu_custom_call) in the "
                 f"compiled program — the kernel ran interpreted")
    return n


def sum_reference(terms64):
    """(sum, cond) of float64 terms that are exact: the sum in f64 and
    ``cond = 2 sum|t| / |sum t|``, the condition number the schemes'
    ``error_bound`` takes."""
    ref = float(np.sum(terms64))
    return ref, 2.0 * float(np.sum(np.abs(terms64))) / abs(ref)


def matmul_bound(scheme, block_k: int, a64, b64, ref):
    """Per-element a-priori bound of the engine matmul: the MXU sums each
    block_k-long tile uncompensated (naive bound), and the scheme folds
    the K / block_k tiles. ``cond`` is per output element."""
    from repro.kernels import schemes

    absprod = np.abs(a64) @ np.abs(b64)
    cond = 2.0 * absprod / np.maximum(np.abs(ref), np.finfo(np.float64).tiny)
    k = a64.shape[1]
    rel = (schemes.get("naive").error_bound(block_k, cond)
           + scheme.error_bound(k // block_k, cond))
    return rel * np.abs(ref)


def attention_f64(q, k, v):
    """Causal softmax attention in float64 on the host, [BH, S, dh]."""
    q, k, v = (x.astype(np.float64) for x in (q, k, v))
    s = q.shape[1]
    mask = np.tril(np.ones((s, s), bool))
    out = np.empty_like(q)
    for h in range(q.shape[0]):
        sc = (q[h] @ k[h].T) * q.shape[-1] ** -0.5
        sc = np.where(mask, sc, -np.inf)
        p = np.exp(sc - sc.max(axis=-1, keepdims=True))
        out[h] = (p / p.sum(axis=-1, keepdims=True)) @ v[h]
    return out


# ---------------------------------------------------------------------------
# Kernel phase
# ---------------------------------------------------------------------------

def kernel_phase(seed: int) -> None:
    import jax

    from repro.core import numerics
    from repro.kernels import engine, ops, schemes
    from repro.kernels import ref as ref_oracle
    from repro.kernels.flash_attention import flash_attention

    check(engine.resolve_interpret(None) is False,
          "engine.resolve_interpret(None) is not False on this backend")
    rng = np.random.default_rng(seed)

    # -- dot / asum, every registered scheme ---------------------------------
    a = rng.standard_normal(N_VEC, dtype=np.float32)
    b = rng.standard_normal(N_VEC, dtype=np.float32)
    ref_dot, cond_dot = sum_reference(a.astype(np.float64) * b)  # exact
    ref_sum, cond_sum = sum_reference(a.astype(np.float64))
    a_d, b_d = jax.device_put(a), jax.device_put(b)
    for name in schemes.names():
        sch = schemes.get(name)
        for op, fn, args, ref, cond in (
                ("dot", functools.partial(ops.dot, scheme=name), (a_d, b_d),
                 ref_dot, cond_dot),
                ("asum", functools.partial(ops.asum, scheme=name), (a_d,),
                 ref_sum, cond_sum)):
            out, text, tc, tr = compile_and_run(fn, *args)
            kernels = mosaic_count(text, f"{op}/{name}")
            err = numerics.relative_error(float(out), ref)
            bound = sch.error_bound(N_VEC, cond)
            log(f"kernel {op}/{name} n={N_VEC} compile_s={tc:.3f} "
                f"run_s={tr:.4f} rel_err={err:.3e} bound={bound:.3e} "
                f"cond={cond:.3e} tpu_custom_call={kernels}")
            check(err <= bound, f"{op}/{name}: rel_err {err:.3e} > a-priori "
                                f"bound {bound:.3e}")

    # -- ill-conditioned GenDot: compensation must not lose to naive.
    # ``gen_sum`` is GenDot with b folded into a: on a dot, the fp32
    # rounding of the products (which no summation scheme compensates)
    # would decide the comparison; on these exact summands only the
    # summation error is left, and that is what kahan compensates.
    n, cond = GENDOT
    gx, exact, achieved = numerics.gen_sum(n, cond, seed=seed)
    gx_d = jax.device_put(gx)
    errs = {name: numerics.relative_error(
                float(ops.asum(gx_d, scheme=name, unroll=1)), exact)
            for name in ("naive", "kahan")}
    log(f"kernel gendot-sum n={n} unroll=1 cond={achieved:.3e} "
        f"naive_rel_err={errs['naive']:.3e} "
        f"kahan_rel_err={errs['kahan']:.3e}")
    check(errs["kahan"] <= errs["naive"],
          f"gendot-sum: kahan error {errs['kahan']:.3e} > naive "
          f"{errs['naive']:.3e}")

    # -- matmul 2048^3 -------------------------------------------------------
    am = rng.standard_normal((MM, MM), dtype=np.float32)
    bm = rng.standard_normal((MM, MM), dtype=np.float32)
    a64, b64 = am.astype(np.float64), bm.astype(np.float64)
    ref = a64 @ b64
    am_d, bm_d = jax.device_put(am), jax.device_put(bm)
    for name in ("naive", "kahan"):
        eng = engine.CompensatedReduction(scheme=name)
        block_k = min(eng.blocks[2], MM)
        out, text, tc, tr = compile_and_run(
            functools.partial(ops.matmul, scheme=name), am_d, bm_d)
        kernels = mosaic_count(text, f"matmul/{name}")
        err = np.abs(np.asarray(out, np.float64) - ref)
        bound = matmul_bound(schemes.get(name), block_k, a64, b64, ref)
        ratio = float(np.max(err / bound))
        log(f"kernel matmul/{name} {MM}^3 compile_s={tc:.3f} run_s={tr:.4f} "
            f"max_abs_err={float(err.max()):.3e} "
            f"max_err_over_bound={ratio:.3e} tpu_custom_call={kernels}")
        check(ratio <= 1.0, f"matmul/{name}: an element exceeds its "
                            f"a-priori bound ({ratio:.3e} x bound)")

    # -- flash attention -----------------------------------------------------
    q, k, v = (rng.standard_normal(FLASH, dtype=np.float32) for _ in range(3))
    ref = attention_f64(q, k, v)
    qkv = [jax.device_put(x) for x in (q, k, v)]
    for name in ("naive", "kahan"):
        out, text, tc, tr = compile_and_run(
            functools.partial(flash_attention, scheme=name), *qkv)
        kernels = mosaic_count(text, f"flash/{name}")
        err = float(np.max(np.abs(np.asarray(out, np.float64) - ref)))
        log(f"kernel flash/{name} {FLASH} compile_s={tc:.3f} run_s={tr:.4f} "
            f"max_abs_err={err:.3e} tol={FLASH_TOL:.1e} "
            f"tpu_custom_call={kernels}")
        check(err <= FLASH_TOL, f"flash/{name}: max abs error {err:.3e} > "
                                f"{FLASH_TOL:.1e}")

    # -- flash kernel vs its jnp oracle: Mosaic against XLA on the chip,
    # so held to FLASH_TOL (bitwise only in interpret mode)
    q, k, v = (jax.device_put(x[:2, :512]) for x in (q, k, v))
    for name in ("naive", "kahan"):
        got = np.asarray(flash_attention(q, k, v, scheme=name))
        want = np.asarray(ref_oracle.flash_attention_ref(q, k, v,
                                                         scheme=name))
        diff = float(np.max(np.abs(got - want)))
        log(f"kernel flash-vs-oracle/{name} {tuple(q.shape)} "
            f"max_abs_diff={diff:.3e} bitwise={np.array_equal(got, want)} "
            f"tol={FLASH_TOL:.1e}")
        check(diff <= FLASH_TOL, f"flash/{name} vs ref.flash_attention_ref: "
                                 f"{diff:.3e} > {FLASH_TOL:.1e}")


# ---------------------------------------------------------------------------
# Serving phase
# ---------------------------------------------------------------------------

def last_prompt_logits(serving, body: str, chunk: int = 64):
    """Logits at each request's last prompt position, from one prefill
    body driven directly in fixed ``chunk``-wide chunks (tails padded)."""
    import jax
    import jax.numpy as jnp

    from repro.kernels import use_policy

    eng = serving.engine
    model = eng.model
    fn = jax.jit(model.prefill_chunk if body == "scan"
                 else model.prefill_chunk_parallel)
    out = []
    for req in serving.requests:
        prompt = np.asarray(req.prompt)
        cache, _ = model.init_cache(1, eng.ec.max_len)
        for off in range(0, len(prompt), chunk):
            nvalid = min(chunk, len(prompt) - off)
            toks = np.zeros((1, chunk), np.int32)
            toks[0, :nvalid] = prompt[off:off + nvalid]
            with use_policy(eng.policy):
                logits, cache = fn(eng.params, {"tokens": jnp.asarray(toks)},
                                   cache, jnp.int32(off), jnp.int32(nvalid))
        out.append(np.asarray(logits, np.float32).reshape(-1)
                   [:serving.cfg.vocab_size])
    return out


def serve_once(seed: int, mode: str, cfg, layout: str = "dense",
               trace: str = TRACE, slots: int = 4, solo: int = SOLO):
    """Build through the launcher, serve ``trace`` on ``slots`` slots,
    check it; returns (serving, handles by request id). Request ``solo``
    is then replayed alone: it must land in another slot than it had
    interleaved, where every slot was busy beside it, and give the same
    bits."""
    from repro.launch import serve
    from repro.serve import InferenceEngine

    args = serve.build_parser().parse_args(
        ["--arch", cfg.name, "--trace", trace, "--max-slots", str(slots),
         "--stats", "--seed", str(seed), "--prefill-mode", mode,
         "--kv-layout", layout])
    sv = serve.build_serving(args, cfg=cfg)
    eng = sv.engine
    check(eng.prefill_body == mode and eng.kv_layout == layout,
          f"{mode}/{layout}: engine runs the {eng.prefill_body!r} prefill "
          f"body on the {eng.kv_layout!r} layout")
    req = sv.requests[solo]
    served = {}
    t0 = time.perf_counter()
    inter_slot, occupancy = run_watching(eng, sv.requests, sv.arrivals,
                                         req.request_id, served)
    cold = time.perf_counter() - t0
    for r in sv.requests:
        h = served[r.request_id]
        want = r.sampling.max_new_tokens
        check(h.done and len(h.tokens) == want,
              f"{mode}/{layout}: request {r.request_id} emitted "
              f"{len(h.tokens)} of {want} tokens")
        check(len(h.telemetry) == want
              and bool(np.all(np.isfinite(h.telemetry))),
              f"{mode}/{layout}: request {r.request_id} telemetry is not "
              f"finite")
    log(f"serve {cfg.name} prefill={eng.prefill_body} layout={layout} "
        f"slots={slots} steps={eng.t} programs={list(eng.prefill_programs)} "
        f"cold_s(compile+run)={cold:.3f}")

    solo_eng = InferenceEngine(sv.cfg, eng.ec, model=eng.model,
                               params=eng.params)
    solo_served = {}
    t0 = time.perf_counter()
    solo_slot, _ = run_watching(solo_eng, [req], None, req.request_id,
                                solo_served)
    warm = time.perf_counter() - t0
    solo, inter = solo_served[req.request_id], served[req.request_id]
    same = (solo.tokens == inter.tokens
            and np.array_equal(np.asarray(solo.telemetry, np.float32),
                               np.asarray(inter.telemetry, np.float32)))
    log(f"serve {mode}/{layout} slots={slots} solo-vs-interleaved request="
        f"{req.request_id} slot solo={solo_slot} interleaved={inter_slot} "
        f"occupancy_beside_it={sorted(occupancy)} bitwise={same} "
        f"solo_s={warm:.3f}")
    check(solo_slot != inter_slot and max(occupancy) == eng.ec.max_slots,
          f"{mode}/{layout}: the trace did not place request "
          f"{req.request_id} in another slot at full occupancy")
    check(same, f"{mode}/{layout}: request {req.request_id} solo differs "
                f"from its interleaved run")
    return sv, served


def run_watching(eng, requests, arrivals, rid: int, sink):
    """Drive ``requests`` to completion, collecting handles in ``sink``;
    returns (the slot request ``rid`` decoded in, the set of decode
    occupancies seen while it ran)."""
    slot, occupancy = None, set()
    for _ in eng.stream(requests, arrivals, _sink=sink):
        running = eng.scheduler.running
        for s, h in running.items():
            if h.request_id == rid:
                slot = s
                occupancy.add(len(running))
    return slot, occupancy


def check_paged_vs_dense(requests, paged_served, dense_served,
                         slots: int) -> None:
    """Every request's tokens and telemetry, served from the paged pool,
    are bitwise those of the dense run."""
    for req in requests:
        rid = req.request_id
        paged, dense = paged_served[rid], dense_served[rid]
        same = (paged.tokens == dense.tokens
                and np.array_equal(np.asarray(paged.telemetry, np.float32),
                                   np.asarray(dense.telemetry, np.float32)))
        log(f"serve paged-vs-dense (flash) slots={slots} request={rid} "
            f"bitwise={same}")
        check(same, f"slots={slots} request {rid}: the paged layout's "
                    f"tokens or telemetry differ from the dense layout's")


def serving_phase(seed: int) -> None:
    from repro.configs import get_config

    cfg = get_config("olmo-1b")
    kcfg = cfg.replace(kahan_attention=True)
    scan_sv, scan_served = serve_once(seed, "scan", cfg)
    flash_sv, flash_served = serve_once(seed, "flash", kcfg)
    paged_sv, paged_served = serve_once(seed, "flash", kcfg, layout="paged")
    check_paged_vs_dense(paged_sv.requests, paged_served, flash_served, 4)

    # the serving cells' width: the replayed request decoded beside 15
    served16 = {}
    for layout in ("dense", "paged"):
        sv16, served16[layout] = serve_once(
            seed, "flash", kcfg, layout=layout, trace=TRACE16,
            slots=SLOTS16, solo=SOLO16)
    check_paged_vs_dense(sv16.requests, served16["paged"], served16["dense"],
                         SLOTS16)

    t0 = time.perf_counter()
    scan_logits = last_prompt_logits(scan_sv, "scan")
    flash_logits = last_prompt_logits(flash_sv, "flash")
    log(f"serve logits replay s={time.perf_counter() - t0:.3f}")
    for req, ls, lf in zip(scan_sv.requests, scan_logits, flash_logits):
        rid = req.request_id
        diff = float(np.max(np.abs(lf - ls)))
        scale = float(np.max(np.abs(ls)))
        agree = sum(x == y for x, y in zip(scan_served[rid].tokens,
                                           flash_served[rid].tokens))
        log(f"serve flash-vs-scan request={rid} prompt={len(req.prompt)} "
            f"max_logit_diff={diff:.4e} max_abs_logit={scale:.4e} "
            f"rel={diff / scale:.3e} tol={LOGITS_TOL:.1e} "
            f"greedy_tokens_equal={agree}/{len(scan_served[rid].tokens)}")
        check(np.all(np.isfinite(lf)) and diff <= LOGITS_TOL * scale,
              f"request {rid}: flash and scan prefill logits differ by "
              f"{diff:.4e} (> {LOGITS_TOL:.1e} x {scale:.4e})")


# ---------------------------------------------------------------------------
# Sharded phase (--chips 4)
# ---------------------------------------------------------------------------

_GATHER4 = re.compile(
    r"all-gather[^\n]*replica_groups=(\{\{0,1,2,3\}\}|\[1,4\]<=\[4\])")


def sharded_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core import numerics
    from repro.distributed import collectives as col
    from repro.kernels import schemes
    from repro.kernels.engine import (CompensatedReduction,
                                      merge_accumulator_grids,
                                      merge_accumulators)

    devs = jax.devices()
    check(len(devs) == 4, f"--chips 4 needs 4 devices, found {len(devs)}")
    mesh = jax.make_mesh((4,), ("data",))
    d0 = devs[0]
    rng = np.random.default_rng(seed)

    def placed(x, spec):
        xs = jax.device_put(x, NamedSharding(mesh, spec))
        check(len({s.device for s in xs.addressable_shards}) == 4,
              "operand is not spread over the 4 devices")
        return xs

    def compiled_on_mesh(fn, *args, what):
        out, text, tc, tr = compile_and_run(fn, *args)
        gathers = [m.group(0)[:120] for m in _GATHER4.finditer(text)]
        mosaic_count(text, what)
        check(bool(gathers), f"{what}: no all-gather across the 4 devices")
        check("all-reduce" not in text, f"{what}: compiled program holds "
                                        f"an all-reduce")
        log(f"sharded {what} compile_s={tc:.3f} run_s={tr:.4f} "
            f"all_gathers={len(gathers)} e.g. {gathers[0]!r}")
        return out

    def bits(x):
        return np.asarray(x, np.float32).tobytes()

    a = rng.standard_normal(N_VEC, dtype=np.float32)
    b = rng.standard_normal(N_VEC, dtype=np.float32)
    a_s, b_s = placed(a, P("data")), placed(b, P("data"))
    shard = N_VEC // 4
    a0 = [jax.device_put(a[i * shard:(i + 1) * shard], d0) for i in range(4)]
    b0 = [jax.device_put(b[i * shard:(i + 1) * shard], d0) for i in range(4)]
    refs = {"asum": sum_reference(a.astype(np.float64)),
            "dot": sum_reference(a.astype(np.float64) * b)}

    am = rng.standard_normal((MM, MM), dtype=np.float32)
    bm = rng.standard_normal((MM, MM), dtype=np.float32)
    am_s, bm_s = placed(am, P(None, "data")), placed(bm, P("data", None))
    ks = MM // 4
    a64, b64 = am.astype(np.float64), bm.astype(np.float64)
    ref_mm = a64 @ b64

    for name in ("naive", "kahan"):
        sch = schemes.get(name)
        eng = CompensatedReduction(scheme=name)
        for op in ("asum", "dot"):
            if op == "asum":
                got = compiled_on_mesh(
                    functools.partial(col.sharded_asum, mesh, scheme=name),
                    a_s, what=f"{op}/{name}")
                accs = [eng.sum_accumulators(x) for x in a0]
            else:
                got = compiled_on_mesh(
                    functools.partial(col.sharded_dot, mesh, scheme=name),
                    a_s, b_s, what=f"{op}/{name}")
                accs = [eng.dot_accumulators(x, y) for x, y in zip(a0, b0)]
            want = merge_accumulators(jnp.stack([c.s for c in accs]),
                                      jnp.stack([c.c for c in accs]))
            ref, cond = refs[op]
            err = numerics.relative_error(float(got), ref)
            bound = sch.error_bound(N_VEC, cond)
            same = bits(got) == bits(want)
            log(f"sharded {op}/{name} n={N_VEC} bitwise_vs_one_chip={same} "
                f"rel_err={err:.3e} bound={bound:.3e}")
            check(same, f"sharded {op}/{name} differs from the one-chip "
                        f"merge of the same shards")
            check(err <= bound, f"sharded {op}/{name}: rel_err {err:.3e} > "
                                f"{bound:.3e}")

        got = compiled_on_mesh(
            functools.partial(col.sharded_matmul, mesh, scheme=name),
            am_s, bm_s, what=f"matmul/{name}")
        accs = [eng.matmul_accumulators(
                    jax.device_put(am[:, i * ks:(i + 1) * ks], d0),
                    jax.device_put(bm[i * ks:(i + 1) * ks], d0))
                for i in range(4)]
        want = merge_accumulator_grids(
            jnp.stack([c.s for c in accs]),
            jnp.stack([c.c for c in accs]))[:MM, :MM]
        block_k = min(eng.blocks[2], ks)
        err = np.abs(np.asarray(got, np.float64) - ref_mm)
        ratio = float(np.max(err / matmul_bound(sch, block_k, a64, b64,
                                                ref_mm)))
        same = bits(got) == bits(want)
        log(f"sharded matmul/{name} {MM}^3 bitwise_vs_one_chip={same} "
            f"max_err_over_bound={ratio:.3e}")
        check(same, f"sharded matmul/{name} differs from the one-chip "
                    f"merge of the same K-slices")
        check(ratio <= 1.0, f"sharded matmul/{name}: an element exceeds "
                            f"its bound ({ratio:.3e} x bound)")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of every input and of the random weights")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the sharded reductions on a "
                         "4-device mesh")
    args = ap.parse_args(argv)

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU (JAX found {dev.platform!r}); nothing "
              f"runs on another backend", file=sys.stderr)
        return 1
    from repro.launch.compile_cache import enable_compile_cache

    log(f"device platform={dev.platform} kind={dev.device_kind} "
        f"count={len(jax.devices())} jax={jax.__version__} "
        f"compile_cache={enable_compile_cache()}")
    phases = ([("sharded", sharded_phase)] if args.chips == 4 else
              [("kernel", kernel_phase), ("serving", serving_phase)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        log(f"phase {name} passed in {time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
