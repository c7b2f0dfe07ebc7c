"""The plain references: olmo against the engine's own logits and served
tokens on a small model on the CPU, and the exact dot product."""

import json
import math
import pathlib

import jax.numpy as jnp
import numpy as np
import pytest

from bench.kinds import serve
from bench.reference import dot as dot_ref
from bench.reference import olmo

ROOT = pathlib.Path(__file__).resolve().parents[2]
SMALL = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 4,
         "d_ff": 128, "vocab_size": 512}


def small_conf(dtype="float32", max_len=128):
    conf = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())
    conf["model"].update(SMALL, dtype=dtype)
    conf["arch_overrides"] = dict(conf["arch_overrides"], **SMALL,
                                  param_dtype=dtype, compute_dtype=dtype)
    conf["engine"].update(max_slots=2, max_len=max_len)
    return conf


@pytest.fixture(scope="module")
def built():
    conf = small_conf()
    engine, w = serve.build(conf, 2**33 + 3)
    return conf, engine, w


def test_reference_matches_the_models_prefill_logits(built):
    conf, engine, w = built
    toks = np.random.default_rng(0).integers(0, 512, 40).astype(np.int32)
    cache, _ = engine.model.init_cache(1, 128)
    got, _ = engine.model.prefill(engine.params, {"tokens": jnp.asarray(toks[None])},
                                  cache)
    padded = np.zeros(128, np.int32)
    padded[:40] = toks
    want = olmo.logits_at(w, jnp.asarray(padded), jnp.asarray([39, 0]),
                          vocab=512)
    got = np.asarray(got, np.float64)[0, :512]
    want = np.asarray(want, np.float64)
    np.testing.assert_allclose(got, want[0], atol=1e-5 * np.abs(want[0]).max())


def test_served_tokens_agree_with_the_reference(built):
    """Flash chunk prefill, the cache, the decode tick, greedy sampling
    and the telemetry, through ``submit``/``step``, in float32."""
    from repro.serve import Request, SamplingParams

    conf, engine, w = built
    rng = np.random.default_rng(1)
    hs = [engine.submit(Request(
        prompt=rng.integers(0, 512, n).astype(np.int32),
        sampling=SamplingParams(max_new_tokens=k))) for n, k in ((70, 9), (5, 4))]
    while engine.scheduler.busy:
        engine.step()
    found = serve.compare(w, hs, conf, 16, control=True)
    program, control = found["program"], found["control"]
    assert program["max_logit_gap"] <= 1e-5
    assert program["max_telemetry_rel_err"] <= 1e-5
    # the float8 control departs from the reference
    assert control["max_telemetry_rel_err"] > 100 * max(
        program["max_telemetry_rel_err"], 1e-7)


def test_weights_are_made_from_the_seed():
    m = dict(SMALL, dtype="bfloat16")
    a = olmo.make_weights(m, serve.weight_key(2**40 + 1), 2048)
    b = olmo.make_weights(m, serve.weight_key(2**40 + 1), 2048)
    c = olmo.make_weights(m, serve.weight_key(2**40 + 2), 2048)
    assert all(np.array_equal(a[k], b[k]) for k in a)
    assert not np.array_equal(a["wq"], c["wq"])
    assert a["embed"].dtype == jnp.bfloat16


def test_exact_dot():
    rng = np.random.default_rng(5)
    a = rng.standard_normal(3 * dot_ref.CHUNK + 17).astype(np.float32)
    b = rng.standard_normal(a.shape[0]).astype(np.float32)
    exact, scale = dot_ref.exact_dot(a, b)
    terms = a.astype(np.float64) * b          # exact: 24 + 24 bits < 53
    # only the float64 chunk sums round: far below a float32 kernel's error
    assert abs(exact - math.fsum(terms)) <= 1e-14 * scale
    assert scale == pytest.approx(float(np.abs(terms).sum()), rel=1e-14)
