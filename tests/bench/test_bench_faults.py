"""A run with the timed path broken underneath comes out not correct, and
the controls fail the limits they set: the harness drives a whole run on
the CPU at a small size, past its look for a chip."""

import json
import pathlib
import time

import pytest

from bench import harness
from bench.kinds import dot, serve

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"
SPEC = harness.load_spec(ROOT)
SMALL = {"n_layers": 2, "d_model": 256, "n_heads": 4, "n_kv_heads": 4,
         "d_ff": 512, "vocab_size": 4096}


def make(config, traffic, seed, seconds):
    """A cell from its files, with the limits the chip runs are held to."""
    return harness.Cell(workload=f"{config}.{traffic}",
                        config=harness.load_json("configs", config),
                        traffic=harness.load_json("traffic", traffic),
                        seed=seed, seconds=seconds, trace=False, chips=1,
                        t0=time.perf_counter())


def dot_cell(scheme="kahan", seed=2**33 + 11, n=1 << 16):
    """The dot cell at ``n``, its limit scaled from the configuration's
    size: the error a float32 answer cannot avoid, its own rounding, is a
    share of sum |a_i b_i| that falls as 1 / sqrt(n)."""
    cell = make("dot-2e27", scheme, seed, 0.5)
    scale = (cell.config["n"] / n) ** 0.5
    cell.config["n"] = n
    cell.traffic["limits"] = {k: v * scale
                              for k, v in cell.traffic["limits"].items()}
    return cell


def serve_cell(seed=2**33 + 12):
    cell = make("olmo-1b", "chat", seed, 1.5)
    c, t = cell.config, cell.traffic
    c["model"].update(SMALL)
    c["arch_overrides"].update(SMALL)
    c["engine"].update(max_slots=4, max_len=256)
    c["check_tokens"] = 40
    t.update(rate_per_s=6.0, backlog=4, ramp_s=0.2)
    t["prompt"].update(median=60, min=8, max=160)
    t["output"].update(median=8, min=2, max=40)
    return cell


def run(cell):
    spec = dict(SPEC, workloads=[{"name": cell.workload, "chips": 1}])
    return harness.run_cell(cell, spec, require_chip=False)


def test_dot_runs_correct():
    line = run(dot_cell())
    assert line["correct"] and line["attempted"] > 0
    assert list(line)[-1] == "checks"


def test_dot_answer_altered_is_not_correct(monkeypatch):
    real = dot.dot_call

    def altered(scheme, compute_dtype=None):
        fn = real(scheme, compute_dtype)
        return lambda a, b: fn(a, b) * (1 + 2**-16)

    monkeypatch.setattr(dot, "dot_call", altered)
    assert not run(dot_cell())["correct"]


@pytest.mark.parametrize("scheme", ["kahan", "naive"])
def test_dot_control_fails_its_limit(scheme):
    """The program's bfloat16-accumulate path in place of float32."""
    cell = dot_cell(scheme)
    cell.control = True
    line = run(cell)
    assert not line["correct"]
    assert line["checks"]["max_rel_err"]["value"] > \
        line["checks"]["max_rel_err"]["limit"]


@pytest.mark.parametrize("scheme", ["kahan", "naive"])
def test_dot_limits_sit_between_chip_readings(scheme):
    """Each limit lies above every reading of the program on the chip and
    below every reading of its control there. The kahan limit also lies
    below every reading of the uncompensated loop on the same seeds, so
    that a kahan cell whose compensation was taken out is caught."""
    readings = json.loads((DATA / "dot_readings.json").read_text())
    limit = harness.load_json("traffic", scheme)["limits"]["max_rel_err"]
    program = readings[scheme].values()
    control = readings[f"{scheme}_bf16_control"].values()
    assert len(program) >= 12 and len(control) >= 12
    assert max(program) < limit < min(control)
    if scheme == "kahan":
        naive = [readings["naive"][seed] for seed in readings["kahan"]]
        assert limit < min(naive)


def _broken_tick(monkeypatch, how):
    real = serve.build

    def build(conf, seed):
        engine, w = real(conf, seed)
        fns = engine._fns
        tick = fns.tick

        def broken(params, cache, *args):
            new_cache, toks, norms = tick(params, cache, *args)
            if how == "state":
                return cache, toks, norms
            return new_cache, (toks + 1) % conf["model"]["vocab_size"], norms

        patched = type(fns)(broken, fns._factory, fns.prefill_body)
        patched._prefill = fns._prefill
        engine._fns = patched
        return engine, w

    monkeypatch.setattr(serve, "build", build)


def test_serving_runs_correct():
    line = run(serve_cell())
    assert line["correct"] and line["attempted"] > 0
    assert list(line)[-1] == "checks"


@pytest.mark.parametrize("how", ["state", "token"])
def test_serving_fault_is_not_correct(monkeypatch, how):
    """A tick that returns its cache unchanged, or alters the token it
    produces."""
    _broken_tick(monkeypatch, how)
    assert not run(serve_cell())["correct"]


def test_serving_control_is_not_correct():
    """The reference's float8 forward in the program's place, held to the
    configuration's limits through the harness's own comparison."""
    cell = serve_cell()
    cell.control = True
    line = run(cell)
    assert not line["correct"]
    assert any(c["value"] > c["limit"] for c in line["checks"].values())
