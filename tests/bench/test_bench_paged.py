"""``olmo-1b-paged`` shrunk to a small size on the CPU: served through
``serve.build`` from the paged pool with the prefix cache and flash
prefill, on requests that share a system prompt, its logits and
telemetry agree with the plain reference, and requests that the check
compares resumed from the cache."""

import json
import pathlib

import numpy as np

from bench import generator
from bench.kinds import serve
from test_bench_reference import SMALL

ROOT = pathlib.Path(__file__).resolve().parents[2]


def small_conf():
    conf = json.loads((ROOT / "bench/configs/olmo-1b-paged.json").read_text())
    conf["model"].update(SMALL, dtype="float32")
    conf["arch_overrides"] = dict(conf["arch_overrides"], **SMALL,
                                  param_dtype="float32",
                                  compute_dtype="float32")
    conf["engine"].update(max_slots=2, max_len=256, num_pages=32)
    return conf


def small_sessions():
    t = json.loads((ROOT / "bench/traffic/sessions.json").read_text())
    t.update(rate_per_s=4.0, backlog=0, ramp_s=0.0)
    t["system"].update(min=64, max=100)
    t["own"].update(median=20, min=4, max=40)
    # at least 3 tokens: a lease outlives the step that admits it
    t["output"].update(median=6, min=3, max=12)
    return t


def test_paged_prefix_serving_agrees_with_the_reference():
    from repro.serve import Request, SamplingParams

    conf = small_conf()
    engine, w = serve.build(conf, 2**33 + 41)
    assert engine.kv_layout == "paged" and engine.prefill_body == "flash"
    reqs = generator.schedule(small_sessions(), 2**33 + 43, 2.0, 512)[:8]
    handles, resumed = [], {}
    for k, r in enumerate(reqs):
        # one request at a time: each finishes, and donates its pages,
        # before the next arrives
        handles.append(engine.submit(Request(
            prompt=r.prompt, request_id=k,
            sampling=SamplingParams(max_new_tokens=r.max_new_tokens))))
        while engine.scheduler.busy:
            engine.step()
            resumed.update({rid: lease.resume
                            for rid, lease in engine._leases.items()})
    assert all(h.done for h in handles)
    assert sum(resumed.values()) == engine.prefix_hit_tokens
    # the four system prompts of 64-100 tokens: a request whose prompt
    # was seen before resumes at a multiple of the 64-token chunk
    hits = [rid for rid, n in resumed.items() if n > 0]
    assert hits and all(resumed[rid] % 64 == 0 for rid in hits)
    found = serve.compare(w, handles, conf, 16)["program"]
    assert found["max_logit_gap"] <= 1e-5
    assert found["max_telemetry_rel_err"] <= 1e-5
    # the float32 program holds the limits of the bf16 configuration
    assert all(found[k] <= lim for k, lim in conf["limits"].items())
    assert np.isfinite([t for h in handles for t in h.telemetry]).all()
