"""The reduction from a profiler trace to per-layer metrics: hand-worked
intervals, and a small trace recorded on a v5e."""

import json
import pathlib
import types

import pytest

from bench import harness, trace_reduce
from bench.trace_reduce import Event

ROOT = pathlib.Path(__file__).resolve().parents[2]
DATA = pathlib.Path(__file__).parent / "data"
PEAK = harness.peak_row("TPU v5 lite")


def synthetic():
    """A 100 ns window: two steps (10-40, 50-90); device ops 12-20,
    18-30 (overlapping) and 60-80; programs named as jit names them."""
    ms = 1e6
    ops = [Event("fusion.1", 12 * ms, 20 * ms), Event("fusion.2", 18 * ms, 30 * ms),
           Event("custom-call.3", 60 * ms, 80 * ms)]
    modules = [Event("jit_tick(1)", 12 * ms, 30 * ms),
               Event("jit_prefill(2)", 60 * ms, 80 * ms)]
    spans = [Event("bench.window", 0, 100 * ms),
             Event("bench.step", 10 * ms, 40 * ms),
             Event("bench.idle", 40 * ms, 50 * ms),
             Event("bench.step", 50 * ms, 90 * ms)]
    return trace_reduce.Trace((0.0, 100 * ms), [ops], [modules], spans)


def fake_run(trace, records):
    return harness.Run(cell=None, outcome=types.SimpleNamespace(records=records),
                       trace=trace, peak=PEAK)


def test_busy_union_and_idle_share():
    tr = synthetic()
    assert tr.window_s == pytest.approx(0.1)
    assert tr.busy_s == pytest.approx(0.038)       # 12-30 and 60-80
    run = fake_run(tr, {})
    assert harness.load_reader("device_idle.serve")(run) == pytest.approx(62.0)


def test_per_program_device_time():
    run = fake_run(synthetic(), {})
    assert harness.load_reader("decode_tick_ms")(run) == pytest.approx(18.0)
    assert [e.dur for e in run.trace.program("prefill")] == [20e6]


def test_host_span_attribution():
    tr = synthetic()
    # step 1: 30 ms with 18 busy; step 2: 40 ms with 20 busy
    run = fake_run(tr, {})
    assert harness.load_reader("step_host_ms")(run) == pytest.approx(16.0)
    # idle 30-60 (midpoint 45 in the idle span), 80-100 and 0-12 (no
    # benchmark span at their midpoints), longest first
    assert tr.breakdown()["idle_gaps"] == [
        ["bench.idle", pytest.approx(0.03)], ["other", pytest.approx(0.02)],
        ["other", pytest.approx(0.012)]]
    assert tr.breakdown()["device_ops"][0] == ["custom-call.3",
                                               pytest.approx(0.02)]


def test_mfu_counts_the_windows_tokens():
    """Every step inside the window, over the window's host seconds, not
    the traced part."""
    m = json.loads((ROOT / "bench/configs/olmo-1b.json").read_text())["model"]
    steps = [{"prefill": [(0, 64, 64)], "decode": [100, 200]},
             {"prefill": [], "decode": [101, 201]}]
    run = fake_run(synthetic(), {"traced_steps": steps[:1],
                                 "window_steps": steps, "window_s": 2.0,
                                 "model": m})
    from bench import counts

    flops = counts.chunk_flops(m, 0, 64) + sum(
        counts.token_flops(m, p) for p in (100, 200, 101, 201))
    want = 100 * flops / 2.0 / PEAK["bf16_flops_per_s"]
    assert harness.load_reader("serve_mfu.chat")(run) == pytest.approx(want)


def test_readers_find_nothing_in_an_empty_trace():
    tr = trace_reduce.Trace((0.0, 1e9), [[]], [[]],
                            [Event("bench.window", 0, 1e9)])
    run = fake_run(tr, {"traced_steps": [], "window_steps": [],
                        "window_s": 1.0, "model": {}, "n": 1})
    for name in ("decode_tick_ms", "step_host_ms", "dot_roofline",
                 "serve_mfu.chat"):
        assert harness.load_reader(name)(run) is None, name


def test_clip_and_merge():
    es = [Event("a", 0, 10), Event("b", 5, 15), Event("c", 20, 30)]
    assert trace_reduce.union(es) == 25
    assert trace_reduce.covered(es, 8, 22) == 9
    assert [(e.start, e.end) for e in trace_reduce.merged(es)] == [(0, 15), (20, 30)]


@pytest.fixture(scope="module")
def recorded():
    return json.loads((DATA / "v5e_trace.json").read_text())


def test_recorded_dot_trace(recorded):
    tr = trace_reduce.from_json(recorded["dot"])
    calls = tr.ops_matching(r"^%dot_accumulators\b")
    assert len(calls) == 8 and len(tr.program("dot")) == 8
    assert 0 < tr.busy_s <= tr.window_s == pytest.approx(0.04)
    # the busy union never exceeds the summed operation time
    assert tr.busy_s <= sum(e.dur for e in tr.ops[0]) * 1e-9
    run = fake_run(tr, {"n": 1 << 27})
    share = harness.load_reader("dot_roofline")(run)
    want = 100 * 8 * 2 * 4 * (1 << 27) / PEAK["hbm_bytes_per_s"] \
        / (sum(e.dur for e in calls) * 1e-9)
    assert share == pytest.approx(want) and 0 < share < 100
    idle = harness.load_reader("device_idle.dot")(run)
    assert idle == pytest.approx(100 * (1 - tr.busy_s / tr.window_s))
    # the idle gaps fall in the benchmark's own spans around each call
    assert {g[0] for g in tr.breakdown()["idle_gaps"]} <= {
        "bench.call", "bench.readback", "other"}


def test_recorded_serving_programs(recorded):
    tr = trace_reduce.from_json(recorded["chat"])
    ticks, chunks = tr.program("tick"), tr.program("prefill")
    assert len(ticks) == 13 and len(chunks) == 12
    run = fake_run(tr, {})
    assert harness.load_reader("decode_tick_ms")(run) == pytest.approx(
        sum(e.dur for e in ticks) / 13 * 1e-6)
    # the tick program is not mistaken for the slot reset or a conversion
    assert not any(e.name.startswith("jit__reset") for e in ticks)
    assert len(tr.host("bench.step")) == 13
