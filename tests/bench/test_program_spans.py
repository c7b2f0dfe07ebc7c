"""The program's spans on the device's clock: the offset interval, idle
time by span, and the readers of the decode tick, on hand-worked traces
and on traces recorded on a v5e."""

import json
import pathlib
import time
import types

import pytest

from bench import harness, program_spans, trace_reduce
from bench.program_spans import Span
from bench.trace_reduce import Event

DATA = pathlib.Path(__file__).parent / "data"
PEAK = harness.peak_row("TPU v5 lite")
MS = 1e6
D = 1.5                            # the device clock lags the host's (ms)
READERS = ("tick_host_ms", "tick_gap_ms")


def _dev(a, b):
    """A device interval given on the host's clock (ms)."""
    return (a - D) * MS, (b - D) * MS


def synthetic():
    """A 100 ms window of three steps on the host's clock; on the
    device's, every program runs ``D`` ms earlier than the host sees
    it. The first tick starts 0.3 ms after its dispatch span begins and
    ends 0.3 ms before its readback ends: the offset interval is
    [D - 0.3, D + 0.3]. The third step admits a request (one prefill
    chunk) before its tick."""
    ticks = [(12.3, 29.7), (48.5, 69.0), (90.5, 96.0)]
    prefill = (84.5, 85.5)
    modules = [Event("jit_tick(1)", *_dev(*ticks[0])),
               Event("jit_tick(1)", *_dev(*ticks[1])),
               Event("jit_prefill(2)", *_dev(*prefill)),
               Event("jit_tick(1)", *_dev(*ticks[2]))]
    ops = [Event("tick ops", e.start, e.end) for e in modules]
    ops += [Event("%reset", *_dev(31.0, 31.5))]
    spans = [("bench.window", 0, 100, {}),
             ("bench.step", 9, 41, {}), ("bench.step", 44, 81, {}),
             ("bench.step", 81.5, 99, {}),
             ("serve.step", 10, 40, {"step_num": 0}),
             ("serve.tick.inputs", 11, 12, {}),
             ("serve.tick.dispatch", 12, 13,
              {"live": 4, "queued": 1, "chunks": 0, "admitted": 0}),
             ("serve.tick.readback", 13, 30, {}),
             ("serve.record", 30, 32, {"finished": 1}),
             ("serve.step", 45, 80, {"step_num": 1}),
             ("serve.tick.inputs", 46, 47.5, {}),
             ("serve.tick.dispatch", 47.5, 48,
              {"live": 3, "queued": 1, "chunks": 0, "admitted": 0}),
             ("serve.tick.readback", 48, 70, {}),
             ("serve.record", 70, 71, {"finished": 0}),
             ("serve.step", 82, 98, {"step_num": 2}),
             ("serve.admit", 82, 82.5, {}),
             ("serve.prefill", 82.5, 88,
              {"request_id": 7, "width": 64, "offset": 0, "new_program": 0}),
             ("serve.prefill.dispatch", 83, 84, {}),
             ("serve.prefill.readback", 86, 88, {}),
             ("serve.tick.inputs", 88, 89, {}),
             ("serve.tick.dispatch", 89, 90,
              {"live": 4, "queued": 0, "chunks": 1, "admitted": 1}),
             ("serve.tick.readback", 90, 97, {}),
             ("serve.record", 97, 97.5, {"finished": 0})]
    spans = [Span(n, a * MS, b * MS, args) for n, a, b, args in spans]
    bench = [Event(s.name, s.start, s.end) for s in spans
             if s.name.startswith("bench.")]
    tr = trace_reduce.Trace((0.0, 100 * MS), [ops], [modules], bench)
    return program_spans.Programs(tr, sorted(spans, key=lambda s: s.start))


def fake_run(prog, monkeypatch, records=None):
    """A run whose trace directory reads as ``prog``."""
    monkeypatch.setattr(program_spans, "load", lambda d, tr: prog)
    return harness.Run(cell=types.SimpleNamespace(trace_dir="unused"),
                       outcome=types.SimpleNamespace(records=records or {}),
                       trace=prog.trace, peak=PEAK)


def test_known_offset_is_found():
    lo, hi = synthetic().offset()
    assert lo == pytest.approx((D - 0.3) * MS)
    assert hi == pytest.approx((D + 0.3) * MS)


def test_crossed_bounds_give_no_offset():
    prog = synthetic()
    late = Span("serve.tick.readback", 13 * MS, 29 * MS)   # ends too soon
    prog.spans = sorted([s for s in prog.spans
                         if (s.name, s.start) != ("serve.tick.readback",
                                                  13 * MS)] + [late],
                        key=lambda s: s.start)
    lo, hi = prog.offset_bounds()
    assert lo > hi and prog.offset() is None


def test_idle_is_split_over_the_innermost_span():
    """Worked by hand on the host's clock, with the device shifted by the
    interval's middle (``D``)."""
    idle = {k: v / MS for k, v in synthetic().idle_by_span().items()}
    want = {"other": 13.5, "bench.step": 5.5, "serve.step": 19.5,
            "serve.tick.inputs": 3.5, "serve.tick.dispatch": 1.8,
            "serve.tick.readback": 3.3, "serve.record": 3.0,
            "serve.admit": 0.5, "serve.prefill": 1.5,
            "serve.prefill.dispatch": 1.0, "serve.prefill.readback": 2.0}
    assert idle == pytest.approx(want)
    assert sum(idle.values()) == pytest.approx(
        100 - synthetic().trace.busy_s * 1e3)


def test_readers_on_a_synthetic_trace(monkeypatch):
    run = fake_run(synthetic(), monkeypatch)
    read = {n: harness.load_reader(n)(run) for n in READERS}
    # inputs + dispatch + record per step: (1 + 1 + 2), (1.5 + 0.5 + 1),
    # (1 + 1 + 0.5)
    assert read["tick_host_ms"] == pytest.approx(9.5 / 3)
    # only the second tick follows no chunk: 18.8 ms less the 0.5 ms
    # reset between the two ticks
    assert read["tick_gap_ms"] == pytest.approx(18.3)


def test_readers_find_nothing_without_program_spans(monkeypatch):
    """The parent's trace: ``bench.*`` spans only."""
    data = json.loads((DATA / "v5e_trace.json").read_text())["chat"]
    tr = trace_reduce.from_json(data)
    prog = program_spans.Programs(
        tr, [Span(n, float(a), float(b)) for n, a, b in data["spans"]])
    run = fake_run(prog, monkeypatch)
    for name in READERS:
        assert harness.load_reader(name)(run) is None, name


def test_recorded_dot_trace_offset():
    """A v5e dot trace: each ``jit_dot`` starts 1.02-1.20 ms before the
    ``bench.call`` that launched it on the raw clocks; the readbacks
    bound the offset from above, the last one (cut by the window)
    dropped."""
    data = json.loads((DATA / "v5e_trace.json").read_text())["dot"]
    prog = program_spans.Programs(
        trace_reduce.from_json(data),
        sorted((Span(n, float(a), float(b)) for n, a, b in data["spans"]),
               key=lambda s: s.start))
    lo, hi = prog.offset()
    assert lo / MS == pytest.approx(1.20, abs=0.01)
    assert hi / MS == pytest.approx(2.25, abs=0.01)


def test_a_traced_run_reads_its_spans_on_the_cpu(monkeypatch, capfd):
    """The whole path on the CPU at a small size: the harness traces a
    serving run, and the readers load the engine's spans from the
    ``.xplane.pb``. The CPU has no device plane, so only the host-clock
    reader finds something to read."""
    from test_bench_faults import serve_cell

    real = harness.peak_row
    monkeypatch.setattr(harness, "peak_row", lambda kind: real("TPU v5 lite"))
    cell = serve_cell()
    cell.trace, cell.t0 = True, time.perf_counter()
    spec = dict(harness.load_spec(), workloads=[{"name": cell.workload,
                                                 "chips": 1}])
    line = harness.run_cell(cell, spec, require_chip=False)
    assert line["metrics"]["tick_host_ms"]["value"] > 0
    assert "tick_gap_ms" not in line["metrics"]
    assert "program_spans: offset_ms=none" in capfd.readouterr().err


@pytest.fixture(scope="module")
def recorded_spans():
    data = json.loads((DATA / "v5e_trace_spans.json").read_text())["chat"]
    return program_spans.from_json(data)


def test_recorded_serving_trace_pairs_spans_with_programs(recorded_spans):
    """A v5e trace of the engine: one ``jit_tick`` per dispatch span and
    one ``jit_prefill`` per chunk, every tick of 16 live slots, the
    admission's 9 chunks counted on its tick's dispatch span."""
    prog = recorded_spans
    ticks = prog.launched("serve.tick.dispatch", "tick")
    assert len(ticks) == len(prog.programs("tick")) == 7
    chunks = prog.launched("serve.prefill.dispatch", "prefill")
    assert len(chunks) == len(prog.programs("prefill")) == 9
    assert all(s.args["live"] == 16 for s, _ in ticks)
    assert [s.args["chunks"] for s, _ in ticks] == [0, 9, 0, 0, 0, 0, 0]
    assert sum(s.name == "serve.prefill" for s in prog.spans) == 9
    # each program starts after its launch span and ends before the
    # host reads it back, on the aligned clock
    lo, hi = prog.offset()
    assert 0 < lo < hi < 5 * MS
    for span, run in ticks:
        assert run.start + hi >= span.start


def test_recorded_serving_trace_readers(recorded_spans, monkeypatch):
    """The readers on the recorded trace, against the spans and programs
    read directly."""
    prog = recorded_spans
    run = fake_run(prog, monkeypatch)
    host = harness.load_reader("tick_host_ms")(run)
    gap = harness.load_reader("tick_gap_ms")(run)
    inputs = [s.dur for s in prog.spans if s.name == "serve.tick.inputs"]
    assert min(inputs) * 1e-6 < host < 4.0
    ticks = [p for _, p in prog.tick_pairs()]
    raw = [(b.start - a.end) * 1e-6 for a, b in zip(ticks, ticks[1:])]
    assert 3.5 < gap <= max(raw)
    # the decode tick reads as it did before the spans were added
    assert harness.load_reader("decode_tick_ms")(run) == pytest.approx(
        128.46, abs=0.05)
    idle = prog.idle_by_span()
    # the split keeps every idle ns of the aligned window
    lo, hi = prog.offset()
    assert sum(idle.values()) == pytest.approx(
        sum(b - a for a, b in prog.idle((lo + hi) / 2)))
    top = sorted(idle, key=idle.get, reverse=True)[:2]
    assert set(top) == {"serve.tick.inputs", "serve.tick.readback"}
