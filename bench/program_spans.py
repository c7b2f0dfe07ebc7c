"""The program's own host spans, on the device's clock.

The serving engine leaves ``serve.*`` spans in the profiler's trace, with
its counters as span arguments (``repro.serve.engine``: ``serve.step``,
``serve.tick.dispatch`` with ``live``, ``queued``, ``chunks`` and
``admitted``, and so on). ``of(run)`` loads them, with the benchmark's
own ``bench.*`` spans and their arguments, once per trace directory, and
pairs them with the device's programs:

* a launch span and the program it launches, in order
  (``serve.tick.dispatch`` -> ``jit_tick``, ``serve.prefill.dispatch``
  -> ``jit_prefill``, ``bench.call`` -> ``jit_dot``);
* a sync span and the program it waits for (``serve.tick.readback``,
  ``serve.prefill.readback``, ``bench.readback``): the one launched by
  the latest launch span of its kind before it.

The host and device planes of one trace do not share a clock: device
events can appear before the host span that caused them. ``offset()``
bounds the offset to add to a device time to put it on the host clock.
No program starts before its launch span begins (a lower bound); no sync
span ends before its program ends (an upper bound). It is ``None`` when
the bounds cross. ``idle_by_span()`` puts each device-idle interval on
the host clock and splits it over the innermost span at each instant.

Readers: ``bench/metrics/tick_host_ms.py`` and ``tick_gap_ms.py``. A
trace without ``serve.*`` spans (a program that has none) gives ``None``
to each of them.
"""

from __future__ import annotations

import bisect
import dataclasses
import re
import sys
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bench.trace_reduce import (WINDOW, Event, Trace, clip, merged,
                                 xplane_file)

#: (launch span, program prefix, sync span)
PAIRS = (("serve.tick.dispatch", "tick", "serve.tick.readback"),
         ("serve.prefill.dispatch", "prefill", "serve.prefill.readback"),
         ("bench.call", "dot", "bench.readback"))


@dataclasses.dataclass(frozen=True)
class Span:
    """A host span (ns on the host's clock) and its arguments."""

    name: str
    start: float
    end: float
    args: Dict[str, Any] = dataclasses.field(default_factory=dict)

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Programs:
    """A traced run's host spans beside its device trace."""

    trace: Trace
    spans: List[Span]                       # serve.* and bench.*, by start

    @property
    def window(self) -> Tuple[float, float]:
        return self.trace.window

    def has_program_spans(self) -> bool:
        return any(s.name.startswith("serve.") for s in self.spans)

    def whole(self, span: Span) -> bool:
        """Whether the span lies inside the window, cut by neither end."""
        return self.window[0] < span.start and span.end < self.window[1]

    def inside(self, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name and self.whole(s)]

    def programs(self, prefix: str) -> List[Event]:
        """Every run of ``jit_<prefix>`` on the first device, by start,
        not clipped: a program launched inside the window may start
        before it on the device's clock."""
        pat = re.compile(rf"jit_{re.escape(prefix)}\b")
        mods = self.trace.modules[0] if self.trace.modules else []
        return [e for e in mods if pat.match(e.name)]

    def launched(self, launch: str, prefix: str) -> List[Tuple[Span, Event]]:
        """(launch span, the program it launched), paired in order: each
        launch span starts one run, and one device runs them in turn."""
        spans = [s for s in self.spans if s.name == launch]
        return list(zip(spans, self.programs(prefix)))

    # -- the clocks -------------------------------------------------------
    def offset_bounds(self) -> Optional[Tuple[float, float]]:
        """(lower, upper) bounds of the ns to add to a device time to put
        it on the host's clock, from the pairs whose spans the window
        holds whole; ``None`` where no pair bounds a side. The bounds may
        cross."""
        lows, highs = [], []
        for launch, prefix, sync in PAIRS:
            pairs = self.launched(launch, prefix)
            lows += [span.start - prog.start for span, prog in pairs
                     if self.whole(span)]
            starts = [span.start for span, _ in pairs]
            for s in self.inside(sync):
                k = bisect.bisect_right(starts, s.start) - 1
                if k >= 0:
                    highs.append(s.end - pairs[k][1].end)
        if not lows or not highs:
            return None
        return max(lows), min(highs)

    def offset(self) -> Optional[Tuple[float, float]]:
        """The offset interval (ns), or ``None`` where it is unbounded or
        its bounds cross."""
        b = self.offset_bounds()
        return b if b is not None and b[0] <= b[1] else None

    # -- what the device waited on ---------------------------------------
    def idle_by_span(self) -> Dict[str, float]:
        """ns of device-idle time in the window, by the innermost host
        span (``serve.*``, or ``bench.*`` other than the window) at each
        instant on the aligned clock (the offset interval's middle; the
        raw clocks where there is none); ``other`` where no span is
        open."""
        b = self.offset()
        shift = (b[0] + b[1]) / 2 if b is not None else 0.0
        spans = [s for s in self.spans if s.name != WINDOW]
        starts = [s.start for s in spans]
        longest = max((s.dur for s in spans), default=0.0)
        out: Dict[str, float] = {}
        for lo, hi in self.idle(shift):
            near = spans[bisect.bisect_left(starts, lo - longest):
                         bisect.bisect_left(starts, hi)]
            for name, ns in split(near, lo, hi):
                out[name] = out.get(name, 0.0) + ns
        return out

    def idle(self, shift: float) -> List[Tuple[float, float]]:
        """The first device's idle intervals in the window, on the host's
        clock for an offset of ``shift`` ns."""
        lo, hi = self.window
        ops = self.trace.ops[0] if self.trace.ops else []
        busy = merged(clip(ops, lo - shift, hi - shift))
        edges = [lo] + [t + shift for e in busy for t in (e.start, e.end)] \
            + [hi]
        return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]

    # -- the decode tick --------------------------------------------------
    def in_step(self, step: Span, name: str) -> List[Span]:
        return [s for s in self.spans if s.name == name
                and step.start <= s.start and s.end <= step.end]

    def tick_pairs(self) -> List[Tuple[Span, Event]]:
        """(``serve.tick.dispatch``, its ``jit_tick`` run) of each tick
        whose dispatch span the window holds whole."""
        return [(s, p) for s, p in self.launched("serve.tick.dispatch", "tick")
                if self.whole(s)]


def split(spans: Sequence[Span], lo: float, hi: float,
          ) -> List[Tuple[str, float]]:
    """[lo, hi) cut where a span begins or ends; each piece named after
    the shortest span that holds it (``other`` where none does)."""
    near = [s for s in spans if s.start < hi and s.end > lo]
    edges = sorted({lo, hi} | {t for s in near for t in (s.start, s.end)
                               if lo < t < hi})
    out = []
    for a, b in zip(edges, edges[1:]):
        mid = (a + b) / 2
        holding = [s for s in near if s.start <= mid < s.end]
        name = min(holding, key=lambda s: s.dur).name if holding else "other"
        out.append((name, b - a))
    return out


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def from_planes(trace: Trace, planes) -> Programs:
    """The host spans (``serve.*``, ``bench.*``) with their arguments,
    from ``jax.profiler.ProfileData`` planes."""
    spans = []
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(("serve.", "bench.")):
                    start = float(e.start_ns)
                    # stats named "_..." are the profiler's own
                    args = {k: v for k, v in e.stats if not k.startswith("_")}
                    spans.append(Span(e.name, start,
                                      start + float(e.duration_ns), args))
    return Programs(trace, sorted(spans, key=lambda s: s.start))


#: the latest traced run's program spans, by its trace directory
_LOADED: Dict[str, Programs] = {}


def load(trace_dir: str, trace: Trace) -> Programs:
    """The program spans of the trace in ``trace_dir`` (read once; the
    first read prints ``report``'s line)."""
    if trace_dir not in _LOADED:
        from jax.profiler import ProfileData

        data = ProfileData.from_file(xplane_file(trace_dir))
        _LOADED.clear()
        _LOADED[trace_dir] = from_planes(trace, data.planes)
        report(_LOADED[trace_dir])
    return _LOADED[trace_dir]


def of(run) -> Optional[Programs]:
    """The run's program spans, or ``None`` where the trace holds no
    ``serve.*`` span."""
    prog = load(run.cell.trace_dir, run.trace)
    return prog if prog.has_program_spans() else None


def report(prog: Programs) -> None:
    """One line on stderr: the offset interval and the idle ms by span."""
    b = prog.offset_bounds()
    where = ("none" if b is None else
             f"[{b[0] * 1e-6:.3f}, {b[1] * 1e-6:.3f}]"
             + ("" if b[0] <= b[1] else " (bounds cross)"))
    idle = sorted(prog.idle_by_span().items(), key=lambda kv: -kv[1])
    print("program_spans: offset_ms=" + where + " idle_ms="
          + ",".join(f"{k}:{v * 1e-6:.3f}" for k, v in idle),
          file=sys.stderr, flush=True)


def from_json(data: Dict) -> Programs:
    """A recorded trace kept as plain data: ``trace_reduce.from_json``'s
    keys, with spans as ``[name, start, end, {args}]``."""
    from bench import trace_reduce

    tr = trace_reduce.from_json({**data, "spans": [
        s[:3] for s in data["spans"] if s[0].startswith("bench.")]})
    return Programs(tr, sorted((Span(n, float(a), float(b), dict(args))
                                for n, a, b, args in data["spans"]),
                               key=lambda s: s.start))
