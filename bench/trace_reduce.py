"""From a profiler trace to the numbers the per-layer metrics read.

``load(dir)`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and
keeps, for the part of the run the benchmark traced (its host span
``bench.window``):

* per device plane (``/device:TPU:<i>``): the operations (line
  ``XLA Ops``) and the programs (line ``XLA Modules``), each an
  ``Event(name, start, end)`` in nanoseconds on the trace's clock;
* the benchmark's own host spans (``bench.*``).

Busy time is the union of a device's operation intervals inside the
window, averaged over the devices. A program's device time is the sum of
its ``XLA Modules`` events, which the trace names after the jitted
function (``jit_tick(…)``, ``jit_prefill(…)``, ``jit_dot(…)``).
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

WINDOW = "bench.window"
OPS, MODULES = "XLA Ops", "XLA Modules"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float                  # ns
    end: float                    # ns

    @property
    def dur(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class Trace:
    window: Tuple[float, float]
    ops: List[List[Event]]        # per device, sorted by start
    modules: List[List[Event]]    # per device, sorted by start
    spans: List[Event]            # the benchmark's host spans, by start

    # -- device time ------------------------------------------------------
    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(union(clip(d, *self.window)) for d in self.ops) \
            / len(self.ops) * 1e-9

    def program(self, prefix: str) -> List[Event]:
        """Module events of the programs whose name starts with
        ``jit_<prefix>`` (the first device's, inside the window)."""
        pat = re.compile(rf"jit_{re.escape(prefix)}\b")
        return [e for e in clip(self.modules[0] if self.modules else [],
                                *self.window) if pat.match(e.name)]

    def ops_matching(self, pattern: str) -> List[Event]:
        """Operation events of the first device whose name matches."""
        pat = re.compile(pattern)
        return [e for e in clip(self.ops[0] if self.ops else [], *self.window)
                if pat.search(e.name)]

    def host(self, name: str) -> List[Event]:
        return [s for s in self.spans if s.name == name]

    # -- what the idle time is spent on ------------------------------------
    def gaps(self) -> List[Event]:
        """Idle intervals of the first device inside the window."""
        busy = merged(clip(self.ops[0] if self.ops else [], *self.window))
        edges = [self.window[0]] + [x for e in busy for x in (e.start, e.end)] \
            + [self.window[1]]
        return [Event("idle", a, b) for a, b in zip(edges[::2], edges[1::2])
                if b > a]

    def label(self, t: float) -> str:
        inner = [s for s in self.spans
                 if s.start <= t < s.end and s.name != WINDOW]
        return min(inner, key=lambda s: s.dur).name if inner else "other"

    def breakdown(self, top: int = 10) -> Dict[str, List[List]]:
        ops: Dict[str, float] = {}
        for e in clip(self.ops[0] if self.ops else [], *self.window):
            name = short(e.name)
            ops[name] = ops.get(name, 0.0) + e.dur * 1e-9
        gaps = sorted(self.gaps(), key=lambda e: -e.dur)[:top]
        gaps = [Event(self.label((g.start + g.end) / 2), g.start, g.end)
                for g in gaps]
        return {"device_ops": [[k, v] for k, v in
                               sorted(ops.items(), key=lambda kv: -kv[1])[:top]],
                "idle_gaps": [[g.name, g.dur * 1e-9] for g in gaps]}


def short(op: str) -> str:
    """An operation event's name is its HLO instruction's text; keep the
    instruction's name and opcode (``%while.67 while``)."""
    m = re.match(r"(%\S+) = ", op)
    if not m:
        return op[:80]
    kind = re.search(r"[\]})] ([a-z][a-z0-9-]*)\(", op[m.end():])
    return f"{m.group(1)} {kind.group(1)}" if kind else m.group(1)


# ---------------------------------------------------------------------------
# Interval arithmetic
# ---------------------------------------------------------------------------

def clip(events: Iterable[Event], lo: float, hi: float) -> List[Event]:
    out = []
    for e in events:
        a, b = max(e.start, lo), min(e.end, hi)
        if b > a or (b == a and lo <= e.start < hi):
            out.append(Event(e.name, a, b))
    return out


def merged(events: Sequence[Event]) -> List[Event]:
    """The union of the intervals, as disjoint sorted intervals."""
    out: List[Event] = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1].end:
            if e.end > out[-1].end:
                out[-1] = Event("busy", out[-1].start, e.end)
        else:
            out.append(Event("busy", e.start, e.end))
    return out


def union(events: Sequence[Event]) -> float:
    return sum(e.dur for e in merged(events))


def covered(events: Sequence[Event], lo: float, hi: float) -> float:
    """How much of [lo, hi) the union of ``events`` covers (ns)."""
    return union(clip(events, lo, hi))


# ---------------------------------------------------------------------------
# Loading
# ---------------------------------------------------------------------------

def from_planes(planes) -> Trace:
    """``planes``: objects with ``.name`` and ``.lines``, lines with
    ``.name`` and ``.events``, events with ``.name``, ``.start_ns`` and
    ``.duration_ns`` (``jax.profiler.ProfileData`` or the test data)."""
    ops, modules, spans = [], [], []
    for plane in planes:
        if plane.name.startswith("/device:"):
            lines = {ln.name: ln for ln in plane.lines}
            if OPS not in lines and MODULES not in lines:
                continue
            ops.append(_events(lines.get(OPS)))
            modules.append(_events(lines.get(MODULES)))
        elif plane.name.startswith("/host:"):
            for ln in plane.lines:
                spans += [e for e in _events(ln) if e.name.startswith("bench.")]
    spans.sort(key=lambda e: e.start)
    win = [s for s in spans if s.name == WINDOW]
    if not win:
        raise ValueError(f"the trace has no {WINDOW!r} span")
    window = (win[0].start, win[0].end)
    return Trace(window, ops, modules, spans)


def _events(line) -> List[Event]:
    if line is None:
        return []
    return sorted((Event(e.name, float(e.start_ns),
                         float(e.start_ns) + float(e.duration_ns))
                   for e in line.events), key=lambda e: e.start)


def xplane_file(trace_dir: str) -> str:
    found = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(found, key=os.path.getmtime)


def load(trace_dir: str) -> Trace:
    from jax.profiler import ProfileData

    return from_planes(ProfileData.from_file(xplane_file(trace_dir)).planes)


def from_json(data: Dict) -> Trace:
    """A trace kept as plain data (``{"window": [a, b], "ops": [[[name,
    start, end], ...] per device], "modules": ..., "spans": [...]}``), as
    the tests keep a recorded one."""
    ev = lambda es: [Event(n, float(a), float(b)) for n, a, b in es]  # noqa: E731
    return Trace(tuple(data["window"]), [ev(d) for d in data["ops"]],
                 [ev(d) for d in data["modules"]], ev(data["spans"]))


def first_device_busy_in(trace: Trace, spans: Sequence[Event]) -> float:
    """ns of device busy time inside the given host spans."""
    ops = trace.ops[0] if trace.ops else []
    return sum(covered(ops, s.start, s.end) for s in spans)
