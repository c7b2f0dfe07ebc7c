"""Scheduler host time per step: each ``step()`` span of the benchmark,
less the device's busy time inside it, averaged over the traced steps."""

from bench.trace_reduce import first_device_busy_in


def read(run):
    steps = run.trace.host("bench.step")
    if not steps:
        return None
    host = sum(s.dur for s in steps) - first_device_busy_in(run.trace, steps)
    return host / len(steps) * 1e-6
