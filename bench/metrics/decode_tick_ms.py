"""Device time of one decode tick: the tick program's events / ticks."""


def read(run):
    ticks = run.trace.program("tick")
    if not ticks:
        return None
    return sum(e.dur for e in ticks) / len(ticks) * 1e-6
