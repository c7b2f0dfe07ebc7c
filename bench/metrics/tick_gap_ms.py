"""Device-idle time between two decode ticks: from the end of one
``jit_tick`` run to the start of the next, less the time other
operations ran on the device in between, averaged over the pairs whose
later tick follows no prefill chunk (its ``serve.tick.dispatch`` span
has ``chunks == 0``). Each run is paired with the span that launched it,
in order; both ends are on the device's clock."""

from bench import program_spans
from bench.trace_reduce import covered


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    ops = prog.trace.ops[0] if prog.trace.ops else []
    pairs = prog.tick_pairs()
    gaps = [(after.start - before.end) - covered(ops, before.end, after.start)
            for (_, before), (span, after) in zip(pairs, pairs[1:])
            if span.args.get("chunks") == 0]
    return sum(gaps) / len(gaps) * 1e-6 if gaps else None
