"""Host time of one admission's page reservation: the mean duration of
the admitted requests' ``serve.reserve`` spans that the traced window
holds whole (radix match, references, eviction with its page resets,
allocation and the page table), on the host's clock. A program without
the span gives ``None``."""

from bench import program_spans


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    durs = [s.dur for s in prog.inside("serve.reserve")
            if s.args.get("admitted") == 1]
    return sum(durs) / len(durs) * 1e-6 if durs else None
