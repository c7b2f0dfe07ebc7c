"""Share of the admitted prompt tokens that the prefix cache served:
the ``hit_tokens`` (the prefill's resume offset) over the ``prompt_len``
of every admitted request's ``serve.reserve`` span that the traced
window holds whole, in %. A program without the span gives ``None``."""

from bench import program_spans


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    admitted = [s.args for s in prog.inside("serve.reserve")
                if s.args.get("admitted") == 1]
    tokens = sum(a["prompt_len"] for a in admitted)
    return 100.0 * sum(a["hit_tokens"] for a in admitted) / tokens \
        if tokens else None
