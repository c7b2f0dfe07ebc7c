"""Host time of the decode tick per step: the durations of the step's
``serve.tick.inputs`` (the per-slot arrays and their upload),
``serve.tick.dispatch`` (the tick program's call) and ``serve.record``
(the tokens recorded, slots reset and pages released), summed per step
and averaged over the steps with a tick that the traced window holds
whole. All on the host's clock: it needs no alignment."""

from bench import program_spans

PARTS = ("serve.tick.inputs", "serve.tick.dispatch", "serve.record")


def read(run):
    prog = program_spans.of(run)
    if prog is None:
        return None
    per_tick = [sum(s.dur for name in PARTS for s in prog.in_step(step, name))
                for step in prog.inside("serve.step")
                if prog.in_step(step, "serve.tick.dispatch")]
    return sum(per_tick) / len(per_tick) * 1e-6 if per_tick else None
