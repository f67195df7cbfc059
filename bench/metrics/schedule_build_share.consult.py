"""Share of the consultations' time spent building chunk and StaticSteal
schedules on a cache miss (``repro.sched.build`` over
``repro.simpolicy.decide``)."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.sched.build",), over="repro.simpolicy.decide")
