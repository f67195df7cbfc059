"""Share of the consultations' time spent on host closed forms for STATIC
and over-cap candidates (``repro.backend.host_instances`` over
``repro.simpolicy.decide``)."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.backend.host_instances",),
                 over="repro.simpolicy.decide")
