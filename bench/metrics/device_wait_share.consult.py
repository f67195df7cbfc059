"""Share of the consultations' time the host waits for the priced
candidates and copies them back (``repro.events.wait`` over
``repro.simpolicy.decide``)."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.events.wait",), over="repro.simpolicy.decide")
