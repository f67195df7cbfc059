"""Share of the consultations' time spent assembling and packing the
candidates' event rows (``repro.events.rows`` and ``repro.events.pack``,
schedule builds excluded, over ``repro.simpolicy.decide``)."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.events.rows", "repro.events.pack"),
                 over="repro.simpolicy.decide", minus=("repro.sched.build",))
