"""Share of the window spent assembling event rows and packing them into
padded batches (``repro.events.rows`` and ``repro.events.pack``), schedule
builds (``repro.sched.build``) excluded."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.events.rows", "repro.events.pack"),
                 minus=("repro.sched.build",))
