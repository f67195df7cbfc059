"""Share of the window the backend spends running STATIC and over-cap
instances as host closed forms and drawing the event instances' fold seeds
(``repro.backend.host_instances``)."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.backend.host_instances",))
