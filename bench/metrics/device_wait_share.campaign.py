"""Share of the window the host waits for the event program's results and
copies them back (``repro.events.wait``)."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.events.wait",))
