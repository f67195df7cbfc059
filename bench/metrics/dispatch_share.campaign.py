"""Share of the window spent dispatching the event program
(``repro.events.dispatch``): handing the padded host batch to the device,
its host-to-device copy included."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.events.dispatch",))
