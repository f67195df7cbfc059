"""Share of the consultations' time spent dispatching the event program
(``repro.events.dispatch`` over ``repro.simpolicy.decide``): handing the
padded candidate batch to the device, its host-to-device copy included."""

from harness.program import share


def read(ctx):
    return share(ctx, ("repro.events.dispatch",),
                 over="repro.simpolicy.decide")
