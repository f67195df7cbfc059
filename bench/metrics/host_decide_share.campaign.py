"""Share of the window the lockstep replay spends in ``ReplayBatch.step``
outside its ``run_lockstep`` calls: the host decide / learn loop."""


def read(ctx):
    lo, hi = ctx.window
    step = ctx.spans.total("ReplayBatch.step", lo, hi)
    execute = ctx.spans.total("run_lockstep", lo, hi)
    if step <= 0:
        return None
    return 100.0 * (step - execute) / (hi - lo)
