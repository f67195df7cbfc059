"""Share of the window the device spends in the event program's
``precompute`` scope: the counter-based draws and the effective-cost gather
over the padded schedule length."""

from harness.program import load


def read(ctx):
    prog = load(ctx)
    s = None if prog is None else prog.scope_seconds("precompute")
    if s is None:
        return None
    return 100.0 * s / ((prog.window[1] - prog.window[0]) * 1e-9)
