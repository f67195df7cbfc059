"""Share of the consultations' latency spent inside the backend's
``run_batch`` pricing call."""


def read(ctx):
    lo, hi = ctx.window
    decide = ctx.spans.total("SimPolicy.decide", lo, hi)
    if decide <= 0:
        return None
    return 100.0 * ctx.spans.total("run_batch", lo, hi) / decide
