"""The event kernel's share of its roofline over the traced window: the
least time its work needs on this chip (``bench/kernels/event_finish.py``
over the published peaks) over the kernel's device time in the trace."""

from kernels.event_finish import roofline_share


def read(ctx):
    if ctx.trace is None or ctx.peaks is None:
        return None
    kernel_s = ctx.trace.kernel_s
    calls = ctx.driver.kernel_calls()
    if kernel_s <= 0 or not calls:
        return None
    share, bound = roofline_share(calls, kernel_s, ctx.peaks["flops_per_s"],
                                  ctx.peaks["hbm_bytes_per_s"])
    ctx.notes["event_kernel_bound"] = bound
    ctx.notes["event_kernel_s"] = kernel_s
    return share
