"""Share of its roofline the BSR kernel reaches: the least time the chips
need for the multiply's own work (``work.least_time`` of the operands'
operations and CSC bytes at the published peaks), over the kernel's device
time per multiply (the ``kernel_ms`` reader). Reports which bound
applies."""

import inputs
import work


def read(ctx):
    ms = inputs.load_module("metrics", "kernel_ms").read(ctx)
    if ms is None:
        return None
    least, bound = work.least_time(ctx.ops, ctx.nbytes, ctx.peak, ctx.chips)
    return {"value": 100.0 * least / (ms / 1e3), "bound": bound}
