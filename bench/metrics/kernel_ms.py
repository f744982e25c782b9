"""Device time of the Pallas BSR kernel per served multiply: the summed
durations of the kernel's launches in the traced window on the busiest
device, over the multiplies in the window."""

import tracereduce


def read(ctx):
    w = ctx.window
    if w is None or not w.requests:
        return None
    per_dev = w.per_device_sum_s(tracereduce.KERNEL_MATCH)
    if not per_dev or max(per_dev) <= 0:
        return None
    return 1e3 * max(per_dev) / len(w.requests)
