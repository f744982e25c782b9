"""Device time of collectives (the ring's ``ppermute`` fetches) per served
multiply, on the busiest device. Nothing to read where no collective ran."""

import tracereduce


def read(ctx):
    w = ctx.window
    if w is None or not w.requests:
        return None
    per_dev = w.per_device_sum_s(tracereduce.COLLECTIVE_MATCH)
    if not per_dev or max(per_dev) <= 0:
        return None
    return 1e3 * max(per_dev) / len(w.requests)
