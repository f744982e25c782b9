"""Share of the traced window in which no op ran on the device, averaged
over the cell's chips."""


def read(ctx):
    w = ctx.window
    if w is None or w.window_s <= 0 or not w.trace.devices:
        return None
    busy = w.device_busy_s()
    return 100.0 * (1.0 - sum(busy) / len(busy) / w.window_s)
