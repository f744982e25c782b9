"""Host time per served multiply: the part of each request's span, timed
by the harness around ``serve()``, in which no device ran an op; the mean
over the window's requests."""


def read(ctx):
    w = ctx.window
    if w is None or not w.requests:
        return None
    host = w.host_only_s()
    return 1e3 * sum(host) / len(host)
