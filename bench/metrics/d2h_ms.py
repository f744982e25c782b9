"""Device-to-host transfer time per served multiply: the program's fetch
of the output stack (``np.asarray``, which first waits for the device),
less the time in it in which any device ran an op."""

import program_spans
import tracereduce as tr


def read(ctx):
    w = ctx.window
    if w is None or not w.requests:
        return None
    fetch = program_spans.union(w, ("spgemm.execute.fetch",))
    if not fetch:
        return None
    busy = program_spans.device_busy(w)

    def host_part(lo, hi):
        total = 0.0
        for s, e in fetch:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                total += (e - s) - tr.covered(busy, s, e)
        return total

    return program_spans.mean_ms(w, host_part)
