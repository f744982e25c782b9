"""Host time per served multiply that no program span explains: the part
of each request's span in which no device ran an op (what ``host_ms``
reads), less the part that any of the program's spans covers. Large where
a phase of the host path has no span."""

import program_spans
import tracereduce as tr


def read(ctx):
    w = ctx.window
    if w is None or not w.requests:
        return None
    spans = program_spans.program_union(w)
    if not spans:
        return None
    explained = tr.merge(spans + program_spans.device_busy(w))
    return program_spans.mean_ms(
        w, lambda lo, hi: (hi - lo) - tr.covered(explained, lo, hi))
