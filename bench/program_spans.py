"""What the readers of the program's own spans share.

The program under test records named profiler spans on the host path of a
served multiply, ``spgemm.<layer>.<phase>``, each a flat leaf on the host
plane of the trace, on the same clock as the device's ops. A reader takes
the union of the intervals of its spans, so that the second span of a
retried stage is not counted twice, clips it to each request span of the
window, and reports the mean over the window's requests, in ms. Where the
trace holds none of its spans, as a trace of a program that records none
does, it reads nothing.
"""

from __future__ import annotations

from typing import Callable, List, Optional, Sequence, Tuple

import tracereduce as tr

PREFIX = "spgemm."

Intervals = List[Tuple[float, float]]


def union(window: tr.Window, names: Sequence[str]) -> Intervals:
    """The union of every span named in ``names``."""
    return tr.merge([(e.start_ns, e.end_ns) for n in names
                     for e in window.trace.spans(n)])


def program_union(window: tr.Window) -> Intervals:
    """The union of every span the program records."""
    return tr.merge([(e.start_ns, e.end_ns) for e in window.trace.host
                     if e.name.startswith(PREFIX)])


def device_busy(window: tr.Window) -> Intervals:
    """The union of the op intervals of every device."""
    return tr.merge([iv for es in window.trace.devices.values()
                     for iv in tr.busy(es)])


def mean_ms(window: tr.Window,
            per_request: Callable[[float, float], float]) -> float:
    """The mean over the window's requests of ``per_request(lo, hi)``, a
    length in ns within the request ``[lo, hi)``, in ms."""
    reqs = window.requests
    return sum(per_request(r.start_ns, r.end_ns) for r in reqs) \
        / len(reqs) / 1e6


def span_ms(window: Optional[tr.Window],
            names: Sequence[str]) -> Optional[float]:
    """The time per request that the spans ``names`` cover, in ms."""
    if window is None or not window.requests:
        return None
    spans = union(window, names)
    if not spans:
        return None
    return mean_ms(window, lambda lo, hi: tr.covered(spans, lo, hi))
