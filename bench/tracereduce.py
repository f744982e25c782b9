"""Reduction of a profiler trace (``.xplane.pb``) to the numbers the
per-layer metrics read.

The trace is first flattened to a :class:`Trace`: per device, the op
events of its ``XLA Ops`` line; on the host, every event, the harness's own
spans among them. Everything after that is interval arithmetic on this
plain structure, which ``to_json``/``from_json`` round-trip, so a small
recorded trace checks the arithmetic on the CPU.

On a TPU v5e an op event's name is the op's whole HLO instruction,
``%<op> = <shape> <opcode>(<operands>), ...``; events are matched on the
op's own name, the text before `` = ``, never on its operands:

* the BSR kernel: ``KERNEL_MATCH``. The kernel has no ``name=`` of its own;
  each of its launches (one per schedule window) is a custom call that
  takes the name of the jitted function around it,
  ``%bsr_spgemm_pallas.<k>``, as read by hand in a trace of the served
  path;
* collectives: ``COLLECTIVE_MATCH``, XLA's opcode names.
"""

from __future__ import annotations

import dataclasses
import json
import re
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

# op names of the Pallas BSR kernel's launches (one per schedule window)
KERNEL_MATCH = re.compile(r"^bsr_spgemm_pallas(\.\d+)*$")
COLLECTIVE_MATCH = re.compile(
    r"^(collective-permute|all-gather|all-reduce|all-to-all|"
    r"reduce-scatter)")
# the device line whose events are ops (the others repeat them as modules
# and steps)
OPS_LINE = "XLA Ops"
# the harness's own host spans are named with this prefix; the window span
# brackets all the others
SPAN_PREFIX = "bench."
WINDOW_NAME = "bench.window"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    end_ns: float

    @property
    def dur_ns(self) -> float:
        return self.end_ns - self.start_ns


@dataclasses.dataclass
class Trace:
    devices: Dict[str, List[Event]]   # device plane -> its op events
    host: List[Event]                 # every host event, spans included

    def spans(self, name: str) -> List[Event]:
        return sorted((e for e in self.host if e.name == name),
                      key=lambda e: e.start_ns)

    def to_json(self) -> str:
        ev = lambda e: [e.name, e.start_ns, e.end_ns]  # noqa: E731
        return json.dumps({
            "devices": {d: [ev(e) for e in es]
                        for d, es in self.devices.items()},
            "host": [ev(e) for e in self.host]})

    @classmethod
    def from_json(cls, text: str) -> "Trace":
        raw = json.loads(text)
        return cls({d: [Event(*e) for e in es]
                    for d, es in raw["devices"].items()},
                   [Event(*e) for e in raw["host"]])


def find_xplane(log_dir: Path) -> Path:
    found = sorted(Path(log_dir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: Path) -> Trace:
    """Flatten an ``.xplane.pb`` (read with JAX's own reader)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "TPU" in plane.name:
            ops = [Event(e.name, e.start_ns, e.end_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            devices[plane.name] = sorted(ops, key=lambda e: e.start_ns)
        elif plane.name.startswith("/host:CPU"):
            host.extend(Event(e.name, e.start_ns, e.end_ns)
                        for line in plane.lines for e in line.events)
    return Trace(devices, host)


# ---- interval arithmetic ----------------------------------------------------

def merge(intervals: Sequence[Tuple[float, float]]) -> List[Tuple[float,
                                                                  float]]:
    """The union of intervals, as disjoint sorted intervals."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(merged: Sequence[Tuple[float, float]], lo: float,
            hi: float) -> float:
    """Length of ``[lo, hi)`` that the disjoint ``merged`` intervals
    cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


def busy(events: Sequence[Event]) -> List[Tuple[float, float]]:
    return merge([(e.start_ns, e.end_ns) for e in events])


def op_name(name: str) -> str:
    """An op event's own name: ``%fusion.12 = f32[8] fusion(...)`` ->
    ``fusion.12``."""
    return name.split(" = ", 1)[0].lstrip("%")


def op_family(name: str) -> str:
    """An op's name without its numeric instance suffix
    (``%fusion.12 = ...`` -> ``fusion``)."""
    return re.sub(r"(\.\d+)+$", "", op_name(name))


def matching(events: Sequence[Event], pattern: re.Pattern) -> List[Event]:
    return [e for e in events if pattern.search(op_name(e.name))]


# ---- what the metrics read ----------------------------------------------------

@dataclasses.dataclass
class Window:
    """A traced window: the trace, the harness's request spans in it, and
    the span that brackets the whole window."""
    trace: Trace
    requests: List[Event]
    lo_ns: float
    hi_ns: float

    @classmethod
    def of(cls, trace: Trace, request_span: str,
           window_span: str) -> "Window":
        win = trace.spans(window_span)
        if not win:
            raise ValueError(f"trace holds no {window_span!r} span")
        return cls(trace, trace.spans(request_span), win[0].start_ns,
                   win[-1].end_ns)

    @property
    def window_s(self) -> float:
        return (self.hi_ns - self.lo_ns) / 1e9

    def device_busy_s(self) -> List[float]:
        """Per device: seconds in the window in which an op ran."""
        return [covered(busy(es), self.lo_ns, self.hi_ns) / 1e9
                for es in self.trace.devices.values()]

    def in_window(self, events: Sequence[Event]) -> List[Event]:
        return [e for e in events
                if e.start_ns >= self.lo_ns and e.end_ns <= self.hi_ns]

    def per_device_sum_s(self, pattern: re.Pattern) -> List[float]:
        """Per device: summed durations of the window's ops matching
        ``pattern``."""
        return [sum(e.dur_ns for e in matching(self.in_window(es),
                                               pattern)) / 1e9
                for es in self.trace.devices.values()]

    def host_only_s(self) -> List[float]:
        """Per request span: seconds in which no device ran an op."""
        any_busy = merge([iv for es in self.trace.devices.values()
                          for iv in busy(es)])
        return [(r.dur_ns - covered(any_busy, r.start_ns, r.end_ns)) / 1e9
                for r in self.requests]

    def top_ops(self, k: int = 10) -> List[List]:
        """The ``k`` op families that took most device time, summed over
        devices, in seconds."""
        tot: Dict[str, float] = {}
        for es in self.trace.devices.values():
            for e in self.in_window(es):
                f = op_family(e.name)
                tot[f] = tot.get(f, 0.0) + e.dur_ns / 1e9
        return [[n, s] for n, s in
                sorted(tot.items(), key=lambda x: -x[1])[:k]]

    def idle_gaps(self, k: int = 10) -> List[List]:
        """The ``k`` longest gaps (of a microsecond or more) in which the
        first device ran no op, each named by what the host was doing."""
        if not self.trace.devices:
            return []
        first = next(iter(self.trace.devices.values()))
        gaps, t = [], self.lo_ns
        for s, e in busy(first):
            if e <= self.lo_ns or s >= self.hi_ns:
                continue
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < self.hi_ns:
            gaps.append((t, self.hi_ns))
        gaps = sorted((g for g in gaps if g[1] - g[0] >= 1e3),
                      key=lambda g: g[0] - g[1])[:k]
        return [[self._host_doing(lo, hi), (hi - lo) / 1e9]
                for lo, hi in gaps]

    def _host_doing(self, lo: float, hi: float) -> str:
        """``<harness span> / <program event> (<share of the gap>)``: the
        host event of the program that overlaps the gap most, inside the
        harness's span that overlaps it most."""
        def best(events):
            ov = [(min(e.end_ns, hi) - max(e.start_ns, lo), e.name)
                  for e in events]
            ov = [x for x in ov if x[0] > 0]
            return max(ov) if ov else (0.0, None)

        spans = [e for e in self.trace.host if e.name.startswith(SPAN_PREFIX)
                 and e.name != WINDOW_NAME]
        _, where = best(spans)
        ov, what = best(e for e in self.trace.host
                        if not e.name.startswith(SPAN_PREFIX))
        where = where or "between requests"
        if what is None:
            return f"{where} / no program event"
        return f"{where} / {what} ({100 * ov / (hi - lo):.0f}%)"
