"""Record a call under the profiler and read the program's spans back."""

from pathlib import Path

import jax
from jax.profiler import ProfileData


def record(log_dir, fn):
    """``fn()`` with the profiler attached, writing under ``log_dir``."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(log_dir), profiler_options=opts)
    try:
        return fn()
    finally:
        jax.profiler.stop_trace()


def host_spans(log_dir):
    """(name, start_ns, end_ns, counters) of every ``spgemm.`` event on
    the host plane, in start order."""
    (path,) = sorted(Path(log_dir).rglob("*.xplane.pb"))
    out = []
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("spgemm."):
                    out.append((e.name, e.start_ns, e.end_ns,
                                dict(e.stats)))
    return sorted(out, key=lambda x: x[1])
