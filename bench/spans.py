"""The program's own spans in a device trace, and the reductions over them.

The serving path opens ``rgl.*`` spans (``src/repro/tracing.py``): host
events in the same profiler session as the device's programs, on the
trace's clock, with their arguments as event stats.  :func:`read` takes
them from a capture as ``(name, start_ns, dur_ns, stats)``; the reductions
below take a :class:`bench.trace.Trace` that carries them as ``spans`` (a
trace without that attribute holds none, and every reader then returns
``None``).

- :func:`span_time`: seconds and count of the named spans in a slice.
- :func:`idle_inside`: device-idle seconds inside the union of the named
  spans in a slice.
- :func:`idle_gaps`: the longest device-idle gaps, each named by the
  innermost span among the harness's and the program's.

The three ``*_ms_*`` functions are metric readers over these (``read(run)``
in the form of ``bench/metrics/<name>.py``).
"""
from __future__ import annotations

import dataclasses
import glob
from pathlib import Path

from bench import trace as tr

PREFIX = "rgl."


def read(log_dir=tr.TRACE_DIR) -> list:
    """``(name, start_ns, dur_ns, stats)`` of every ``rgl.`` host event in
    the newest capture under ``log_dir``; the capture is left in place."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    out = []
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append((e.name, e.start_ns, e.duration_ns,
                                dict(e.stats)))
    return out


def spans_of(trace):
    """The trace's program spans, or ``None`` when it carries none."""
    return getattr(trace, "spans", None)


def _clipped(trace, names, lo, hi) -> list:
    out = []
    for name, st, d, _ in spans_of(trace) or ():
        a, b = max(st, lo), min(st + d, hi)
        if name in names and b > a:
            out.append((a, b))
    return out


def _merged(intervals) -> list:
    out: list = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _overlap_ns(xs: list, ys: list) -> float:
    """Length of the intersection of two merged interval lists."""
    i = j = 0
    total = 0.0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if b > a:
            total += b - a
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def span_time(trace, names, lo, hi):
    """(seconds inside [lo, hi], spans overlapping it) of the spans named
    exactly one of ``names``."""
    c = _clipped(trace, tuple(names), lo, hi)
    return sum(b - a for a, b in c) / 1e9, len(c)


def idle_inside(trace, names, lo, hi) -> float:
    """Device-idle seconds inside the union of the named spans within
    [lo, hi], averaged over the devices."""
    inside = _merged(_clipped(trace, tuple(names), lo, hi))
    total = sum(b - a for a, b in inside)
    devs = sorted({m[3] for m in trace.modules} | {o[4] for o in trace.ops})
    if not devs:
        return total / 1e9
    busy = sum(_overlap_ns(inside, _merged(tr.busy_intervals(trace, lo, hi,
                                                             d)))
               for d in devs) / len(devs)
    return (total - busy) / 1e9


def idle_gaps(trace, lo, hi, n: int = 10) -> list:
    """:func:`bench.trace.idle_gaps` with the program's spans among the
    candidates: each gap is named by the span that covers most of it, the
    innermost among equals (``rgl.*`` names are kept whole)."""
    extra = [(name, st, d) for name, st, d, _ in spans_of(trace) or ()]
    both = dataclasses.replace(trace, host=list(trace.host) + extra)
    return tr.idle_gaps(both, lo, hi, n)


# --------------------------------------------------------------------------
# metric readers
# --------------------------------------------------------------------------
def _slice(run):
    if run.trace is None or spans_of(run.trace) is None:
        return None
    return run.trace_window


def linearize_ms_per_q(run):
    """Linearize (layer: linearize): ``rgl.linearize`` seconds in the traced
    slice over their count, one per admitted request."""
    w = _slice(run)
    if w is None:
        return None
    s, n = span_time(run.trace, [PREFIX + "linearize"], *w)
    return s * 1e3 / n if n else None


def admit_idle_ms_per_wave(run):
    """Host admission (layer: host admission): device-idle time inside
    ``rgl.admit`` and ``rgl.prefill`` in the traced slice, over the
    ``rgl.admit`` spans there (one per admission wave)."""
    w = _slice(run)
    if w is None:
        return None
    _, waves = span_time(run.trace, [PREFIX + "admit"], *w)
    if not waves:
        return None
    idle = idle_inside(run.trace, [PREFIX + "admit", PREFIX + "prefill"], *w)
    return idle * 1e3 / waves


def decode_idle_ms_per_step(run):
    """Decode step, host side (layer: model step): device-idle time inside
    ``rgl.decode`` spans in the traced slice over their count."""
    w = _slice(run)
    if w is None:
        return None
    _, steps = span_time(run.trace, [PREFIX + "decode"], *w)
    if not steps:
        return None
    return idle_inside(run.trace, [PREFIX + "decode"], *w) * 1e3 / steps
