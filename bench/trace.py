"""Profiler capture and the reduction from a device trace to numbers.

The capture writes an ``.xplane.pb`` under ``bench/.trace/``; :func:`load`
reads it with ``jax.profiler.ProfileData`` into plain lists (device
modules, device ops, host spans) and deletes the files.  Everything after
that works on those lists, so the tests check the reduction on a small
recorded trace without a chip.

Names: a device *module* is one XLA program execution (``jit_serve_step``),
an *op* one operation inside it (a fusion, a Pallas kernel); host spans are
the ``bench:`` annotations the harness writes around its calls into the
program.
"""
from __future__ import annotations

import dataclasses
import glob
import re
import shutil
from pathlib import Path

TRACE_DIR = Path(__file__).resolve().parent / ".trace"
HOST_PREFIX = "bench:"
_SUFFIX = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Trace:
    modules: list  # [(name, start_ns, dur_ns, device)]
    ops: list  # [(name, module, start_ns, dur_ns, device)]
    host: list  # [(name, start_ns, dur_ns)] harness spans
    devices: int

    @classmethod
    def from_json(cls, d: dict) -> "Trace":
        return cls(modules=[tuple(x) for x in d["modules"]],
                   ops=[tuple(x) for x in d["ops"]],
                   host=[tuple(x) for x in d["host"]], devices=d["devices"])


def module_name(name: str) -> str:
    """``jit_serve_step(12)`` -> ``jit_serve_step``."""
    return _SUFFIX.sub("", name.strip())


def op_name(text: str) -> str:
    """An op event's short name: the HLO instruction name, and for a custom
    call (a Pallas kernel) its target and kernel name."""
    head = text.split(" = ", 1)[0].lstrip("%").strip()
    m = re.search(r'custom_call_target="([^"]+)"', text)
    if m is None:
        return head
    k = re.search(r'"(?:kernel_name|name)"\s*:\s*"([^"]+)"', text)
    return f"{head}[{m.group(1)}{':' + k.group(1) if k else ''}]"


def _is_device(plane_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and "Core" not in plane_name


def load(log_dir=TRACE_DIR) -> Trace:
    from jax.profiler import ProfileData

    files = sorted(glob.glob(str(Path(log_dir) / "plugins" / "profile" / "*"
                                 / "*.xplane.pb")))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    pd = ProfileData.from_file(files[-1])
    modules, ops, host, devices = [], [], [], set()
    for plane in pd.planes:
        if _is_device(plane.name):
            dev = plane.name
            for line in plane.lines:
                lname = line.name
                if "Module" in lname:
                    devices.add(dev)
                    for e in line.events:
                        modules.append((module_name(e.name), e.start_ns,
                                        e.duration_ns, dev))
                elif "Ops" in lname and "Overhead" not in lname:
                    for e in line.events:
                        st = dict(e.stats)
                        ops.append((op_name(e.name), module_name(str(st.get(
                            "hlo_module", ""))), e.start_ns, e.duration_ns,
                            dev))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((e.name, e.start_ns, e.duration_ns))
    shutil.rmtree(log_dir, ignore_errors=True)
    return Trace(modules=modules, ops=ops, host=host, devices=len(devices))


# --------------------------------------------------------------------------
# reductions
# --------------------------------------------------------------------------
def window(tr: Trace, span: str = HOST_PREFIX + "window"):
    """(start_ns, end_ns) of the harness's window span."""
    w = [h for h in tr.host if h[0] == span]
    if not w:
        raise ValueError(f"no {span!r} span in the trace")
    name, st, dur = max(w, key=lambda h: h[2])
    return st, st + dur


def _clip(st, dur, lo, hi):
    a, b = max(st, lo), min(st + dur, hi)
    return (a, b) if b > a else None


def union_ns(intervals) -> float:
    """Length of the union of (start, end) intervals."""
    total, cur_a, cur_b = 0.0, None, None
    for a, b in sorted(intervals):
        if cur_b is None or a > cur_b:
            if cur_b is not None:
                total += cur_b - cur_a
            cur_a, cur_b = a, b
        else:
            cur_b = max(cur_b, b)
    if cur_b is not None:
        total += cur_b - cur_a
    return total


def busy_intervals(tr: Trace, lo, hi, device=None) -> list:
    """Device-busy (start, end) intervals inside [lo, hi] (modules, or ops
    where a device has no module line)."""
    src = [(st, d, dev) for _, st, d, dev in tr.modules] or \
        [(st, d, dev) for _, _, st, d, dev in tr.ops]
    out = []
    for st, d, dev in src:
        if device is not None and dev != device:
            continue
        c = _clip(st, d, lo, hi)
        if c:
            out.append(c)
    return out


def busy_s(tr: Trace, lo, hi) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = sorted({m[3] for m in tr.modules} | {o[4] for o in tr.ops})
    if not devs:
        return 0.0
    return sum(union_ns(busy_intervals(tr, lo, hi, d)) for d in devs) \
        / len(devs) / 1e9


def _inside(st, d, lo, hi) -> float:
    c = _clip(st, d, lo, hi)
    return c[1] - c[0] if c else 0.0


def module_time(tr: Trace, patterns, lo, hi):
    """(seconds inside [lo, hi], calls overlapping it) of the device
    modules whose name starts with any of ``patterns``."""
    tot, n = 0.0, 0
    for name, st, d, _ in tr.modules:
        if name.startswith(tuple(patterns)) and _inside(st, d, lo, hi):
            tot += _inside(st, d, lo, hi)
            n += 1
    return tot / 1e9, n


def op_time(tr: Trace, patterns, lo, hi):
    """(seconds inside [lo, hi], events overlapping it) of the device ops
    whose name contains any of ``patterns``."""
    tot, n = 0.0, 0
    for name, _, st, d, _ in tr.ops:
        if any(p in name for p in patterns) and _inside(st, d, lo, hi):
            tot += _inside(st, d, lo, hi)
            n += 1
    return tot / 1e9, n


def top_modules(tr: Trace, lo, hi, n: int = 10) -> list:
    """The ``n`` device modules with the most time inside [lo, hi]."""
    acc: dict = {}
    for name, st, d, _ in tr.modules:
        if _inside(st, d, lo, hi):
            acc[name] = acc.get(name, 0.0) + _inside(st, d, lo, hi) / 1e9
    return sorted(([k, v] for k, v in acc.items()), key=lambda kv: -kv[1])[:n]


def idle_gaps(tr: Trace, lo, hi, n: int = 10) -> list:
    """The ``n`` longest device-idle gaps in [lo, hi], each named by the
    innermost harness span that covers most of it."""
    devs = sorted({m[3] for m in tr.modules}) or [None]
    busy = sorted(busy_intervals(tr, lo, hi, devs[0]))
    gaps, cur = [], lo
    for a, b in busy:
        if a > cur:
            gaps.append((cur, a))
        cur = max(cur, b)
    if hi > cur:
        gaps.append((cur, hi))
    spans = [h for h in tr.host if h[0] != HOST_PREFIX + "window"]
    out = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        best, rank = "no harness span", (0.0, 0.0)
        for name, st, d in spans:
            c = _clip(st, d, a, b)
            # the span covering most of the gap; among equals the shortest,
            # which is the innermost
            if c is not None and (c[1] - c[0], -d) > rank:
                best, rank = name, (c[1] - c[0], -d)
        out.append([best[len(HOST_PREFIX):] if best.startswith(HOST_PREFIX)
                    else best, (b - a) / 1e9])
    return out

