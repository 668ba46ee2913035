"""Reduction of a JAX profiler trace (``.xplane.pb``) to the benchmark's
device numbers, read with ``jax.profiler.ProfileData`` alone.

- Device busy time is the union of the intervals in which an operation ran
  on a device (the ``XLA Ops`` line of each ``/device:`` plane), clipped to
  the traced window; idle time is the rest of the window.
- Device time by program sums the operations of each XLA module, named
  without its numeric suffix (``jit_paged_attention``): each paged-attention
  op of the program is its own jitted function, so its module name is
  stable across runs and PRs.
- Each idle gap is put down to the innermost host span of the harness (a
  ``jax.profiler.TraceAnnotation`` named ``bench.<layer call>``) that
  holds the gap's midpoint, or to ``no span`` where none does.

The window is the host span named ``bench.window`` (``WINDOW_SPAN``).
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import re

SPAN_PREFIX = "bench."
WINDOW_SPAN = SPAN_PREFIX + "window"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
NO_SPAN = "no span"
_SUFFIX = re.compile(r"[(.:]\d+\)?$")


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float                         # mean over the devices traced
    devices: int
    module_s: dict[str, float]            # device seconds by XLA module
    idle_by_span: dict[str, float]        # idle seconds by host span

    def top(self, d: dict, n: int = 10) -> list[list]:
        return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])
                [:n]]


def module_name(name: str) -> str:
    """``jit_paged_attention(1234)`` -> ``jit_paged_attention``."""
    return _SUFFIX.sub("", name)


def _union(intervals):
    """Sorted, merged [start, end) intervals."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _innermost(spans):
    """(times, names): from ``times[i]`` on, until the next time, the
    innermost of the nested ``spans`` (start, end, name) is ``names[i]``."""
    times, names, stack = [], [], []
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, _ = stack.pop()
            times.append(end)
            names.append(stack[-1][1] if stack else NO_SPAN)
        stack.append((e, n))
        times.append(s)
        names.append(n)
    while stack:
        end, _ = stack.pop()
        times.append(end)
        names.append(stack[-1][1] if stack else NO_SPAN)
    return times, names


def _events(plane, line_name):
    for line in plane.lines:
        if line.name == line_name:
            yield from line.events


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def reduce_planes(planes) -> Reduced:
    """Reduce the planes of one trace (``ProfileData.planes``, or objects
    with the same ``name``/``lines``/``events`` shape)."""
    host_spans = []                       # (start, end, name)
    device_planes = []
    for plane in planes:
        if plane.name.startswith("/device:"):
            device_planes.append(plane)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(SPAN_PREFIX):
                        host_spans.append((e.start_ns,
                                           e.start_ns + e.duration_ns, e.name))
    windows = [(s, e) for s, e, n in host_spans if n == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no host span named {WINDOW_SPAN!r} in the trace")
    lo, hi = windows[0]
    window_ns = hi - lo

    module_ns: dict[str, float] = collections.defaultdict(float)
    busy_ns = 0.0
    busy_all = []
    n_dev = 0
    for plane in device_planes:
        ops = [(e.start_ns, e.start_ns + e.duration_ns, e)
               for e in _events(plane, OPS_LINE)]
        if not ops:
            continue
        n_dev += 1
        mods = sorted((e.start_ns, e.start_ns + e.duration_ns,
                       module_name(e.name))
                      for e in _events(plane, MODULES_LINE))
        starts = [m[0] for m in mods]
        for s, e, ev in ops:
            cs, ce = max(s, lo), min(e, hi)
            if ce <= cs:
                continue
            mod = _stat(ev, "hlo_module")
            if mod is None:
                i = bisect.bisect_right(starts, s) - 1
                mod = (mods[i][2] if i >= 0 and mods[i][1] >= e
                       else module_name(ev.name))
            module_ns[module_name(str(mod))] += ce - cs
        merged = _union(_clip([(s, e) for s, e, _ in ops], lo, hi))
        busy_ns += sum(e - s for s, e in merged)
        busy_all.append(merged)
    if not n_dev:
        raise ValueError("no device operations in the trace")

    # idle gaps of the first device, put down to host spans
    idle: dict[str, float] = collections.defaultdict(float)
    times, names = _innermost([x for x in host_spans
                               if x[1] > lo and x[0] < hi
                               and x[2] != WINDOW_SPAN])
    prev = lo
    for s, e in busy_all[0] + [[hi, hi]]:
        if s > prev:
            i = bisect.bisect_right(times, (prev + s) / 2) - 1
            idle[names[i] if i >= 0 else NO_SPAN] += s - prev
        prev = max(prev, e)
    return Reduced(window_s=window_ns / 1e9, busy_s=busy_ns / n_dev / 1e9,
                   devices=n_dev,
                   module_s={k: v / 1e9 for k, v in module_ns.items()},
                   idle_by_span={k: v / 1e9 for k, v in idle.items()})


def reduce_file(path: str) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)
