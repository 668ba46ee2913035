"""Reduction of the program's own host spans in a JAX profiler trace, on
top of ``chipbench.tracereduce``.

The program marks the phases of ``ServeEngine.step`` and of the decoder
with ``jax.profiler.TraceAnnotation`` spans named ``serve.<phase>``
(``repro.obs.trace.span``), on the clock the device's operations are timed
by. ``reduce_planes`` returns what ``tracereduce.reduce_planes`` returns,
with the idle gaps put down to the innermost span of either prefix
(``bench.`` or ``serve.``), and three more numbers by program span name:

- ``span_s``: inclusive seconds;
- ``span_calls``: the number of spans;
- ``idle_in_span``: the first device's idle seconds that fall inside any
  span of that name, by containment, so a span nested in another hides
  nothing of the outer; spans of one name that overlap are merged first.

Only program spans that start inside the ``bench.window`` span count. On
a trace with no program span every number is ``tracereduce``'s.
``layer_metrics`` gives the per-layer numbers these spans define.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses

from chipbench import tracereduce as tr

PROGRAM_PREFIX = "serve."


@dataclasses.dataclass
class Reduced(tr.Reduced):
    span_s: dict[str, float] = dataclasses.field(default_factory=dict)
    span_calls: dict[str, int] = dataclasses.field(default_factory=dict)
    idle_in_span: dict[str, float] = dataclasses.field(default_factory=dict)


def _overlap(a, b) -> float:
    """Length of the intersection of two sorted lists of disjoint
    [start, end) intervals."""
    i = j = 0
    total = 0.0
    while i < len(a) and j < len(b):
        s, e = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if e > s:
            total += e - s
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return total


def _idle_gaps(planes, lo, hi):
    """The first traced device's idle intervals inside [lo, hi)."""
    for plane in planes:
        if not plane.name.startswith("/device:"):
            continue
        ops = [(e.start_ns, e.start_ns + e.duration_ns)
               for e in tr._events(plane, tr.OPS_LINE)]
        if not ops:
            continue
        gaps, prev = [], lo
        for s, e in tr._union(tr._clip(ops, lo, hi)) + [[hi, hi]]:
            if s > prev:
                gaps.append((prev, s))
            prev = max(prev, e)
        return gaps
    return []


def reduce_planes(planes) -> Reduced:
    """Reduce the planes of one trace (``ProfileData.planes``, or objects
    with the same ``name``/``lines``/``events`` shape)."""
    planes = list(planes)
    base = tr.reduce_planes(planes)
    host = [(e.start_ns, e.start_ns + e.duration_ns, e.name)
            for plane in planes if plane.name.startswith("/host:")
            for line in plane.lines for e in line.events
            if e.name.startswith((tr.SPAN_PREFIX, PROGRAM_PREFIX))]
    lo, hi = next((s, e) for s, e, n in host if n == tr.WINDOW_SPAN)
    bench = [x for x in host if x[2].startswith(tr.SPAN_PREFIX)
             and x[2] != tr.WINDOW_SPAN and x[1] > lo and x[0] < hi]
    prog = [x for x in host if x[2].startswith(PROGRAM_PREFIX)
            and lo <= x[0] < hi]
    gaps = _idle_gaps(planes, lo, hi)

    idle: dict[str, float] = collections.defaultdict(float)
    times, names = tr._innermost(bench + prog)
    for s, e in gaps:
        i = bisect.bisect_right(times, (s + e) / 2) - 1
        idle[names[i] if i >= 0 else tr.NO_SPAN] += e - s

    span_ns: dict[str, float] = collections.defaultdict(float)
    calls: dict[str, int] = collections.defaultdict(int)
    by_name = collections.defaultdict(list)
    for s, e, n in prog:
        span_ns[n] += e - s
        calls[n] += 1
        by_name[n].append((s, e))
    return Reduced(
        **{**vars(base), "idle_by_span": {k: v / 1e9
                                          for k, v in idle.items()}},
        span_s={k: v / 1e9 for k, v in span_ns.items()},
        span_calls=dict(calls),
        idle_in_span={n: _overlap(gaps, tr._union(iv)) / 1e9
                      for n, iv in by_name.items()})


def reduce_file(path: str) -> Reduced:
    import jax
    return reduce_planes(jax.profiler.ProfileData.from_file(path).planes)


def layer_metrics(r) -> dict[str, float]:
    """The per-layer numbers that the program's spans define, by name;
    one whose spans ``r`` lacks (a trace without them, or a reduction by
    ``tracereduce`` alone) is left out.

    - ``schedule_ms_per_step``: ``serve.schedule`` per ``serve.step``;
    - ``fabric_ms_per_step``: ``serve.pages`` and ``serve.account`` per
      ``serve.step``;
    - ``decode_dispatch_ms_per_call``: per ``serve.decode.dispatch``;
    - ``dispatch_idle_share``: the window's idle time inside
      ``serve.decode.dispatch``, in percent of the window.
    """
    span_s = getattr(r, "span_s", {})
    calls = getattr(r, "span_calls", {})
    out = {}
    steps = calls.get("serve.step", 0)
    if steps and "serve.schedule" in span_s:
        out["schedule_ms_per_step"] = 1e3 * span_s["serve.schedule"] / steps
    if steps and "serve.account" in span_s:
        out["fabric_ms_per_step"] = 1e3 * (span_s.get("serve.pages", 0.0)
                                           + span_s["serve.account"]) / steps
    n = calls.get("serve.decode.dispatch", 0)
    if n:
        out["decode_dispatch_ms_per_call"] = (
            1e3 * span_s["serve.decode.dispatch"] / n)
        out["dispatch_idle_share"] = (
            100.0 * r.idle_in_span["serve.decode.dispatch"] / r.window_s)
    return out
