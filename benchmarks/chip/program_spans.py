"""The program's own spans in a profiler trace, and the device-idle time
each holds.

``JaxBackend.submit`` and the ``ContinuousBatcher`` mark their host work
with ``jax.profiler.TraceAnnotation`` spans (``PROGRAM_SPANS``), which the
profiler writes into its host plane on the same clock as the device
planes. This module reads them beside what ``trace_reduce`` reads:

1. ``load(trace_dir)`` is ``trace_reduce.load`` plus a ``program`` list
   of ``[name, start_s, duration_s, stats]``, one event per program span,
   ``stats`` being the span's keyword arguments (host integers).
2. ``reduce(events)`` takes the traced window as ``trace_reduce.reduce``
   does (the longest ``bench.window`` span) and gives, averaged over the
   devices:

   - ``idle_in_program_spans``: the device-idle time of the window split
     by the innermost program span the host was in (a span's self time:
     its interval less its children's), and the idle time outside every
     program span;
   - ``program``: for each span name that lies whole in the window, its
     count and the device-idle time inside its intervals, children
     included; the ``batcher.sync`` spans inside ``batcher.decode`` spans;
     the ``active`` and ``slots`` stats of each ``batcher.decode`` span;
     and ``[uid, prompt_len, bucket]`` of each ``batcher.admit`` span.

The program's spans nest on the one thread that runs ``Backend.submit``,
so a span's parent is the span that holds its interval.
"""

from __future__ import annotations

import bisect
import glob
import os
from typing import Any, Callable, Dict, List, Optional, Tuple

from benchmarks.chip import trace_reduce as TR

SUBMIT = "backend.submit"
TICK = "batcher.tick"
ADMIT = "batcher.admit"
PREFILL = "batcher.prefill"
SPLICE = "batcher.splice"
DECODE = "batcher.decode"
STEP = "batcher.step"
SYNC = "batcher.sync"
#: the names the program gives its spans (src/repro/engine/backend.py,
#: src/repro/serving/scheduler.py)
PROGRAM_SPANS = (SUBMIT, TICK, ADMIT, PREFILL, SPLICE, DECODE, STEP, SYNC)
OUTSIDE = "outside every program span"

Span = Tuple[str, float, float]     # (name, start_s, end_s)


def load(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    events = TR.load(trace_dir)
    path = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                            recursive=True))[-1]
    program = events["program"] = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                program.extend(
                    [ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9,
                     dict(ev.stats)]
                    for ev in line.events if ev.name in PROGRAM_SPANS)
    return events


def _idle(ops: List[Any], w0: float, w1: float
          ) -> Callable[[float], float]:
    """Device-idle seconds in [w0, t], as a function of t."""
    busy = TR._union(TR._clip(ops, w0, w1))
    gaps = [(a, b) for (_, a), (b, _) in
            zip([(w0, w0)] + busy, busy + [(w1, w1)]) if b > a]
    starts = [a for a, _ in gaps]
    before = [0.0]
    for a, b in gaps:
        before.append(before[-1] + b - a)

    def idle_to(t: float) -> float:
        i = bisect.bisect_right(starts, t) - 1
        if i < 0:
            return 0.0
        a, b = gaps[i]
        return before[i] + min(t, b) - a

    return idle_to


def _innermost(spans: List[Span]) -> List[Span]:
    """The covered time cut into pieces, each named by the innermost span
    that holds it. A child is cut at its parent's end."""
    out: List[Span] = []
    stack: List[Tuple[str, float]] = []
    t = 0.0
    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        while stack and stack[-1][1] <= s:
            top, end = stack.pop()
            out.append((top, t, end))
            t = end
        if stack:
            out.append((stack[-1][0], t, s))
            e = min(e, stack[-1][1])
        stack.append((name, e))
        t = s
    while stack:
        top, end = stack.pop()
        out.append((top, t, end))
        t = end
    return [p for p in out if p[2] > p[1]]


def reduce(events: Dict[str, Any]) -> Dict[str, Any]:
    windows = [ev for ev in events["host"] if ev[0] == TR.WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {TR.WINDOW!r} span")
    _, w0, wdur = max(windows, key=lambda ev: ev[2])
    w1 = w0 + wdur
    program = events.get("program", [])
    clipped = TR._clip([ev[:3] for ev in program], w0, w1)
    whole = sorted((ev for ev in program
                    if w0 <= ev[1] and ev[1] + ev[2] <= w1),
                   key=lambda ev: ev[1])
    clocks = [_idle(dev["ops"], w0, w1)
              for dev in events["devices"].values()]
    n = max(len(clocks), 1)

    def idle(s: float, e: float) -> float:
        return sum(f(e) - f(s) for f in clocks) / n

    self_idle: Dict[str, float] = {}
    for name, s, e in _innermost(clipped):
        self_idle[name] = self_idle.get(name, 0.0) + idle(s, e)
    self_idle[OUTSIDE] = idle(w0, w1) - sum(self_idle.values())
    spans: Dict[str, Dict[str, Any]] = {}
    for name, s, d, _ in whole:
        row = spans.setdefault(name, {"count": 0, "idle_s": 0.0})
        row["count"] += 1
        row["idle_s"] += idle(s, s + d)
    syncs = [ev[1] for ev in whole if ev[0] == SYNC]
    decodes = [ev for ev in whole if ev[0] == DECODE]
    return {
        "idle_in_program_spans": sorted(
            ([k, v] for k, v in self_idle.items()), key=lambda kv: -kv[1]),
        "program": {
            "spans": spans,
            "decode_syncs": sum(
                bisect.bisect_left(syncs, s + d) - bisect.bisect_left(syncs, s)
                for _, s, d, _ in decodes),
            "decode_active": [st["active"] for *_, st in decodes],
            "decode_slots": [st["slots"] for *_, st in decodes],
            "admits": [[st["uid"], st["prompt_len"], st["bucket"]]
                       for name, *_, st in whole if name == ADMIT],
        },
    }


def _per_span_ms(p: Dict[str, Any], name: str) -> Optional[float]:
    row = p["spans"].get(name)
    return 1e3 * row["idle_s"] / row["count"] if row else None


def tick_idle_ms(p: Dict[str, Any]) -> Optional[float]:
    """Device-idle milliseconds inside one ``batcher.decode`` span."""
    return _per_span_ms(p, DECODE)


def admit_idle_ms(p: Dict[str, Any]) -> Optional[float]:
    """Device-idle milliseconds inside one ``batcher.admit`` span."""
    return _per_span_ms(p, ADMIT)


def syncs_per_tick(p: Dict[str, Any]) -> Optional[float]:
    """``batcher.sync`` spans inside ``batcher.decode`` spans, per
    ``batcher.decode`` span."""
    n = len(p["decode_active"])
    return p["decode_syncs"] / n if n else None


def slot_occupancy(p: Dict[str, Any]) -> Optional[float]:
    """Percent of the batch's slots active, averaged over the
    ``batcher.decode`` spans."""
    pairs = list(zip(p["decode_active"], p["decode_slots"]))
    return 100.0 * sum(a / s for a, s in pairs) / len(pairs) \
        if pairs else None
