"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, so the second can be checked on a small recorded trace:

1. ``load(trace_dir)`` reads the ``.xplane.pb`` that
   ``jax.profiler.stop_trace`` wrote (with ``ProfileData``, nothing but
   JAX) into plain lists of ``[name, start_s, duration_s]``: per TPU
   device its ``XLA Modules`` line (one event per program execution) and
   its ``XLA Ops`` line (one event per operation), and the host's spans
   of the benchmark's own annotations (``ANNOTATIONS``). All on the
   trace's one clock.
2. ``reduce(events)`` takes the traced window from the ``bench.window``
   span and computes, per device then averaged over devices: busy time
   (the union of operation intervals), device time per program, the
   executions of the decode step, the operations that took most time,
   and the idle gaps named by the benchmark span the host was in.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from typing import Any, Dict, List, Tuple

WINDOW = "bench.window"
SUBMIT = "server.submit"
BACKEND = "Backend.submit"
ANNOTATIONS = (WINDOW, SUBMIT, BACKEND)
#: the jitted decode step's program, as XLA names it
DECODE_MODULE = "jit_serve_step"

_DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
_MODULE_ID = re.compile(r"\(\d+\)$")

Event = List[Any]   # [name, start_s, duration_s]


def load(trace_dir: str) -> Dict[str, Any]:
    from jax.profiler import ProfileData

    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    devices: Dict[str, Dict[str, List[Event]]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if _DEVICE_PLANE.match(plane.name):
            lines = {line.name: line for line in plane.lines}
            devices[plane.name] = {
                key: [[ev.name, ev.start_ns * 1e-9, ev.duration_ns * 1e-9]
                      for ev in lines[name].events] if name in lines else []
                for key, name in (("modules", "XLA Modules"),
                                  ("ops", "XLA Ops"))}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend([ev.name, ev.start_ns * 1e-9,
                             ev.duration_ns * 1e-9]
                            for ev in line.events if ev.name in ANNOTATIONS)
    return {"devices": devices, "host": host}


def admit_share(tr: Dict[str, Any]):
    """Percent of the device's busy time spent outside the decode step's
    program: the batcher's admission (prefill, splice, argmax)."""
    if not tr or tr["busy_s"] <= 0:
        return None
    decode = tr["module_busy_s"].get(DECODE_MODULE, 0.0)
    return 100.0 * (1.0 - decode / tr["busy_s"])


def idle_share(tr: Dict[str, Any]):
    """Percent of the traced window in which no operation ran on the
    device (1 - busy union / window)."""
    if not tr or tr["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])


def module_name(name: str) -> str:
    return _MODULE_ID.sub("", name)


def op_name(name: str) -> str:
    """``%fusion.3`` of the HLO text ``%fusion.3 = bf16[...] fusion(...)``
    that names an operation's event."""
    return name.split(" = ", 1)[0]


def _clip(events: List[Event], lo: float, hi: float) -> List[Tuple]:
    out = []
    for name, start, dur in events:
        s, e = max(start, lo), min(start + dur, hi)
        if e > s:
            out.append((name, s, e))
    return sorted(out, key=lambda x: x[1])


def _union(spans: List[Tuple]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for _, s, e in spans:
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


class _HostActivity:
    """What the host was doing at a time: inside ``Backend.submit`` (the
    server's thread driving the model), else inside ``server.submit``
    (the load generator admitting a request), else neither."""

    ORDER = (BACKEND, SUBMIT)

    def __init__(self, host: List[Tuple]):
        self.spans = {name: _union([ev for ev in host if ev[0] == name])
                      for name in self.ORDER}
        self.starts = {name: [s for s, _ in spans]
                       for name, spans in self.spans.items()}

    def at(self, t: float) -> str:
        for name in self.ORDER:
            i = bisect.bisect_right(self.starts[name], t) - 1
            if i >= 0 and t < self.spans[name][i][1]:
                return name
        return "neither benchmark span"


def reduce(events: Dict[str, Any], top: int = 10) -> Dict[str, Any]:
    windows = [ev for ev in events["host"] if ev[0] == WINDOW]
    if not windows:
        raise ValueError(f"trace holds no {WINDOW!r} span")
    _, w0, wdur = max(windows, key=lambda ev: ev[2])
    w1 = w0 + wdur
    activity = _HostActivity(
        _clip([ev for ev in events["host"] if ev[0] != WINDOW], w0, w1))
    per_device = []
    op_time: Dict[str, float] = {}
    gaps: Dict[str, List[float]] = {}
    for dev in events["devices"].values():
        ops = _clip(dev["ops"], w0, w1)
        modules = _clip(dev["modules"], w0, w1)
        busy = _union(ops)
        # attribute each operation to the program execution holding it
        starts = [m[1] for m in modules]
        by_module: Dict[str, List[Tuple]] = {}
        for name, s, e in ops:
            i = bisect.bisect_right(starts, s) - 1
            owner = (module_name(modules[i][0])
                     if i >= 0 and s < modules[i][2] else "(none)")
            by_module.setdefault(owner, []).append((name, s, e))
            key = f"{owner}/{op_name(name)}"
            op_time[key] = op_time.get(key, 0.0) + (e - s)
        decode = [e - s for name, s, e in modules
                  if module_name(name) == DECODE_MODULE]
        per_device.append({
            "busy_s": sum(e - s for s, e in busy),
            "module_busy_s": {k: sum(e - s for s, e in _union(v))
                              for k, v in by_module.items()},
            "decode_steps": decode,
        })
        edges = [(w0, w0)] + busy + [(w1, w1)]
        for (_, prev_end), (nxt, _) in zip(edges, edges[1:]):
            if nxt > prev_end:
                what = activity.at(0.5 * (prev_end + nxt))
                gaps.setdefault(what, []).append(nxt - prev_end)
    n = max(len(per_device), 1)
    busy_s = sum(d["busy_s"] for d in per_device) / n
    module_busy: Dict[str, float] = {}
    for d in per_device:
        for k, v in d["module_busy_s"].items():
            module_busy[k] = module_busy.get(k, 0.0) + v / n
    decode = [x for d in per_device for x in d["decode_steps"]]
    ops_top = sorted(op_time.items(), key=lambda kv: -kv[1])[:top]
    gaps_top = sorted(
        ([f"host in {what}: {len(v)} gaps, longest {max(v)} s",
          sum(v) / n] for what, v in gaps.items()),
        key=lambda kv: -kv[1])[:top]
    return {
        "window_s": w1 - w0,
        "busy_s": busy_s,
        "devices": len(per_device),
        "module_busy_s": module_busy,
        "decode_step_s": decode,
        "breakdown": {"device_ops": [[k, v / n] for k, v in ops_top],
                      "idle_gaps": gaps_top},
    }
