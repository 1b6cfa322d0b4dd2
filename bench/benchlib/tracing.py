"""From a profiler trace to device busy time, kernel and program time,
and idle gaps named by what the host was doing.

``load_events`` reads the ``.xplane.pb`` that ``jax.profiler`` writes
into plain tuples; ``reduce`` works on those tuples alone, so it can be
checked on a small recorded trace. Times are nanoseconds on the
profiler's own clock, which puts host and device events on one axis.

- busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane),
  clipped to the window and averaged over the devices.
- window: the host span ``bench.window`` that the benchmark puts around
  its measured loop.
- gaps: the stretches of the window in which no operation ran, each
  named by the innermost ``bench.*`` host span around its midpoint.
- ops: device seconds per op; the ranking in ``device_ops`` names each
  by its HLO instruction and leaves out the ops that only hold others.
"""
from __future__ import annotations

import glob
import os
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "bench.window"
HOST_PREFIX = "bench."
#: ops that only hold others (a scan's ``while``): left out of the op
#: ranking, whose entries would otherwise count their bodies twice
CONTAINERS = ("while", "conditional", "call")

#: (plane, line, name, start_ns, duration_ns)
Event = Tuple[str, str, str, float, float]


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load_events(path: str) -> List[Event]:
    """Device op and module events, and the benchmark's host spans."""
    from jax.profiler import ProfileData
    out: List[Event] = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith("/device:")
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if device and line.name not in (OPS_LINE, MODULES_LINE):
                continue
            for ev in line.events:
                if not device and not ev.name.startswith(HOST_PREFIX):
                    continue
                out.append((plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)))
    return out


def short_name(op: str) -> str:
    """``fusion.428`` of an op event named by its HLO text
    (``%fusion.428 = (f32[...]) fusion(...)``)."""
    return op.split(" = ", 1)[0].lstrip("%")


def union_length(intervals: Iterable[Tuple[float, float]]) -> float:
    total, end = 0.0, None
    start = None
    for a, b in sorted(intervals):
        if end is None or a > end:
            if end is not None:
                total += end - start
            start, end = a, b
        else:
            end = max(end, b)
    if end is not None:
        total += end - start
    return total


def merged(intervals: Iterable[Tuple[float, float]]) -> List[List[float]]:
    out: List[List[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(a: float, b: float, lo: float, hi: float) -> Optional[Tuple]:
    a, b = max(a, lo), min(b, hi)
    return (a, b) if b > a else None


def window_of(events: Sequence[Event]) -> Tuple[float, float]:
    spans = [(s, s + d) for p, l, n, s, d in events if n == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} host span")
    return min(a for a, _ in spans), max(b for _, b in spans)


def reduce(events: Sequence[Event], top: int = 10) -> Dict:
    """Busy and window seconds, per-op and per-module device seconds,
    and the longest idle gaps, all inside the ``bench.window`` span."""
    lo, hi = window_of(events)
    devices = sorted({p for p, l, *_ in events if p.startswith("/device:")})
    busy = 0.0
    op_ns: Dict[str, float] = {}
    mod: Dict[str, List[float]] = {}
    ivs_by_dev: Dict[str, List[Tuple[float, float]]] = {d: [] for d in devices}
    for p, l, n, s, d in events:
        if p not in ivs_by_dev:
            continue
        c = _clip(s, s + d, lo, hi)
        if c is None:
            continue
        if l == OPS_LINE:
            ivs_by_dev[p].append(c)
            op_ns[n] = op_ns.get(n, 0.0) + (c[1] - c[0])
        elif l == MODULES_LINE:
            mod.setdefault(n, []).append(d)
    for d in devices:
        busy += union_length(ivs_by_dev[d])
    n_dev = max(len(devices), 1)
    host = [(s, s + d, n) for p, l, n, s, d in events
            if p.startswith("/host:") and n != WINDOW_SPAN]
    gaps = []
    if devices:
        prev = lo
        for a, b in merged(ivs_by_dev[devices[0]]) + [[hi, hi]]:
            if a > prev:
                gaps.append((prev, a))
            prev = max(prev, b)
    named = []
    for a, b in gaps:
        mid = (a + b) / 2
        around = [(e - s, n) for s, e, n in host if s <= mid <= e]
        named.append([min(around)[1] if around else "host: none",
                      (b - a) * 1e-9])
    named.sort(key=lambda g: -g[1])
    ops = sorted(((k, v) for k, v in op_ns.items()
                  if not short_name(k).startswith(CONTAINERS)),
                 key=lambda kv: -kv[1])
    return {"window_s": (hi - lo) * 1e-9,
            "busy_s": busy / n_dev * 1e-9,
            "devices": len(devices),
            "op_s": {k: v / n_dev * 1e-9 for k, v in op_ns.items()},
            "module_s": {k: [x * 1e-9 for x in v] for k, v in mod.items()},
            "device_ops": [[short_name(k), v / n_dev * 1e-9]
                           for k, v in ops[:top]],
            "idle_gaps": named[:top]}
