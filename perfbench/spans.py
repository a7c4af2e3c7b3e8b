#!/usr/bin/env python3
"""The program's own host spans in a profiler trace of one window.

``serve/engine.py`` runs each wave in spans named ``spmm.*``: ``spmm.stage``
(children ``.pack``, ``.put``), ``spmm.dispatch`` and ``spmm.retire``
(children ``.wait``, ``.fetch``, ``.copy``), each with the stats ``wave``
and ``cols``. They nest inside the harness's ``engine.step``. From them:

- the mean seconds of one span per wave inside the window, which the
  per-layer metrics ``retire_fetch_ms``, ``retire_copy_ms`` and
  ``stage_put_ms`` read (None where the program has no such span);
- the window's device idle time put down to the innermost host span over
  each idle instant, the one that started latest: time under
  ``engine.step`` that no ``spmm.*`` span covers stays ``engine.step``.

  python3 perfbench/spans.py <trace dir>

prints, as one JSON line, the idle split and each span's count and mean.
"""
from __future__ import annotations

import collections
import heapq
import json
import os
import sys
from typing import Dict, List, Optional, Tuple

if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))

from perfbench import trace as _trace  # noqa: E402

PREFIX = "spmm."
UNTRACED = "untraced"

Interval = Tuple[float, float]


def host_spans(pd) -> Dict[str, List[Interval]]:
    """Host span name -> [(start_ns, end_ns)], for the window span, the
    harness's spans and every span named ``spmm.*``."""
    out: Dict[str, List[Interval]] = collections.defaultdict(list)
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == _trace.WINDOW_SPAN or e.name in \
                        _trace.HOST_SPANS or e.name.startswith(PREFIX):
                    out[e.name].append((e.start_ns,
                                        e.start_ns + e.duration_ns))
    return out


def window(spans: Dict[str, List[Interval]]) -> Interval:
    if not spans.get(_trace.WINDOW_SPAN):
        raise ValueError(f"trace holds no {_trace.WINDOW_SPAN!r} span")
    return max(spans[_trace.WINDOW_SPAN], key=lambda iv: iv[1] - iv[0])


def durations_s(spans: Dict[str, List[Interval]], name: str) -> List[float]:
    """Seconds of each span ``name`` that starts inside the window."""
    w0, w1 = window(spans)
    return [(b - a) / 1e9 for a, b in spans.get(name, ()) if w0 <= a <= w1]


def labels(spans: Dict[str, List[Interval]], w0: float, w1: float
           ) -> List[Tuple[float, float, str]]:
    """Cut ``[w0, w1]`` into pieces ``(start, end, name)``, each named for
    the innermost span over it (the one that started latest), else
    ``untraced``."""
    ivs = sorted((a, b, n) for n, v in spans.items()
                 if n != _trace.WINDOW_SPAN for a, b in v
                 if b > w0 and a < w1 and b > a)
    cuts = sorted({w0, w1} | {x for a, b, _ in ivs for x in (a, b)
                              if w0 < x < w1})
    out: List[Tuple[float, float, str]] = []
    active: list = []              # heap of (-start, end, name)
    i = 0
    for a, b in zip(cuts, cuts[1:]):
        while i < len(ivs) and ivs[i][0] <= a:
            heapq.heappush(active, (-ivs[i][0], ivs[i][1], ivs[i][2]))
            i += 1
        while active and active[0][1] <= a:
            heapq.heappop(active)
        name = active[0][2] if active else UNTRACED
        if out and out[-1][2] == name and out[-1][1] == a:
            out[-1] = (out[-1][0], b, name)
        else:
            out.append((a, b, name))
    return out


def idle_by_innermost(pd) -> Tuple[float, float, List[list]]:
    """``(window_s, busy_s, [[span, idle seconds], ...])``: each idle
    instant of each device goes to the innermost host span over it; busy
    and idle are averaged over the devices traced, as ``trace.reduce``
    does, so the seconds sum to the window less the busy time."""
    device, _ = _trace._events(pd)
    if not device:
        raise ValueError("trace holds no operation on a TPU device")
    spans = host_spans(pd)
    w0, w1 = window(spans)
    pieces = labels(spans, w0, w1)
    gaps: Dict[str, float] = collections.defaultdict(float)
    busy_ns = 0.0
    for evs in device.values():
        busy = _trace.merge([(max(a, w0), min(b, w1)) for _, a, b in evs
                             if min(b, w1) > max(a, w0)])
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        j = 0
        for a, b in idle:
            while j < len(pieces) and pieces[j][1] <= a:
                j += 1
            k = j
            while k < len(pieces) and pieces[k][0] < b:
                pa, pb, name = pieces[k]
                gaps[name] += min(b, pb) - max(a, pa)
                k += 1
    n = len(device)
    split = sorted(([k, v / n / 1e9] for k, v in gaps.items() if v > 0),
                   key=lambda kv: -kv[1])
    return (w1 - w0) / 1e9, busy_ns / n / 1e9, split


def trace_dir(cell_name: str) -> str:
    from perfbench import harness
    return os.path.join(harness.STATE, "trace", cell_name)


def mean_ms(run, name: str) -> Optional[float]:
    """Mean milliseconds of the span ``name`` per wave inside the traced
    window of ``run``; None without a trace or without such a span."""
    if run.trace is None:
        return None
    try:
        path = _trace.find_xplane(trace_dir(run.cell.name))
    except FileNotFoundError:
        return None
    got = durations_s(host_spans(_trace.load(path)), name)
    return sum(got) / len(got) * 1e3 if got else None


def summary(path: str) -> dict:
    pd = _trace.load(path)
    window_s, busy_s, split = idle_by_innermost(pd)
    spans = host_spans(pd)
    per_span = {}
    for name in sorted(spans):
        if name.startswith(PREFIX):
            got = durations_s(spans, name)
            per_span[name] = {"n": len(got), "total_s": sum(got),
                              "mean_ms": sum(got) / len(got) * 1e3
                              if got else None}
    return {"window_s": window_s, "busy_s": busy_s, "idle_gaps": split,
            "spans": per_span}


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 1:
        print(__doc__, file=sys.stderr)
        return 2
    print(json.dumps(summary(_trace.find_xplane(argv[0]))), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
