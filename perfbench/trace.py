"""Reduce a profiler trace of one measured window to device metrics.

The harness wraps the window in a host span named ``bench.window`` and each
call into the program in spans of its own (``engine.submit``,
``engine.step``, ``client.idle``). The device planes hold one event per
XLA operation with its start and duration on the same clock. From them:

- busy: the union of the operation intervals inside the window;
- kernel time: the summed device time of the Pallas kernels, matched by
  kind (a ``tpu_custom_call``), not by name, so a renamed or replacement
  kernel stays counted;
- the operations that took most time, by short name and output shape;
- the idle gaps, attributed to the host span that overlaps them.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_SPAN = "bench.window"
HOST_SPANS = ("engine.submit", "engine.step", "client.idle")
KERNEL_MARK = 'custom_call_target="tpu_custom_call"'
TOP = 10

Interval = Tuple[float, float]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                  # averaged over the chips traced
    kernel_s: float                # Pallas kernels, averaged over chips
    kernel_calls: int
    device_ops: List[list]         # [[short name, seconds], ...]
    idle_gaps: List[list]          # [[host span, seconds], ...]


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def short_name(hlo: str) -> str:
    """``%op.1 = f32[14336,1024]{1,0:T(8,128)} custom-call(...)`` ->
    ``op.1 f32[14336,1024]``."""
    lhs, sep, rhs = hlo.partition(" = ")
    name = lhs.strip().lstrip("%")
    if not sep:
        return name
    shape = re.sub(r"\{[^}]*\}", "", rhs.split(" ", 1)[0])
    return f"{name} {shape}"


def merge(intervals: Sequence[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def _clip(iv: Interval, lo: float, hi: float) -> Optional[Interval]:
    a, b = max(iv[0], lo), min(iv[1], hi)
    return (a, b) if b > a else None


def _overlap(a: Interval, spans: Sequence[Interval]) -> float:
    return sum(max(0.0, min(a[1], s[1]) - max(a[0], s[0])) for s in spans)


def _events(pd):
    """(device planes -> [(name, start_ns, end_ns)]), (host span name ->
    [(start_ns, end_ns)])."""
    device: Dict[str, list] = {}
    host: Dict[str, list] = collections.defaultdict(list)
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == "XLA Ops"
                   for e in line.events]
            if evs:
                device[plane.name] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN or e.name in HOST_SPANS:
                        host[e.name].append((e.start_ns,
                                             e.start_ns + e.duration_ns))
    return device, host


def reduce(pd, kernel_until_s: Optional[float] = None) -> Summary:
    """Reduce a ``jax.profiler.ProfileData``. Kernel time counts kernels
    that start inside the window and, when ``kernel_until_s`` is given, no
    later than that many seconds after the window opened (so that it covers
    the same waves as the work it is set against)."""
    device, host = _events(pd)
    if not host.get(WINDOW_SPAN):
        raise ValueError(f"trace holds no {WINDOW_SPAN!r} span")
    if not device:
        raise ValueError("trace holds no operation on a TPU device")
    w0, w1 = max(host[WINDOW_SPAN], key=lambda iv: iv[1] - iv[0])
    k1 = w1 if kernel_until_s is None else min(w1, w0 + kernel_until_s * 1e9)
    n = len(device)
    busy_ns = kernel_ns = 0.0
    kernel_calls = 0
    per_op: Dict[str, float] = collections.defaultdict(float)
    gaps: Dict[str, float] = collections.defaultdict(float)
    spans = {k: merge(v) for k, v in host.items() if k != WINDOW_SPAN}
    for evs in device.values():
        inside = []
        for name, a, b in evs:
            iv = _clip((a, b), w0, w1)
            if iv is None:
                continue
            inside.append(iv)
            per_op[short_name(name)] += iv[1] - iv[0]
            if KERNEL_MARK in name and w0 <= a <= k1:
                kernel_ns += b - a
                kernel_calls += 1
        busy = merge(inside)
        busy_ns += sum(b - a for a, b in busy)
        edges = [w0] + [x for iv in busy for x in iv] + [w1]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            rest = b - a
            for name, ivs in spans.items():
                got = _overlap((a, b), ivs)
                gaps[name] += got
                rest -= got
            gaps["untraced"] += max(0.0, rest)
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]
    idle = sorted(((k, v) for k, v in gaps.items() if v > 0),
                  key=lambda kv: -kv[1])[:TOP]
    return Summary(
        window_s=(w1 - w0) / 1e9, busy_s=busy_ns / n / 1e9,
        kernel_s=kernel_ns / n / 1e9, kernel_calls=kernel_calls,
        device_ops=[[k, v / n / 1e9] for k, v in top],
        idle_gaps=[[k, v / n / 1e9] for k, v in idle])


def load(path: str):
    from jax.profiler import ProfileData
    return ProfileData.from_file(path)
