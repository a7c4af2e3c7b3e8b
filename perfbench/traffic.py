"""The one traffic generator. A mix is a JSON file of parameters under
``perfbench/traffic/``; every mix goes through the functions here.

In an open loop, arrivals are a Poisson process drawn from the seed: the
gaps between them are independent exponential draws at the stated rate,
and each request's width is an independent draw from the stated
distribution. In a closed loop each client goes round the stated list of
widths in its order, starting a share of the list further on than the
client before it: the waves the engine packs from that do not depend on
the seed. In both, the seed picks where in the shared right-hand-side pool
each request's columns start, which is the data the system multiplies.

Parameters:

- ``loop``: ``"open"`` (requests are due on a schedule, whatever the
  system does) or ``"closed"`` (``clients`` callers, each sending its next
  request when the last one returned).
- ``rate_per_s`` (open): arrivals per second.
- ``clients`` (closed): number of callers.
- ``widths``: ``{"dist": "list", "values": [...]}`` or
  ``{"dist": "bounded_pareto", "min": a, "max": b, "alpha": s}``; the
  columns of one request's right-hand side.
- ``max_wave_cols``, ``variant``: the serving engine's settings.
- ``pool_cols``: width of the seeded right-hand-side pool that requests
  take their columns from.
- ``compare``: how many finished requests the correctness check samples
  (the widest is always among them).
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np


@dataclasses.dataclass(frozen=True)
class Request:
    rid: int
    client: int           # -1 in an open loop
    due_s: float          # offset from the window's start (open loop)
    width: int
    offset: int           # first column in the right-hand-side pool


def draw_widths(spec: dict, n: int, rng) -> np.ndarray:
    """``n`` independent widths: from the stated list, or from the bounded
    Pareto distribution by inversion."""
    dist = spec["dist"]
    if dist == "list":
        return rng.choice(np.asarray(spec["values"], dtype=np.int64), n)
    if dist == "bounded_pareto":
        lo, hi, a = float(spec["min"]), float(spec["max"]), \
            float(spec["alpha"])
        u = rng.random(n)
        x = lo / (1.0 - u * (1.0 - (lo / hi) ** a)) ** (1.0 / a)
        return np.clip(np.round(x), lo, hi).astype(np.int64)
    raise ValueError(f"unknown width distribution {dist!r}")


def all_widths(spec: dict) -> List[int]:
    if spec["dist"] == "list":
        return sorted(set(int(v) for v in spec["values"]))
    return list(range(int(spec["min"]), int(spec["max"]) + 1))


def open_schedule(traffic: dict, seed: int, seconds: float) -> List[Request]:
    """Requests due in ``[0, seconds)`` of a Poisson process at
    ``rate_per_s``, the first at 0."""
    rng = np.random.default_rng(seed)
    rate = float(traffic["rate_per_s"])
    # Enough gaps to pass the window's end with room to spare.
    n = int(rate * seconds + 10 * np.sqrt(rate * seconds) + 10)
    due = np.concatenate([[0.0], np.cumsum(rng.exponential(1.0 / rate,
                                                           n - 1))])
    due = due[due < seconds]
    widths = draw_widths(traffic["widths"], len(due), rng)
    pool = int(traffic["pool_cols"])
    offsets = rng.integers(0, pool - widths + 1)
    return [Request(i, -1, float(t), int(w), int(o))
            for i, (t, w, o) in enumerate(zip(due, widths, offsets))]


class ClosedClients:
    """Each client goes round the stated widths in order, client ``c``
    starting ``c / clients`` of the way into the list."""

    def __init__(self, traffic: dict, seed: int):
        self.n = int(traffic["clients"])
        self._cycle = [int(v) for v in traffic["widths"]["values"]]
        self.pool = int(traffic["pool_cols"])
        self._rng = [np.random.default_rng([seed, c]) for c in range(self.n)]
        self._sent = [0] * self.n

    def next(self, client: int) -> Request:
        """The client's next request; ``rid`` counts that client's
        requests, so ``(client, rid)`` names it whatever the timing."""
        j = self._sent[client]
        self._sent[client] += 1
        cyc = self._cycle
        w = cyc[(j + client * len(cyc) // self.n) % len(cyc)]
        return Request(j, client, 0.0, w,
                       int(self._rng[client].integers(0, self.pool - w + 1)))


def wave_widths(traffic: dict, quantum: int) -> List[int]:
    """Every wave width (bucketed to ``quantum``) this mix can launch: any
    packed sum up to the cap when more than one request can wait, else the
    parts of each single request."""
    cap = int(traffic["max_wave_cols"])
    bucket = lambda c: -(-c // quantum) * quantum  # noqa: E731
    widths = all_widths(traffic["widths"])
    packs = traffic["loop"] == "open" or int(traffic.get("clients", 1)) > 1
    if packs:
        return list(range(bucket(min(min(widths), cap)), cap + 1, quantum))
    out = set()
    for w in widths:
        full, rest = divmod(w, cap)
        if full:
            out.add(cap)
        if rest:
            out.add(bucket(rest))
    return sorted(out)
