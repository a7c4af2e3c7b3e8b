"""Cost-model wave packing for the continuous SpMM serving engine.

The old engine packed waves by a FIXED column count (``max_wave_cols``):
one size had to fit every operand and every machine, and the FIFO scan
stopped at the first request that didn't fit, so one wide request at the
head starved narrower queued requests that would have packed into the
same wave. This module replaces both decisions with measured data:

* :class:`WaveCostModel` — an affine per-launch wall-time estimate
  ``us(cols) = launch_overhead_us + us_per_col * cols``, seeded from the
  autotuner's persisted measurements (``kernels.autotune`` disk cache —
  its keys encode the operand geometry AND the RHS width, its entries
  carry measured µs) or from a committed ``BENCH_kernels.json`` record,
  then refined online by an EWMA over every retired wave. The paper's
  streaming claim is that the mesh is fed continuously because the
  schedule knows the cost of the next step; this is that cost.
* :class:`WavePacker` — turns a LATENCY BUDGET into a wave width through
  the cost model (``target_cols``), and packs the queue up to that width
  with a bounded skip-scan (head-of-line requests that don't fit are
  bypassed, at most ``skip_limit`` per wave, original order preserved)
  so mixed-width queues pack densely without starving anyone.

Both classes are engine-agnostic: they see only objects with a
``b.shape[1]`` column count, so tests drive them with plain stubs.
"""
from __future__ import annotations

import dataclasses
import json
from collections import deque
from typing import Deque, List, Optional, Sequence, Tuple

# Requests narrower than this never make the target smaller: a pathological
# µs/col estimate must not shrink waves below one useful kernel tile.
MIN_TARGET_COLS = 8

# Default bound on how many queued requests one wave may bypass. Small on
# purpose: the scan stays O(wave + skip_limit) and a bypassed request is
# re-examined at the very next wave (it is still at the front).
DEFAULT_SKIP_LIMIT = 8

# EWMA weight of a fresh observation (higher = adapt faster, noisier).
DEFAULT_EWMA = 0.25


def fit_us_per_col(pairs: Sequence[Tuple[int, float]]
                   ) -> Tuple[Optional[float], float]:
    """Fit ``us(cols) = overhead + slope * cols`` to measured
    ``(cols, us)`` points. Returns ``(us_per_col, launch_overhead_us)``;
    ``(None, 0.0)`` when nothing usable was given.

    One point pins the slope through the origin (overhead 0 — a
    conservative over-estimate of µs/col, so packing starts cautious);
    two or more points get a least-squares line with the intercept
    clamped to >= 0 and the slope to > 0 (a non-increasing fit falls
    back to the through-origin estimate of the widest point).
    """
    pts = [(int(c), float(u)) for c, u in pairs if c > 0 and u > 0]
    if not pts:
        return None, 0.0
    if len(pts) == 1:
        c, u = pts[0]
        return u / c, 0.0
    n = len(pts)
    mx = sum(c for c, _ in pts) / n
    my = sum(u for _, u in pts) / n
    sxx = sum((c - mx) ** 2 for c, _ in pts)
    sxy = sum((c - mx) * (u - my) for c, u in pts)
    if sxx <= 0 or sxy <= 0:
        c, u = max(pts)
        return u / c, 0.0
    slope = sxy / sxx
    intercept = max(0.0, my - slope * mx)
    return slope, intercept


@dataclasses.dataclass
class WaveCostModel:
    """Affine launch-cost estimate, seeded offline and refined online.

    ``us_per_col`` is None until either a seed or the first observed wave
    provides one; callers treat that as "no estimate — use the hard cap".
    """
    us_per_col: Optional[float] = None
    launch_overhead_us: float = 0.0
    ewma: float = DEFAULT_EWMA
    n_observed: int = 0
    source: str = "unseeded"

    def predict_us(self, cols: int) -> Optional[float]:
        """Predicted wall µs of one ``cols``-wide wave (None = no data)."""
        if self.us_per_col is None:
            return None
        return self.launch_overhead_us + self.us_per_col * max(0, cols)

    def target_cols(self, budget_us: Optional[float], hard_cap: int) -> int:
        """The widest wave predicted to finish inside ``budget_us``,
        clamped to ``[MIN_TARGET_COLS, hard_cap]`` (the cap is the shape
        the engine's static feasibility check proved — the budget may
        only narrow it, never widen it)."""
        if budget_us is None or self.us_per_col is None \
                or self.us_per_col <= 0:
            return hard_cap
        fit = int((budget_us - self.launch_overhead_us) / self.us_per_col)
        return max(MIN_TARGET_COLS, min(hard_cap, fit))

    def observe(self, cols: int, wall_us: float) -> None:
        """Fold one retired wave's measured wall time into the estimate."""
        if cols <= 0 or wall_us <= 0:
            return
        obs = max(0.0, wall_us - self.launch_overhead_us) / cols
        if obs <= 0:
            return
        if self.us_per_col is None:
            self.us_per_col = obs
        else:
            self.us_per_col = (1.0 - self.ewma) * self.us_per_col \
                + self.ewma * obs
        self.n_observed += 1


# ----------------------------------------------------------------------
# Offline seeds: the measurements this repo already persists.
def seed_from_autotune(padded_rows: int, n_sections: int, smax: int,
                       section: int, backend: str) -> WaveCostModel:
    """Seed a cost model from the autotuner's persisted sweeps for THIS
    operand geometry: every cache entry whose key matches
    ``(padded_rows, n_sections, smax, section, backend)`` contributes a
    measured ``(n_cols, us)`` point. Unseeded model if none match."""
    from ..kernels import autotune
    pairs = []
    for key, cfg in autotune.cached_configs().items():
        parsed = autotune.parse_cache_key(key)
        if parsed is None:
            continue
        if (parsed["padded_rows"], parsed["n_sections"], parsed["smax"],
                parsed["section"], parsed["backend"]) != \
                (padded_rows, n_sections, smax, section, backend):
            continue
        pairs.append((parsed["n_cols"], cfg.measured_us))
    slope, overhead = fit_us_per_col(pairs)
    if slope is None:
        return WaveCostModel()
    return WaveCostModel(slope, overhead,
                         source=f"autotune[{len(pairs)} pts]")


def seed_from_bench(path: str, backend: str) -> WaveCostModel:
    """Seed a cost model from a committed ``BENCH_kernels.json``: fused
    InCRS rows record their measured µs and RHS width (``cols=N`` in the
    ``derived`` field) — the cheapest µs/col across them is a usable
    machine-level prior even when the operand geometry differs. A record
    measured on another ``backend`` (e.g. the CPU interpreter, for a TPU
    engine) seeds nothing."""
    try:
        with open(path) as f:
            record = json.load(f)
    except (OSError, ValueError):
        return WaveCostModel()
    # The record's backend, named as autotune.backend_name names it.
    measured_on = "interpret" if record.get("interpret") \
        else record.get("backend")
    if measured_on != backend:
        return WaveCostModel()
    best: Optional[float] = None
    for row in record.get("rows", []):
        name = str(row.get("name", ""))
        derived = str(row.get("derived", ""))
        if not name.startswith("incrs_spmm") or "cols=" not in derived:
            continue
        try:
            cols = int(derived.split("cols=")[1].split(";")[0])
            us = float(row["us"])
        except (KeyError, IndexError, ValueError):
            continue
        if cols > 0 and us > 0:
            per = us / cols
            best = per if best is None else min(best, per)
    if best is None:
        return WaveCostModel()
    return WaveCostModel(best, 0.0, source=f"bench[{path}]")


def seed_cost_model(padded_rows: Optional[int] = None,
                    n_sections: Optional[int] = None,
                    smax: Optional[int] = None,
                    section: Optional[int] = None,
                    backend: str = "interpret",
                    bench_path: Optional[str] = None) -> WaveCostModel:
    """Best available offline seed for ``backend``: exact-geometry
    autotune measurements first, a bench record measured on the same
    backend next, unseeded last (the first retired wave then provides the
    estimate)."""
    if None not in (padded_rows, n_sections, smax, section):
        model = seed_from_autotune(padded_rows, n_sections, smax, section,
                                   backend)
        if model.us_per_col is not None:
            return model
    if bench_path is not None:
        model = seed_from_bench(bench_path, backend)
        if model.us_per_col is not None:
            return model
    return WaveCostModel()


# ----------------------------------------------------------------------
@dataclasses.dataclass
class WavePacker:
    """Latency-aware wave packing over a deque of requests.

    ``budget_us`` — per-wave latency target; None = pack to the hard cap
    (the engine's proven ``max_wave_cols``), i.e. throughput mode.
    ``skip_limit`` — bounded head-of-line bypass: how many non-fitting
    requests one wave may scan past. 0 restores the strict-FIFO
    wave-barrier behaviour (stop at the first request that doesn't fit).
    """
    cost: WaveCostModel = dataclasses.field(default_factory=WaveCostModel)
    budget_us: Optional[float] = None
    skip_limit: int = DEFAULT_SKIP_LIMIT
    last_target: Optional[int] = None

    def target_cols(self, hard_cap: int) -> int:
        target = self.cost.target_cols(self.budget_us, hard_cap)
        self.last_target = target
        return target

    def next_wave(self, queue: Deque, hard_cap: int) -> List:
        """Pop the next wave off ``queue`` (mutating it): requests are
        admitted front-to-back while they fit the target width; at most
        ``skip_limit`` non-fitting requests are bypassed (and restored to
        the front in their original order). A head request wider than the
        dynamic target is admitted alone — the engine's admission split
        guarantees every queued request fits the hard cap."""
        if not queue:
            return []
        target = self.target_cols(hard_cap)
        wave: List = []
        bypassed: List = []
        cols = 0
        skips = 0
        while queue:
            req = queue.popleft()
            width = req.b.shape[1]
            if not wave and width >= target:
                wave.append(req)            # wide head: ship it alone
                cols += width
                break
            if cols + width <= target:
                wave.append(req)
                cols += width
            else:
                bypassed.append(req)
                skips += 1
                if skips >= max(0, self.skip_limit) + (0 if wave else 1):
                    break
        # Bypassed requests return to the FRONT, original order intact —
        # they are first in line for the very next wave (no starvation).
        queue.extendleft(reversed(bypassed))
        return wave

    def observe(self, cols: int, wall_us: float) -> None:
        self.cost.observe(cols, wall_us)


__all__ = [
    "WaveCostModel", "WavePacker", "fit_us_per_col", "seed_from_autotune",
    "seed_from_bench", "seed_cost_model", "MIN_TARGET_COLS",
    "DEFAULT_SKIP_LIMIT",
]
