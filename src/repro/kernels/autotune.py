"""Autotuner for the fused InCRS SpMM kernels.

Sweeps ``(bm, bn, variant)`` for one prepared operand + RHS shape, picks
by *measured* microseconds with the cycle-level cost model of
``core.mesh_sim.fused_spmm_cost`` as the prior: every candidate is
predicted first, only the most promising few are measured, and each
winning config records its ``overhead_factor = measured / predicted`` —
the same predict -> measure -> report methodology the SUMMA compute
model uses (SNIPPETS.md; that exemplar lands at ~3.9x).

Tuned configs are persisted in a small disk cache
(``~/.cache/repro-autotune.json``, overridable via the
``REPRO_AUTOTUNE_CACHE`` env var) keyed by
``(padded_rows, n_sections, smax, section, n_cols, backend)`` — i.e. the
spec's prepared shape + the RHS width + where it runs. The cache is
versioned: bumping ``AUTOTUNE_VERSION`` (a kernel change that shifts the
performance landscape) invalidates every stored entry at load time.

``sparse.api.plan`` attaches a cached config to its ``MatmulPlan`` so
every ``spmm`` / ``Linear.apply`` / serve-engine call rides it, and
``ops.spmm(variant="auto")`` consults the same cache (falling back to
the cost model alone when no tuned entry exists).
"""
from __future__ import annotations

import dataclasses
import json
import logging
import os
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from ..analysis import kernel_check, vmem
from ..core.mesh_sim import (MXU_MACS, FusedKernelCost, MatchedKernelCost,
                             SpGEMMCost, fused_spmm_cost, index_match_cost)
from .incrs_spmm import (incrs_spmm, incrs_spmm_pipelined,
                         incrs_spmm_reuse, _resolve_row_tile)

log = logging.getLogger(__name__)

# Bump on any kernel change that shifts the performance landscape —
# invalidates every persisted tuning entry at load time.
AUTOTUNE_VERSION = 1

CACHE_ENV = "REPRO_AUTOTUNE_CACHE"

# Row-panel accumulator budget shared by the reuse/pipelined variants
# (bm x Np f32 held in VMEM for a whole row tile). Owned by
# ``analysis.vmem`` (the footprint model is the single source of truth);
# re-exported here under the historical name — ``ops`` uses this as its
# fallback gate so the two always agree.
PANEL_BYTES = vmem.PANEL_BYTES

# Cost-model cycles per second on each chip, keyed by
# ``jax.Device.device_kind``. One model cycle retires one 128x128 MXU's
# MACs (``mesh_sim.MXU_MACS``), so the rate is the chip's published bf16
# peak over the FLOPs of such a cycle. v5e: 197 TFLOP/s bf16 (Google Cloud
# documentation, "TPU v5e"). The model's HBM and VPU terms are still the
# constants of ``core.mesh_sim`` and await a calibration on the chip.
TPU_CLOCK_HZ: Dict[str, float] = {
    "TPU v5 lite": 197e12 / (2 * MXU_MACS),
}


def tpu_clock_hz() -> float:
    """Cost-model clock of the attached chip; an unknown kind is an
    error, never a default."""
    kind = jax.devices()[0].device_kind
    if kind not in TPU_CLOCK_HZ:
        raise ValueError(f"no cost-model clock for device kind {kind!r}; "
                         f"add its published peak to "
                         f"autotune.TPU_CLOCK_HZ")
    return TPU_CLOCK_HZ[kind]

# Interpret-mode wall cost is dominated by per-op Python dispatch, not
# cycles: model it as flat per-grid-step / per-expansion / per-dot costs
# (µs), calibrated against BENCH_kernels.json interpret rows.
_I_STEP_US = 500.0
_I_EXPAND_US = 400.0
_I_DOT_US = 90.0
# The matched/SpGEMM family has its own interpret-mode constants: its
# per-step overhead is far lower than the fused InCRS family's (no DMA
# emulation), its wall time scales with how many one-hot elements each
# step materializes (the (bm, rmax, R) compare tensors), and the merge
# pass additionally re-copies the full stripes array every step
# (``MatchedKernelCost.interp_copy_bytes``). Fit against measured
# engine timings on the kernel_bench workloads (see the spgemm rows of
# BENCH_kernels.json).
_IM_STEP_US = 15.0
_IM_ELEM_US = 0.0007
_IM_COPY_US_PER_BYTE = 0.00017

# How many candidates (in cost-model order) get measured per sweep.
MEASURE_TOP_K = 4

_KERNELS = {"expand": incrs_spmm, "reuse": incrs_spmm_reuse,
            "pipelined": incrs_spmm_pipelined}


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class TunedConfig:
    """One winning kernel configuration with its prediction audit trail.

    ``rounds`` is only meaningful for the matched family (the index-match /
    SpGEMM kernels, where the round window R is itself tuned); 0 = n/a for
    the fused InCRS family."""
    variant: str
    bm: int
    bn: int
    measured_us: float
    predicted_us: float
    rounds: int = 0

    @property
    def overhead_factor(self) -> float:
        """measured / predicted — how much slower reality is than the
        pure cost model (SUMMA-compute-model style)."""
        if self.predicted_us <= 0:
            return float("inf")
        return self.measured_us / self.predicted_us

    def to_json(self) -> dict:
        return dataclasses.asdict(self)

    @staticmethod
    def from_json(d: dict) -> "TunedConfig":
        return TunedConfig(str(d["variant"]), int(d["bm"]), int(d["bn"]),
                           float(d["measured_us"]), float(d["predicted_us"]),
                           int(d.get("rounds", 0)))


def backend_name(interpret: bool) -> str:
    return "interpret" if interpret else jax.default_backend()


def cache_key(padded_rows: int, n_sections: int, smax: int, section: int,
              n_cols: int, backend: str) -> str:
    """Tuning-cache key: prepared-operand shape + RHS width + backend."""
    return (f"m{padded_rows}.sec{n_sections}x{section}.w{smax}"
            f".n{n_cols}.{backend}")


def matched_cache_key(m: int, n: int, k: int, backend: str) -> str:
    """Tuning-cache key for the matched family (index-match / SpGEMM):
    logical problem shape + backend. The round window R is part of the
    tuned *result* (``TunedConfig.rounds``), not the key — retuning the
    same shape reconsiders every R."""
    return f"im.m{m}.n{n}.k{k}.{backend}"


# ----------------------------------------------------------------------
# Disk-backed cache with versioned invalidation.
_MEM: Dict[str, TunedConfig] = {}


def cache_path() -> str:
    override = os.environ.get(CACHE_ENV)
    if override:
        return override
    return os.path.join(os.path.expanduser("~"), ".cache",
                        "repro-autotune.json")


def _load_disk() -> Dict[str, dict]:
    try:
        with open(cache_path()) as f:
            blob = json.load(f)
    except (OSError, ValueError):
        return {}
    if not isinstance(blob, dict) or \
            blob.get("version") != AUTOTUNE_VERSION:
        return {}                      # versioned invalidation
    entries = blob.get("entries")
    return entries if isinstance(entries, dict) else {}


def _store_disk(key: str, cfg: TunedConfig) -> None:
    path = cache_path()
    entries = _load_disk()
    entries[key] = cfg.to_json()
    payload = {"version": AUTOTUNE_VERSION, "entries": entries}
    try:
        os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
        fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path) or ".",
                                   prefix=".autotune-")
        with os.fdopen(fd, "w") as f:
            json.dump(payload, f, indent=1, sort_keys=True)
        os.replace(tmp, path)          # atomic: readers never see a torn file
    except OSError:
        pass                           # read-only FS: tuning still works


def lookup(key: str) -> Optional[TunedConfig]:
    """In-memory first, then disk (populating memory on a hit)."""
    hit = _MEM.get(key)
    if hit is not None:
        return hit
    raw = _load_disk().get(key)
    if raw is None:
        return None
    try:
        cfg = TunedConfig.from_json(raw)
    except (KeyError, TypeError, ValueError):
        return None
    _MEM[key] = cfg
    return cfg


def clear_memory_cache() -> None:
    """Forget in-process tuning state (tests; does not touch the disk)."""
    _MEM.clear()
    _logged.clear()


def cached_configs() -> Dict[str, TunedConfig]:
    """Every persisted tuning entry (disk merged under in-memory wins),
    keyed by ``cache_key`` string. The serve scheduler reads this to seed
    its µs/col cost model from real measurements instead of guessing."""
    out: Dict[str, TunedConfig] = {}
    for key, raw in _load_disk().items():
        try:
            out[key] = TunedConfig.from_json(raw)
        except (KeyError, TypeError, ValueError):
            continue
    out.update(_MEM)
    return out


def parse_cache_key(key: str) -> Optional[dict]:
    """Invert ``cache_key``: ``m{rows}.sec{ns}x{sec}.w{smax}.n{cols}.{be}``
    -> a dict of its fields, or None for a malformed key."""
    parts = key.split(".")
    if len(parts) < 5:
        return None
    m_s, sec_s, w_s, n_s = parts[0], parts[1], parts[2], parts[3]
    backend = ".".join(parts[4:])
    try:
        if not (m_s.startswith("m") and sec_s.startswith("sec")
                and w_s.startswith("w") and n_s.startswith("n")):
            return None
        ns_s, section_s = sec_s[3:].split("x")
        return {"padded_rows": int(m_s[1:]), "n_sections": int(ns_s),
                "section": int(section_s), "smax": int(w_s[1:]),
                "n_cols": int(n_s[1:]), "backend": backend}
    except ValueError:
        return None


# ----------------------------------------------------------------------
# Cost-model prior.
def predict_us(variant: str, m: int, n: int, *, n_sections: int, smax: int,
               section: int, bm: int, bn: int, interpret: bool) -> float:
    """Predicted wall µs for one launch from the cycle model alone."""
    cost = fused_spmm_cost(variant, m, n, n_sections=n_sections, smax=smax,
                           section=section, bm=bm, bn=bn)
    if interpret:
        return (cost.grid_steps * _I_STEP_US
                + cost.expansions * _I_EXPAND_US
                + cost.dots * _I_DOT_US)
    return cost.cycles / tpu_clock_hz() * 1e6


def engine_predict_us(cost: MatchedKernelCost, interpret: bool) -> float:
    """Predicted wall µs of one matched-family engine launch (fused
    index-match, condense/merge, or gather-densify) from its cycle
    breakdown."""
    if interpret:
        return (cost.grid_steps * _IM_STEP_US
                + cost.expand_elems * _IM_ELEM_US
                + cost.interp_copy_bytes * _IM_COPY_US_PER_BYTE)
    return cost.cycles / tpu_clock_hz() * 1e6


def predict_matched_us(m: int, n: int, *, rounds: int, n_rounds: int,
                       rmax_a: int, rmax_b: int, bm: int, bn: int,
                       interpret: bool) -> float:
    """Predicted wall µs of one fused ``index_match_spmm`` launch."""
    return engine_predict_us(
        index_match_cost(m, n, rounds=rounds, n_rounds=n_rounds,
                         rmax_a=rmax_a, rmax_b=rmax_b, bm=bm, bn=bn),
        interpret)


def pick_spgemm_engine(cost: SpGEMMCost, interpret: bool) -> str:
    """The SpGEMM auto-dispatch decision — fused one-pass vs condense/
    merge vs densify, by predicted wall time on THIS backend (TPU uses
    modelled cycles, the interpreter its per-step/per-element µs model,
    which knows about the merge pass's per-step stripe re-copy). One-time
    log explains the pick per cost signature."""
    us = {"condense_merge": engine_predict_us(cost.spgemm, interpret),
          "reference": engine_predict_us(cost.fused, interpret),
          "densify": engine_predict_us(cost.densify, interpret)}
    pick = min(us, key=us.get)
    sig = ("spgemm", cost.spgemm.grid_steps, cost.densify.grid_steps,
           interpret)
    if sig not in _logged:
        _logged.add(sig)
        log.info("spmm auto (sparse RHS): picked %r "
                 "(predicted µs: fused=%.0f condense_merge=%.0f "
                 "densify=%.0f)",
                 pick, us["reference"], us["condense_merge"],
                 us["densify"])
    return pick


def kernel_cost(variant: str, m: int, n: int, *, n_sections: int,
                smax: int, section: int, bm: int, bn: int,
                nnz: int | None = None) -> FusedKernelCost:
    """Cycle breakdown for roofline reporting (re-export of the oracle)."""
    return fused_spmm_cost(variant, m, n, n_sections=n_sections, smax=smax,
                           section=section, bm=bm, bn=bn, nnz=nnz)


def candidate_space(padded_rows: int, n: int) -> List[Tuple[str, int, int]]:
    """The raw ``(variant, bm, bn)`` sweep space for one problem, before
    any feasibility filtering."""
    bms, seen = [], set()
    for bm in (32, 64, 128, 256):
        eff, _ = _resolve_row_tile(padded_rows, bm)
        if eff not in seen:
            seen.add(eff)
            bms.append(eff)
    np128 = -(-n // 128) * 128
    bns = sorted({min(bn, np128) for bn in (128, 256, 512)})
    return [(variant, bm, bn)
            for bm in bms for bn in bns
            for variant in ("expand", "reuse", "pipelined")]


def split_candidates(padded_rows: int, n: int, *, section: int,
                     n_sections: int, smax: Optional[int] = None,
                     vmem_budget: Optional[int] = None
                     ) -> Tuple[List[Tuple[str, int, int]], List[dict]]:
    """Partition the sweep space into (feasible, skipped_infeasible)
    through the static checker of ``analysis.kernel_check``: the
    row-panel working-set heuristic, the hard VMEM budget, and the grid
    interpreter's interval bounds proof (out-of-bounds index arithmetic
    at this exact geometry). Each skip records the violated rule/term so
    the sweep result can show *why* a candidate was never measured."""
    feasible: List[Tuple[str, int, int]] = []
    skipped: List[dict] = []
    eff_smax = section if smax is None else smax
    for variant, bm, bn in candidate_space(padded_rows, n):
        vs = kernel_check.check_incrs_config(
            variant, m=padded_rows, n=n, bm=bm, bn=bn,
            n_sections=n_sections, smax=eff_smax, section=section,
            budget=vmem_budget, rules=kernel_check.LAUNCH_RULES)
        if vs:
            v = vs[0]
            skipped.append({"variant": variant, "bm": bm, "bn": bn,
                            "rule": v.rule, "term": v.term,
                            "bytes": v.nbytes, "limit": v.limit,
                            "message": v.message})
        else:
            feasible.append((variant, bm, bn))
    return feasible, skipped


def candidates(padded_rows: int, n: int, *, section: int,
               n_sections: int, smax: Optional[int] = None,
               vmem_budget: Optional[int] = None
               ) -> List[Tuple[str, int, int]]:
    """Feasible ``(variant, bm, bn)`` sweep space for one problem."""
    return split_candidates(padded_rows, n, section=section,
                            n_sections=n_sections, smax=smax,
                            vmem_budget=vmem_budget)[0]


# Round windows the matched-family sweep considers: the paper's R=32, the
# TPU lane-aligned 128, and the midpoint.
MATCHED_ROUNDS: Tuple[int, ...] = (32, 64, 128)


def matched_candidate_space(m: int, n: int,
                            rounds_options: Tuple[int, ...] = MATCHED_ROUNDS
                            ) -> List[Tuple[int, int, int]]:
    """The raw ``(rounds, bm, bn)`` sweep space for one index-match /
    SpGEMM problem, before feasibility filtering. Tiles are capped at the
    (8/128-aligned) padded operand extents — a 16-row problem never sweeps
    bm=256."""
    bms = sorted({min(bm, -(-m // 8) * 8) for bm in (32, 64, 128, 256)})
    bns = sorted({min(bn, -(-n // 128) * 128) for bn in (128, 256)})
    return [(r, bm, bn)
            for r in rounds_options for bm in bms for bn in bns]


# ----------------------------------------------------------------------
def _measure_us(fn, reps: int) -> float:
    jax.block_until_ready(fn())        # compile / warm caches
    best = float("inf")
    for _ in range(max(1, reps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


@dataclasses.dataclass
class SweepRecord:
    """Audit trail of one autotune sweep: what was considered, what the
    static VMEM prefilter rejected (and why), what got measured."""
    key: str
    cache_hit: bool
    n_candidates: int
    skipped_infeasible: List[dict]
    measured: List[dict]
    elapsed_s: float
    winner: Optional[TunedConfig]

    def to_json(self) -> dict:
        return {"key": self.key, "cache_hit": self.cache_hit,
                "n_candidates": self.n_candidates,
                "skipped_infeasible": self.skipped_infeasible,
                "measured": self.measured, "elapsed_s": self.elapsed_s,
                "winner": self.winner.to_json() if self.winner else None}


# Sweep record of the most recent ``tune`` call (tests / kernel_bench).
LAST_SWEEP: Optional[SweepRecord] = None


def tune(idx, val, b, *, section: int, interpret: bool,
         reps: int = 3, persist: bool = True,
         top_k: int = MEASURE_TOP_K,
         vmem_budget: Optional[int] = None,
         prefilter: bool = True) -> TunedConfig:
    """Sweep ``(variant, bm, bn)`` for one prepared operand + RHS.

    Cache hit -> returns the stored config without running anything.
    Miss -> statically drop VMEM-infeasible candidates (recorded as
    ``skipped_infeasible`` on the ``LAST_SWEEP`` record — they are
    never measured), rank the rest by the cost model, measure the
    ``top_k`` most promising, keep the fastest, persist it.

    ``vmem_budget`` overrides the hard per-core budget (bytes);
    ``prefilter=False`` disables the static filter entirely (the
    before/after baseline for ``kernel_bench``'s ``autotune_prefilter``
    comparison).
    """
    global LAST_SWEEP
    t_sweep = time.perf_counter()
    n_sections, m, smax = idx.shape
    n = b.shape[1]
    key = cache_key(m, n_sections, smax, section, n,
                    backend_name(interpret))
    hit = lookup(key)
    if hit is not None:
        LAST_SWEEP = SweepRecord(key, True, 0, [], [],
                                 time.perf_counter() - t_sweep, hit)
        return hit

    if prefilter:
        cands, skipped = split_candidates(
            m, n, section=section, n_sections=n_sections, smax=smax,
            vmem_budget=vmem_budget)
    else:
        cands, skipped = candidate_space(m, n), []
    if not cands:
        raise kernel_check.KernelConfigError(
            [kernel_check.Violation(s["rule"], s["message"], s["term"],
                                    s["bytes"], s["limit"])
             for s in skipped[:3]],
            context=f"autotune {key}: no feasible candidate under the "
                    f"VMEM budget")
    ranked = sorted(
        cands,
        key=lambda c: predict_us(c[0], m, n, n_sections=n_sections,
                                 smax=smax, section=section, bm=c[1],
                                 bn=c[2], interpret=interpret))
    best_cfg: Optional[TunedConfig] = None
    measured_log: List[dict] = []
    for variant, bm, bn in ranked[:max(1, top_k)]:
        predicted = predict_us(variant, m, n, n_sections=n_sections,
                               smax=smax, section=section, bm=bm, bn=bn,
                               interpret=interpret)
        kp = n_sections * section
        np_ = -(-n // bn) * bn
        bp = jnp.pad(b, ((0, kp - b.shape[0]), (0, np_ - n)))
        kern = _KERNELS[variant]
        measured = _measure_us(
            lambda: kern(idx, val, bp, section=section, bm=bm, bn=bn,
                         interpret=interpret), reps)
        measured_log.append({"variant": variant, "bm": bm, "bn": bn,
                             "us": measured, "predicted_us": predicted})
        cfg = TunedConfig(variant, bm, bn, measured, predicted)
        if best_cfg is None or cfg.measured_us < best_cfg.measured_us:
            best_cfg = cfg
    assert best_cfg is not None  # lint: allow-assert (ranked is non-empty)
    _MEM[key] = best_cfg
    LAST_SWEEP = SweepRecord(key, False, len(cands) + len(skipped),
                             skipped, measured_log,
                             time.perf_counter() - t_sweep, best_cfg)
    if persist:
        _store_disk(key, best_cfg)
    log.info("autotune: %s -> %s bm=%d bn=%d (measured %.0fµs, predicted "
             "%.0fµs, overhead %.2fx)", key, best_cfg.variant, best_cfg.bm,
             best_cfg.bn, best_cfg.measured_us, best_cfg.predicted_us,
             best_cfg.overhead_factor)
    return best_cfg


def tune_index_match(a, bt, *, interpret: bool, reps: int = 3,
                     persist: bool = True, top_k: int = MEASURE_TOP_K,
                     rounds_options: Tuple[int, ...] = MATCHED_ROUNDS
                     ) -> TunedConfig:
    """Sweep ``(rounds, bm, bn)`` for one CRS x CRS matched-family problem
    (``a @ bt.T``, both row-stored sparse).

    Same protocol as ``tune``: cache hit returns immediately; otherwise
    statically drop infeasible candidates (VMEM / bounds via
    ``check_matched_config``), rank the rest by the cycle-model prior,
    measure the ``top_k`` most promising through the fused kernel (prep
    re-done per candidate — rounds changes the prepped layout), keep the
    fastest, persist under ``matched_cache_key``. The winner's round
    window lands in ``TunedConfig.rounds``; ``ops.spmm`` picks it up for
    every later call at this shape.
    """
    global LAST_SWEEP
    from . import ops as _ops               # circular at module scope
    from ..core import mesh_sim as _ms
    t_sweep = time.perf_counter()
    m, k = a.shape
    n = bt.shape[0]
    key = matched_cache_key(m, n, k, backend_name(interpret))
    hit = lookup(key)
    if hit is not None:
        LAST_SWEEP = SweepRecord(key, True, 0, [], [],
                                 time.perf_counter() - t_sweep, hit)
        return hit

    rmax_of = {r: (max(1, int(_ms._round_lengths(a, r).max(initial=1))),
                   max(1, int(_ms._round_lengths(bt, r).max(initial=1))))
               for r in rounds_options}
    cands: List[Tuple[int, int, int]] = []
    skipped: List[dict] = []
    for r, bm, bn in matched_candidate_space(m, n, rounds_options):
        n_rounds = max(1, -(-k // r))
        rmax_a, rmax_b = rmax_of[r]
        rmax = max(rmax_a, rmax_b)          # prepped pads to common rmax
        vs = kernel_check.check_matched_config(
            "index_match", m=-(-m // bm) * bm, n=-(-n // bn) * bn,
            bm=bm, bn=bn, rounds=r, n_rounds=n_rounds,
            rmax_a=rmax, rmax_b=rmax, rules=kernel_check.LAUNCH_RULES)
        if vs:
            v = vs[0]
            skipped.append({"rounds": r, "bm": bm, "bn": bn,
                            "rule": v.rule, "term": v.term,
                            "bytes": v.nbytes, "limit": v.limit,
                            "message": v.message})
        else:
            cands.append((r, bm, bn))
    if not cands:
        raise kernel_check.KernelConfigError(
            [kernel_check.Violation(s["rule"], s["message"], s["term"],
                                    s["bytes"], s["limit"])
             for s in skipped[:3]],
            context=f"autotune {key}: no feasible candidate under the "
                    f"VMEM budget")

    def _predict(c):
        r, bm, bn = c
        rmax = max(rmax_of[r])
        return predict_matched_us(
            -(-m // bm) * bm, -(-n // bn) * bn, rounds=r,
            n_rounds=max(1, -(-k // r)), rmax_a=rmax, rmax_b=rmax,
            bm=bm, bn=bn, interpret=interpret)

    ranked = sorted(cands, key=_predict)
    best_cfg: Optional[TunedConfig] = None
    measured_log: List[dict] = []
    for r, bm, bn in ranked[:max(1, top_k)]:
        predicted = _predict((r, bm, bn))
        ai, av = _ops.prep_rounds(a, r, pad_rows_to=bm)
        bi, bv = _ops.prep_rounds(bt, r, pad_rows_to=bn)
        measured = _measure_us(
            lambda: _ops.index_match_prepped(ai, av, bi, bv, rounds=r,
                                             bm=bm, bn=bn,
                                             interpret=interpret), reps)
        measured_log.append({"rounds": r, "bm": bm, "bn": bn,
                             "us": measured, "predicted_us": predicted})
        cfg = TunedConfig("index_match", bm, bn, measured, predicted,
                          rounds=r)
        if best_cfg is None or cfg.measured_us < best_cfg.measured_us:
            best_cfg = cfg
    assert best_cfg is not None  # lint: allow-assert (ranked is non-empty)
    _MEM[key] = best_cfg
    LAST_SWEEP = SweepRecord(key, False, len(cands) + len(skipped),
                             skipped, measured_log,
                             time.perf_counter() - t_sweep, best_cfg)
    if persist:
        _store_disk(key, best_cfg)
    log.info("autotune: %s -> rounds=%d bm=%d bn=%d (measured %.0fµs, "
             "predicted %.0fµs, overhead %.2fx)", key, best_cfg.rounds,
             best_cfg.bm, best_cfg.bn, best_cfg.measured_us,
             best_cfg.predicted_us, best_cfg.overhead_factor)
    return best_cfg


# ----------------------------------------------------------------------
# Model-only variant pick (ops.spmm variant="auto" with no tuned entry).
_logged: set = set()


def model_pick_variant(m: int, n: int, *, n_sections: int, smax: int,
                       section: int, bm: int, bn: int,
                       interpret: bool) -> str:
    """Choose a variant from the cost model alone (no measurement), with
    a one-time log line explaining the pick for this problem shape."""
    bm, _ = _resolve_row_tile(m, bm)   # same clamp the kernels apply
    allowed = [v for v in ("expand", "reuse", "pipelined")
               if not kernel_check.check_incrs_config(
                   v, m=m, n=n, bm=bm, bn=bn, n_sections=n_sections,
                   smax=smax, section=section,
                   rules=kernel_check.LAUNCH_RULES)]
    if not allowed:
        allowed = ["expand"]           # smallest footprint: last resort
    scored = {v: predict_us(v, m, n, n_sections=n_sections, smax=smax,
                            section=section, bm=bm, bn=bn,
                            interpret=interpret)
              for v in allowed}
    pick = min(scored, key=scored.get)
    sig = (m, n, n_sections, smax, section, bm, bn, interpret)
    if sig not in _logged:
        _logged.add(sig)
        log.info(
            "spmm auto (no tuned entry): picked %r for m=%d n=%d "
            "(predicted µs: %s)", pick, m, n,
            ", ".join(f"{v}={u:.0f}" for v, u in sorted(scored.items())))
    return pick
