"""Symbolic VMEM footprint model for every kernel family — the single
source of truth for "will this (variant, bm, bn) fit on a core?".

The paper's mesh architecture can prove its resource budgets (comparator
rows, stripe width, per-PE storage) *before* execution; this module is
the Pallas-port equivalent. Each builder below mirrors, term by term,
the actual ``BlockSpec`` block shapes + ``scratch_shapes`` of the kernel
it models (``kernels/incrs_spmm.py``, ``kernels/bsr_spmm.py``,
``kernels/dense_mm.py``), so a config can be rejected statically instead
of discovered at measure time in the autotune sweep — or as an OOM on
real hardware. ``analysis.kernel_check`` turns these footprints into
violations; ``kernels.autotune`` prefilters its candidate sweep with
them; ``benchmarks/roofline.py --kernels`` prints them per row.

Two budgets with different meanings:

* ``DEFAULT_VMEM_BUDGET`` (16 MiB, the physical per-core VMEM of a
  v4/v5-class TPU) — a HARD limit: a config whose total footprint
  exceeds it cannot run. Overridable per call or via the
  ``REPRO_VMEM_BUDGET`` env var.
* ``PANEL_BYTES`` (2 MiB) — the row-panel accumulator WORKING-SET
  budget shared by the reuse/pipelined variants (one ``bm x Np`` f32
  panel live for a whole row tile). This is a tuning heuristic, not a
  hard limit: exceeding it leaves too little VMEM headroom for the
  automatic pipeline to double-buffer well, so auto dispatch and the
  autotuner skip such configs, but an explicit caller may still run
  them (they remain legal as long as the hard budget holds).

Pure Python on purpose: no jax import, so the lint/CI gate and the
``python -m repro.analysis`` CLI stay fast and ``-O``-independent.
"""
from __future__ import annotations

import dataclasses
import math
import os
from typing import Dict, Optional, Tuple

# Hard physical budget: VMEM per TPU core (v4/v5-class, ~16 MB).
DEFAULT_VMEM_BUDGET = 16 * 1024 * 1024

# Env override for the hard budget (integer bytes).
VMEM_BUDGET_ENV = "REPRO_VMEM_BUDGET"

# Row-panel accumulator working-set budget shared by the reuse/pipelined
# variants. Lives here (not in kernels/autotune.py) so the checker, the
# autotuner and ops.spmm's auto-dispatch gate all agree on one number;
# autotune re-exports it under its historical name ``PANEL_BYTES``.
PANEL_BYTES = 2 * 1024 * 1024

# TPU f32 native tile granularity: (sublane, lane) = (8, 128).
SUBLANE = 8
LANE = 128

# The automatic Pallas pipeline double-buffers every in/out BlockSpec
# block (block t+1 is fetched while block t computes); scratch buffers
# are single-instance.
PIPELINE_BUFFERS = 2

INCRS_VARIANTS = ("expand", "reuse", "pipelined")

# Expected scratch_shapes signature per kernel entry point, derived
# from the footprint builders below. ``kernel_check.check_scratch_drift``
# parses the real kernel source and compares against this — if someone
# adds/removes a scratch buffer without updating the model, CI flags it.
# (Owning module per entry comes from ``grid_interp.GEOMETRIES``.)
EXPECTED_SCRATCH: Dict[str, Tuple[str, ...]] = {
    "incrs_spmm": ("VMEM",),
    "incrs_spmm_reuse": ("VMEM", "VMEM"),
    "incrs_spmm_pipelined": ("VMEM", "SemaphoreType.DMA", "VMEM"),
    "bsr_spmm": ("VMEM",),
    "dense_mm": ("VMEM",),
    "index_match_spmm": ("VMEM",),
    "flash_attention": ("VMEM", "VMEM", "VMEM"),
    "incrs_gather": (),
    "spgemm_condense": (),
    "spgemm_merge": ("VMEM",),
}


def vmem_budget(budget: Optional[int] = None) -> int:
    """Resolve the hard VMEM budget: explicit arg > env var > default."""
    if budget is not None:
        return int(budget)
    env = os.environ.get(VMEM_BUDGET_ENV)
    if env:
        try:
            return int(env)
        except ValueError:
            raise ValueError(
                f"{VMEM_BUDGET_ENV} must be an integer byte count, "
                f"got {env!r}")
    return DEFAULT_VMEM_BUDGET


def resolve_row_tile(m: int, bm: int) -> Tuple[int, int]:
    """Pure mirror of ``incrs_spmm._resolve_row_tile`` (no jax import):
    clamp ``bm`` to the sublane-rounded panel height, pad ``m`` up to a
    whole number of tiles. Returns ``(bm, padded_m)``."""
    bm = max(1, min(bm, -(-m // SUBLANE) * SUBLANE))
    return bm, -(-m // bm) * bm


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class VmemTerm:
    """One VMEM-resident buffer of a kernel launch."""
    name: str
    where: str                     # "in_spec" | "out_spec" | "scratch" | "transient"
    shape: Tuple[int, ...]
    dtype_bytes: int = 4
    buffers: int = 1               # pipeline copies (in/out specs: 2)
    note: str = ""

    @property
    def single_bytes(self) -> int:
        """Bytes of ONE copy (the live working set, ignoring pipeline
        double-buffering) — what the panel-budget heuristic gates on."""
        return int(math.prod(self.shape)) * self.dtype_bytes

    @property
    def nbytes(self) -> int:
        return self.single_bytes * self.buffers

    @property
    def formula(self) -> str:
        dims = "x".join(str(d) for d in self.shape)
        pre = f"{self.buffers}x(" if self.buffers > 1 else "("
        post = ")" if self.buffers > 1 else ")"
        return f"{pre}{dims}{post}x{self.dtype_bytes}B"


@dataclasses.dataclass(frozen=True)
class VmemFootprint:
    """Full per-launch VMEM accounting for one kernel configuration."""
    kernel: str
    variant: Optional[str]
    grid: Tuple[int, ...]
    terms: Tuple[VmemTerm, ...]

    @property
    def total_bytes(self) -> int:
        return sum(t.nbytes for t in self.terms)

    def term(self, name: str) -> Optional[VmemTerm]:
        for t in self.terms:
            if t.name == name:
                return t
        return None

    @property
    def largest(self) -> VmemTerm:
        return max(self.terms, key=lambda t: t.nbytes)

    def to_json(self) -> dict:
        return {
            "kernel": self.kernel, "variant": self.variant,
            "grid": list(self.grid), "total_bytes": self.total_bytes,
            "terms": [{"name": t.name, "where": t.where,
                       "bytes": t.nbytes, "formula": t.formula}
                      for t in self.terms],
        }

    def describe(self) -> str:
        lines = [f"{self.kernel}"
                 + (f" [{self.variant}]" if self.variant else "")
                 + f": grid={self.grid} total={self.total_bytes} B"]
        for t in self.terms:
            lines.append(f"  {t.name:<24} {t.where:<9} {t.formula:<20} "
                         f"= {t.nbytes} B" + (f"  ({t.note})" if t.note
                                              else ""))
        return "\n".join(lines)


def _expand_term(rows: int, width: int, name: str = "expand_transient"
                 ) -> VmemTerm:
    """Transient of ``_expand_stripe``: the (rows, width) column iota and
    the stripe carried through its slot loop."""
    return VmemTerm(name, "transient", (2, rows, width), 4, 1,
                    note="column iota + loop-carried stripe")


# ----------------------------------------------------------------------
def incrs_footprint(variant: str, *, m: int, n: int, bm: int, bn: int,
                    n_sections: int, smax: int, section: int,
                    rhs_dtype_bytes: int = 4) -> VmemFootprint:
    """Footprint of one fused InCRS SpMM launch, term-for-term from the
    BlockSpecs + scratch_shapes in ``kernels/incrs_spmm.py``.

    ``m``/``n`` are the logical operand dims; row-tile resolution and
    column padding are applied exactly as the kernels do.
    """
    if variant not in INCRS_VARIANTS:
        raise ValueError(f"unknown InCRS variant {variant!r}; "
                         f"expected one of {INCRS_VARIANTS}")
    bm, mp = resolve_row_tile(m, bm)
    np_ = -(-n // bn) * bn             # ops pads the RHS width to bn
    P = PIPELINE_BUFFERS
    if variant == "expand":
        grid = (mp // bm, np_ // bn, n_sections)
        terms = (
            VmemTerm("idx_block", "in_spec", (1, bm, smax), 4, P),
            VmemTerm("val_block", "in_spec", (1, bm, smax), 4, P),
            VmemTerm("rhs_block", "in_spec", (section, bn),
                     rhs_dtype_bytes, P),
            VmemTerm("out_tile", "out_spec", (bm, bn), 4, P),
            VmemTerm("acc_scratch", "scratch", (bm, bn), 4, 1),
            _expand_term(bm, section),
        )
    elif variant == "reuse":
        grid = (mp // bm, n_sections, np_ // bn)
        terms = (
            VmemTerm("idx_block", "in_spec", (1, bm, smax), 4, P),
            VmemTerm("val_block", "in_spec", (1, bm, smax), 4, P),
            VmemTerm("rhs_block", "in_spec", (section, bn),
                     rhs_dtype_bytes, P),
            VmemTerm("out_tile", "out_spec", (bm, bn), 4, P),
            VmemTerm("stripe_scratch", "scratch", (bm, section), 4, 1),
            VmemTerm("row_panel_accumulator", "scratch", (bm, np_), 4, 1,
                     note="output-stationary (bm, Np) panel"),
            _expand_term(bm, section),
        )
    else:                              # pipelined
        grid = (mp // bm,)
        terms = (
            VmemTerm("idx_block", "in_spec", (n_sections, bm, smax), 4, P,
                     note="whole row-panel stripes"),
            VmemTerm("val_block", "in_spec", (n_sections, bm, smax), 4, P,
                     note="whole row-panel stripes"),
            # RHS stays in HBM (memory_space=ANY): zero VMEM, streamed
            # through the rhs_stream_window below by manual DMA.
            VmemTerm("row_panel_accumulator", "out_spec", (bm, np_), 4, P,
                     note="output-stationary (bm, Np) out block"),
            VmemTerm("rhs_stream_window", "scratch", (2, section, bn),
                     rhs_dtype_bytes, 1,
                     note="double-buffered manual-DMA window"),
            VmemTerm("stripe_scratch", "scratch", (bm, section), 4, 1),
            _expand_term(bm, section),
        )
    return VmemFootprint("incrs_spmm", variant, grid, terms)


def bsr_footprint(*, n_block_rows: int, n_blocks: int, bm: int, bk: int,
                  n: int, bn: int, dtype_bytes: int = 4) -> VmemFootprint:
    """Footprint of one ``bsr_spmm.bsr_matmul`` launch (grid over stored
    blocks x col tiles, scalar-prefetched row/col maps live in SMEM)."""
    grid = (n_blocks, max(1, n // max(1, bn)))
    terms = (
        VmemTerm("values_block", "in_spec", (1, bm, bk), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("rhs_block", "in_spec", (bk, bn), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("out_tile", "out_spec", (bm, bn), 4, PIPELINE_BUFFERS),
        VmemTerm("acc_scratch", "scratch", (bm, bn), 4, 1),
    )
    return VmemFootprint("bsr_spmm", None, grid, terms)


def flash_footprint(*, lanes: int, sq: int, sk: int, hd: int,
                    bq: int = 128, bk: int = 128,
                    dtype_bytes: int = 4) -> VmemFootprint:
    """Footprint of one ``flash_attention`` launch, term-for-term from
    the BlockSpecs + scratch_shapes in ``kernels/flash_attention.py``
    (grid over query lanes x q tiles x k tiles; f32 online-softmax
    state in scratch)."""
    grid = (lanes, max(1, sq // max(1, bq)), max(1, sk // max(1, bk)))
    terms = (
        VmemTerm("q_block", "in_spec", (1, bq, hd), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("k_block", "in_spec", (1, bk, hd), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("v_block", "in_spec", (1, bk, hd), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("out_tile", "out_spec", (1, bq, hd), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("running_max", "scratch", (bq, 1), 4, 1),
        VmemTerm("running_denom", "scratch", (bq, 1), 4, 1),
        VmemTerm("out_accumulator", "scratch", (bq, hd), 4, 1,
                 note="f32 online-softmax accumulator"),
        VmemTerm("scores_transient", "transient", (bq, bk), 4, 1,
                 note="q @ k^T logits tile"),
    )
    return VmemFootprint("flash_attention", None, grid, terms)


def matched_footprint(stage: str, *, m: int, n: int, bm: int, bn: int,
                      n_rounds: int, rmax_a: int, rmax_b: int,
                      rounds: int) -> VmemFootprint:
    """Footprint of one matched-family launch, term-for-term from the
    BlockSpecs + scratch_shapes of ``kernels/index_match_spmm.py`` and
    ``spgemm/kernels.py``.

    Stages: ``"index_match"`` (fused reference), ``"condense"`` (stripe
    writer — NO scratch, but two (rows, R) expansion transients),
    ``"merge"`` (stripe reader with the f32 accumulator scratch).
    """
    if stage not in ("index_match", "condense", "merge"):
        raise ValueError(f"unknown matched stage {stage!r}; expected "
                         f"'index_match', 'condense' or 'merge'")
    mp = -(-m // bm) * bm
    np_ = -(-n // bn) * bn
    P = PIPELINE_BUFFERS
    grid = (mp // bm, np_ // bn, n_rounds)
    if stage == "merge":
        terms = (
            VmemTerm("stripe_block", "in_spec", (1, bm, bn), 4, P),
            VmemTerm("out_tile", "out_spec", (bm, bn), 4, P),
            VmemTerm("acc_scratch", "scratch", (bm, bn), 4, 1),
        )
        return VmemFootprint("spgemm_merge", None, grid, terms)
    operand_terms = (
        VmemTerm("a_idx_block", "in_spec", (1, bm, rmax_a), 4, P),
        VmemTerm("a_val_block", "in_spec", (1, bm, rmax_a), 4, P),
        VmemTerm("b_idx_block", "in_spec", (1, bn, rmax_b), 4, P),
        VmemTerm("b_val_block", "in_spec", (1, bn, rmax_b), 4, P),
        _expand_term(bm, rounds, "a_expand_transient"),
        _expand_term(bn, rounds, "b_expand_transient"),
    )
    if stage == "condense":
        terms = operand_terms + (
            VmemTerm("stripe_tile", "out_spec", (1, bm, bn), 4, P),
        )
        return VmemFootprint("spgemm_condense", None, grid, terms)
    terms = operand_terms + (
        VmemTerm("out_tile", "out_spec", (bm, bn), 4, P),
        VmemTerm("acc_scratch", "scratch", (bm, bn), 4, 1),
    )
    return VmemFootprint("index_match_spmm", None, grid, terms)


def dense_footprint(*, m: int, k: int, n: int, bm: int, bk: int, bn: int,
                    dtype_bytes: int = 4) -> VmemFootprint:
    """Footprint of one ``dense_mm.matmul`` launch (tiled MXU baseline)."""
    grid = (max(1, m // max(1, bm)), max(1, n // max(1, bn)),
            max(1, k // max(1, bk)))
    terms = (
        VmemTerm("a_block", "in_spec", (bm, bk), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("b_block", "in_spec", (bk, bn), dtype_bytes,
                 PIPELINE_BUFFERS),
        VmemTerm("out_tile", "out_spec", (bm, bn), 4, PIPELINE_BUFFERS),
        VmemTerm("acc_scratch", "scratch", (bm, bn), 4, 1),
    )
    return VmemFootprint("dense_mm", None, grid, terms)
