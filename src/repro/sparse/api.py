"""One front door: ``SparseSpec`` -> ``plan`` -> execute.

The paper's central claim is that ONE representation (InCRS) plus one
locate–compute architecture serves every access order and sparsity regime.
This module states that claim as API: a ``SparseSpec`` names WHAT the
sparse operand looks like (format x sparsity selection x geometry x
optional mesh sharding), ``plan`` turns a spec into a ``MatmulPlan`` whose
static metadata is built ONCE (Sextans' "general-purpose SpMM behind a
single interface"; SpArch's one-time condense/plan step before streamed
execution), and executing the plan runs the right fused kernel with the
right prep, variant dispatch, and sharding — ``plan(values, B)`` many
times per plan.

``sparse.Linear`` is the layer face of the same contract: ONE constructor
(`Linear.init` / ``Linear.from_dense``), one registered pytree node, one
``apply`` — replacing the three parallel per-family constructor sets
(``sparse_linear_*``, ``incrs_linear_*``, ``incrs_linear_sharded_*``),
which live on as one-release deprecation shims. Switching a layer from
dense to fused-InCRS to row-sharded InCRS is a spec change, not a code
path change:

    spec = SparseSpec("incrs", density=0.05)
    lin  = sparse.Linear.init(key, d_in, d_out, spec)
    y    = lin(x)                      # fused kernel fwd, custom-VJP bwd
    lin2 = sparse.Linear.from_dense(lin.to_dense(),
                                    dataclasses.replace(spec, mesh=mesh))

Formats: ``dense`` (tiled dense matmul baseline; an optional pattern masks
the compute), ``bsr`` (block-structured, whole MXU tiles skipped),
``incrs`` (element-level through the fused InCRS kernel; add ``mesh=`` for
the row-sharded data path), ``crs`` (both operands sparse — the paper's
Alg. 2 index-matching kernel; plan–execute only, no trainable layer).

Everything here delegates to the SAME family implementations the legacy
names used, so outputs are bit-identical (``tests/test_api.py`` pins it).
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from ..analysis import kernel_check as _kernel_check
from ..core.bsr import BSR
from ..core.crs import CRS
from ..core.incrs import InCRS
from ..kernels import autotune as _autotune
from ..kernels import ops
from . import linear as _lin
from .pattern import (FamilyOps, SparsityPattern, get_pattern, magnitude_mask,
                      parse_nm, register_family, _FAMILIES)

FORMATS = ("dense", "bsr", "crs", "incrs")


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True, eq=False)
class SparseSpec:
    """WHAT one sparse operand looks like — the single vocabulary every
    consumer (layers, plans, engines, launchers) speaks.

    ``format``    one of ``dense`` | ``bsr`` | ``crs`` | ``incrs``.
    selection     exactly one of ``density`` (magnitude, one global
                  threshold), ``mask`` (explicit element mask of W — kept
                  slots stay live even at value 0.0), ``pattern`` (an
                  existing lifecycle ``SparsityPattern``), or a structured
                  ``policy`` like ``"2:4"`` (exactly n survivors per
                  m-group along d_in). Nothing set -> keep the non-zeros.
    geometry      ``section``/``block`` for InCRS stripes (defaults
                  ``core.incrs.S_DEFAULT``/``B_DEFAULT``), ``block`` is the
                  tile side for ``bsr``, ``rounds`` the index-match window
                  for ``crs``. ``rhs_format`` (crs only) declares the
                  streamed right-hand side sparse too (``"crs"`` or
                  ``"incrs"``): execution takes the SpGEMM condense/merge
                  pipeline instead of the fused reference kernel.
    layout        ``mesh`` (+ optional ``shard_axis``) row-shards an
                  ``incrs`` operand across that mesh — one contiguous
                  output-row stripe panel per device; omitted -> one
                  device.

    ``eq=False`` -> identity hash/eq: specs ride alongside jit-static
    metadata. Derive variants with ``dataclasses.replace``.
    """
    format: str = "incrs"
    density: Optional[float] = None
    mask: Optional[np.ndarray] = None
    pattern: Optional[SparsityPattern] = None
    policy: str = "magnitude"
    section: Optional[int] = None
    block: Optional[int] = None
    rounds: int = 128
    mesh: Optional[Mesh] = None
    shard_axis: Any = None
    rhs_format: Optional[str] = None

    def __post_init__(self):
        if self.format not in FORMATS:
            raise ValueError(f"format must be one of {FORMATS}, "
                             f"got {self.format!r}")
        if self.rhs_format is not None:
            if self.rhs_format not in ("dense", "crs", "incrs"):
                raise ValueError(f"rhs_format must be None, 'dense', 'crs' "
                                 f"or 'incrs', got {self.rhs_format!r}")
            if self.rhs_format != "dense" and self.format != "crs":
                raise ValueError(
                    f"a sparse rhs_format ({self.rhs_format!r}) is the "
                    f"SpGEMM path and needs format='crs' (both operands "
                    f"sparse); format {self.format!r} streams a dense RHS")
        n_sel = sum(x is not None
                    for x in (self.density, self.mask, self.pattern))
        if n_sel > 1:
            raise ValueError("pass at most one of density / mask / pattern")
        if self.policy != "magnitude":
            parse_nm(self.policy)               # validate eagerly
            if n_sel:
                raise ValueError(f"policy {self.policy!r} IS the "
                                 f"selection; drop density/mask/pattern")
        if self.mesh is not None and self.format != "incrs":
            raise ValueError(f"mesh sharding is the InCRS data path; "
                             f"format {self.format!r} does not shard")

    @property
    def sharded(self) -> bool:
        return self.mesh is not None

    def resolve_pattern(self, w: np.ndarray) -> Optional[SparsityPattern]:
        """The concrete ``SparsityPattern`` this spec selects on weight
        ``w`` (d_in, d_out) — or None for an unmasked dense spec."""
        if self.pattern is not None:
            return self.pattern
        if self.mask is not None:
            return SparsityPattern(np.asarray(self.mask, bool))
        if self.policy != "magnitude":
            return SparsityPattern(
                magnitude_mask(w, None, policy=self.policy))
        if self.density is None and self.format == "dense":
            return None                          # plain dense baseline
        return SparsityPattern(magnitude_mask(
            w, self.density,
            block=self.block if self.format == "bsr" else None))


# ----------------------------------------------------------------------
# Dense "family": the baseline format behind the same node/registry shape
# as the sparse ones, so a Linear can be dense by spec alone (and a masked
# dense layer rides the sparsity lifecycle like any other family).
@dataclasses.dataclass(frozen=True, eq=False)
class DenseLinearMeta:
    d_in: int
    d_out: int
    pattern: Any = None       # optional lifecycle pattern masking compute


@dataclasses.dataclass
class DenseLinearParams:
    values: jnp.ndarray       # (d_in, d_out) dense W — the trainable leaf
    meta: DenseLinearMeta

    @property
    def pattern(self):
        return self.meta.pattern


_lin._register_params_pytree(DenseLinearParams)


def _dense_masked(values, meta: DenseLinearMeta):
    if meta.pattern is None:
        return values
    return jnp.where(jnp.asarray(meta.pattern.mask), values, 0.0)


def _dense_apply(p: DenseLinearParams, x):
    return x @ _dense_masked(p.values, p.meta).astype(x.dtype)


def _dense_to_dense(p: DenseLinearParams) -> np.ndarray:
    return np.asarray(_dense_masked(p.values, p.meta), np.float32)


def _make_dense(w, spec: SparseSpec, dtype=jnp.float32) -> DenseLinearParams:
    w = np.asarray(w, np.float32)
    pat = spec.resolve_pattern(w)
    if pat is not None and pat.shape != w.shape:
        raise ValueError(f"pattern shape {pat.shape} != weight {w.shape}")
    if pat is not None:
        w = np.where(pat.mask, w, 0.0)
    return DenseLinearParams(jnp.asarray(w, dtype),
                             DenseLinearMeta(*w.shape, pattern=pat))


register_family(DenseLinearParams, FamilyOps(
    "dense",
    to_dense=_dense_to_dense,
    pack=lambda w, pat, like: DenseLinearParams(
        jnp.asarray(np.where(pat.mask, np.asarray(w, np.float32), 0.0),
                    like.values.dtype),
        DenseLinearMeta(like.meta.d_in, like.meta.d_out, pattern=pat)),
    pack_values=lambda meta, w: jnp.asarray(
        np.where(meta.pattern.mask, np.asarray(w, np.float32), 0.0)
        if meta.pattern is not None else np.asarray(w, np.float32)),
    default_mask=lambda w, d, n: magnitude_mask(w, d)))


# ----------------------------------------------------------------------
# Index-matching (crs) plan metadata: the fixed sparse operand A is
# round-prepped ONCE; per call only the streamed CRS right-hand side pays
# prep. No trainable layer — plan–execute only.
@dataclasses.dataclass(eq=False)
class CRSPlanMeta:
    ai: jnp.ndarray           # (n_rounds, Mp, rmax) int32 round indices
    scatter: jnp.ndarray      # (nnz,) flat slots into the val array, in
    #                           A's row-major non-zero order
    shape: Tuple[int, int]    # (M, K) of A
    rounds: int
    pattern: Any = None
    rhs_format: Optional[str] = None   # None/dense -> fused reference;
    #                                    "crs"/"incrs" -> condense/merge
    # Per-RHS-object round-prep memo (weakref-guarded, like
    # ops._PREP_CACHE): the plan carries BOTH operands' prepped metadata —
    # A's is built once at plan time, each streamed RHS pays prep once.
    _rhs_prep: Dict = dataclasses.field(default_factory=dict, repr=False)


_RHS_PREP_MAX = 8


def _rhs_rounds_prep(meta: CRSPlanMeta, b: CRS):
    hit = meta._rhs_prep.get(id(b))
    if hit is not None and hit[0]() is b:
        return hit[1]
    prep = ops.prep_rounds(b, meta.rounds, pad_rows_to=128)
    if len(meta._rhs_prep) >= _RHS_PREP_MAX:
        meta._rhs_prep.pop(next(iter(meta._rhs_prep)))
    meta._rhs_prep[id(b)] = (weakref.ref(b), prep)
    return prep


def _crs_plan_meta(pat: SparsityPattern, rounds: int,
                   rhs_format: Optional[str] = None) -> CRSPlanMeta:
    mask_a = np.ascontiguousarray(pat.mask.T)          # A = W^T (M, K)
    m, k = mask_a.shape
    crs0 = CRS.from_mask(np.zeros((m, k), np.float32), mask_a)
    ai, _ = ops.prep_rounds(crs0, rounds, pad_rows_to=128)
    n_rounds, mp, rmax = ai.shape
    # Replicate prep_rounds' slot arithmetic to map each non-zero (in CRS
    # row-major order) to its flat (round, row, slot) cell.
    if crs0.nnz:
        row_of = np.repeat(np.arange(m),
                           np.diff(crs0.row_ptr).astype(np.int64))
        r = crs0.col_idx.astype(np.int64) // rounds
        counts = np.zeros((m, n_rounds), dtype=np.int64)
        np.add.at(counts, (row_of, r), 1)
        group_start = np.concatenate([[0],
                                      np.cumsum(counts.reshape(-1))[:-1]])
        slot = np.arange(crs0.nnz, dtype=np.int64) \
            - group_start[row_of * n_rounds + r]
        flat = (r * mp + row_of) * rmax + slot
    else:
        flat = np.zeros((0,), np.int64)
    return CRSPlanMeta(ai, jnp.asarray(flat, jnp.int32), (m, k), rounds,
                       pattern=pat, rhs_format=rhs_format)


def _crs_call(meta: CRSPlanMeta, values, b, variant, interpret,
              config=None):
    if isinstance(b, InCRS):
        b = b.crs
    if not isinstance(b, CRS):
        raise TypeError("a 'crs' plan runs sparse x sparse C = A @ B^T "
                        "and needs B^T as a CRS (or InCRS)")
    av = jnp.zeros((int(np.prod(meta.ai.shape)),), jnp.float32
                   ).at[meta.scatter].set(jnp.asarray(values, jnp.float32)
                                          ).reshape(meta.ai.shape)
    bi, bv = _rhs_rounds_prep(meta, b)
    if meta.rhs_format in ("crs", "incrs") and variant != "reference":
        from .. import spgemm as _spgemm       # circular at module scope
        out = _spgemm.condense_merge_prepped(
            meta.ai, av, bi, bv, rounds=meta.rounds, interpret=interpret)
    else:
        out = ops.index_match_prepped(meta.ai, av, bi, bv,
                                      rounds=meta.rounds,
                                      interpret=interpret)
    return out[:meta.shape[0], :b.shape[0]]


def _crs_pack(meta: CRSPlanMeta, w) -> jnp.ndarray:
    a = np.asarray(w, np.float32).T
    return jnp.asarray(a[meta.pattern.mask.T])


# ----------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class FormatAdapter:
    """Everything one (format, sharded?) family plugs into the front door:
    construction from a dense weight, layer apply, plan execution, and
    spec recovery from packed metadata."""
    name: str
    make: Callable                     # (w, spec, dtype) -> inner params
    apply: Optional[Callable]          # (inner, x) -> y; None: no layer
    call: Callable                     # (meta, values, b, variant,
    #                                     interpret, config=None) -> C;
    #                                     config is an optional
    #                                     autotune.TunedConfig the plan
    #                                     carries (InCRS families honor
    #                                     it, others may ignore it)
    pack: Callable                     # (meta, w) -> plan/layer values
    spec_of: Callable                  # (meta) -> SparseSpec
    plan_values: Callable = lambda inner: inner.values  # layer -> plan vals


_ADAPTERS: Dict[Tuple[str, bool], FormatAdapter] = {}
_BY_CLS: Dict[type, FormatAdapter] = {}


def register_format(fmt: str, sharded: bool, params_cls: Optional[type],
                    adapter: FormatAdapter) -> None:
    """THE spec registry: consumers (Linear, plans, engines, the trainer's
    prune hook, checkpointing) discover families here instead of
    per-family isinstance chains."""
    _ADAPTERS[(fmt, sharded)] = adapter
    if params_cls is not None:
        _BY_CLS[params_cls] = adapter


def _adapter(spec: SparseSpec) -> FormatAdapter:
    ad = _ADAPTERS.get((spec.format, spec.sharded))
    if ad is None:
        raise ValueError(f"no kernel family serves format "
                         f"{spec.format!r} (sharded={spec.sharded})")
    return ad


def adapter_of(node: Any) -> FormatAdapter:
    """Registry lookup for a params node (Linear inner or raw family)."""
    ad = _BY_CLS.get(type(node))
    if ad is None:
        raise TypeError(f"{type(node).__name__} is not a registered "
                        f"sparse-linear family")
    return ad


# ---- per-format constructors (delegating to the family packers) --------
def _make_bsr(w, spec: SparseSpec, dtype=jnp.float32):
    """BSR stores — and trains — WHOLE tiles: an element selection is
    widened to the blocks it touches, and the minted pattern records that
    block-expanded mask (so ``pattern``/``nnz``/``to_dense`` agree with
    what the kernel actually computes). An explicit lifecycle ``pattern``
    must already be block-aligned — widening it here would silently fork
    the caller's lineage."""
    if spec.block is None:
        raise ValueError("format 'bsr' needs block= (the square tile side)")
    if spec.policy != "magnitude":
        raise ValueError("n:m selection is element-level; 'bsr' prunes "
                         "whole blocks — use format 'incrs' or "
                         "policy='magnitude'")
    w = np.asarray(w, np.float32)
    pat = spec.resolve_pattern(w)
    if pat is None:                       # keep non-zero blocks
        pat = SparsityPattern(magnitude_mask(w, None, block=spec.block))
    from .pattern import expand_block_mask
    block_mask = pat.block_mask(spec.block)
    expanded = expand_block_mask(block_mask, spec.block)
    if spec.pattern is not None:
        if not np.array_equal(expanded, pat.mask):
            raise ValueError(
                "format 'bsr' keeps whole tiles: the lifecycle pattern "
                "must be block-aligned (pass the block-expanded mask, or "
                "use mask= to let the packer widen it)")
    elif not np.array_equal(expanded, pat.mask):
        pat = SparsityPattern(expanded)   # widen an element mask to tiles
    return _lin._bsr_from_mask(w, block_mask, spec.block,
                               dtype=dtype, _pattern=pat)


def _require_f32(dtype, fmt: str) -> None:
    """The InCRS families pack f32 stripe values by design (the fused
    kernel accumulates in f32) — reject a narrower/wider request loudly
    instead of silently returning f32."""
    if jnp.dtype(dtype) != jnp.float32:
        raise ValueError(f"format {fmt!r} stores f32 stripe values (the "
                         f"fused kernel's accumulation dtype); "
                         f"dtype={jnp.dtype(dtype).name} is not supported")


def _make_incrs(w, spec: SparseSpec, dtype=jnp.float32):
    _require_f32(dtype, "incrs")
    if spec.policy != "magnitude":
        return _lin._incrs_from_dense(
            w, mask=magnitude_mask(w, None, policy=spec.policy),
            section=spec.section, block=spec.block)
    return _lin._incrs_from_dense(w, density=spec.density, mask=spec.mask,
                                  section=spec.section, block=spec.block,
                                  _pattern=spec.pattern)


def _make_incrs_sharded(w, spec: SparseSpec, dtype=jnp.float32):
    _require_f32(dtype, "incrs")
    kw = dict(mesh=spec.mesh, axis=spec.shard_axis,
              section=spec.section, block=spec.block)
    if spec.policy != "magnitude":
        return _lin._incrs_sharded_from_dense(
            w, mask=magnitude_mask(w, None, policy=spec.policy), **kw)
    return _lin._incrs_sharded_from_dense(w, density=spec.density,
                                          mask=spec.mask,
                                          _pattern=spec.pattern, **kw)


def _make_crs(w, spec, dtype=jnp.float32):
    raise ValueError("format 'crs' (both operands sparse) is plan–execute "
                     "only — use sparse.plan / ops.spmm(a_crs, bt_crs); "
                     "there is no trainable crs layer")


# ---- per-format plan execution ----------------------------------------
def _dense_call(meta, values, b, variant, interpret, config=None):
    return ops.spmm(values, b, interpret=interpret)


def _bsr_call(meta, values, b, variant, interpret, config=None):
    return _lin._sparse_mm(values, jnp.asarray(b).T, meta).T


def _incrs_call(meta, values, b, variant, interpret, config=None):
    prep = ops.PreparedOperand(meta.fwd_idx, values,
                               (meta.d_out, meta.d_in), meta.section)
    if variant is None and config is not None:
        # Plan-persisted tuned config: variant AND tile sizes, no per-call
        # cache lookup or model evaluation.
        return ops.spmm(prep, b, variant=config.variant, bm=config.bm,
                        bn=config.bn, interpret=interpret)
    return ops.spmm(prep, b, variant=variant or "auto", interpret=interpret)


def _incrs_sharded_call(meta, values, b, variant, interpret, config=None):
    prep = ops.ShardedPreparedOperand(
        meta.fwd_idx, values, (meta.d_out, meta.d_in), meta.section,
        meta.shard_width, meta.mesh, meta.axes)
    if variant is None and config is not None:
        # bm re-clamps to each shard-local panel inside the kernel.
        return ops.spmm(prep, b, variant=config.variant, bm=config.bm,
                        bn=config.bn, interpret=interpret)
    return ops.spmm(prep, b, variant=variant or "auto", interpret=interpret)


def _dense_pack(meta, w) -> jnp.ndarray:
    """Dense W (d_in, d_out) -> plan values A = W^T (pattern-masked) —
    the same A-orientation every other adapter's pack returns."""
    w = np.asarray(w, np.float32)
    if meta is not None and meta.pattern is not None:
        w = np.where(meta.pattern.mask, w, 0.0)
    return jnp.asarray(w).T


register_format("dense", False, DenseLinearParams, FormatAdapter(
    "dense",
    make=_make_dense, apply=_dense_apply, call=_dense_call,
    pack=_dense_pack,
    spec_of=lambda meta: SparseSpec("dense", pattern=meta.pattern),
    plan_values=lambda inner: _dense_masked(inner.values, inner.meta).T))

register_format("bsr", False, _lin.SparseLinearParams, FormatAdapter(
    "bsr",
    make=_make_bsr, apply=_lin._bsr_apply, call=_bsr_call,
    pack=lambda meta, w: _lin._bsr_pack_values(meta, w),
    spec_of=lambda meta: SparseSpec("bsr", block=meta.block,
                                    pattern=meta.pattern)))

register_format("incrs", False, _lin.InCRSLinearParams, FormatAdapter(
    "incrs",
    make=_make_incrs, apply=_lin._incrs_apply, call=_incrs_call,
    pack=lambda meta, w: _lin._incrs_pack_values(meta, w),
    spec_of=lambda meta: SparseSpec("incrs", section=meta.section,
                                    block=meta.block,
                                    pattern=meta.pattern)))

register_format("incrs", True, _lin.ShardedInCRSLinearParams, FormatAdapter(
    "incrs_sharded",
    make=_make_incrs_sharded, apply=_lin._incrs_sharded_apply,
    call=_incrs_sharded_call,
    pack=lambda meta, w: _lin._sharded_pack_values(meta, w),
    spec_of=lambda meta: SparseSpec("incrs", section=meta.section,
                                    block=meta.block, pattern=meta.pattern,
                                    mesh=meta.mesh,
                                    shard_axis=meta.axes)))

register_format("crs", False, None, FormatAdapter(
    "crs",
    make=_make_crs, apply=None, call=_crs_call, pack=_crs_pack,
    spec_of=lambda meta: SparseSpec("crs", rounds=meta.rounds,
                                    pattern=meta.pattern,
                                    rhs_format=meta.rhs_format)))


# ----------------------------------------------------------------------
@dataclasses.dataclass(eq=False)
class MatmulPlan:
    """The execute half of plan–execute: static kernel metadata built once
    from a concrete spec; ``plan(values, B)`` runs C = A @ B (A = W^T, the
    kernel orientation) any number of times with zero host prep.

    ``pack`` turns a dense W (d_in, d_out) into the plan's packed values;
    ``bind`` closes over one values array, yielding the serving-operand
    view ``serve.SpMMEngine`` consumes.

    ``tuned`` is an optional ``kernels.autotune.TunedConfig`` the plan
    carries (attached by ``plan(..., tune=...)`` or ``MatmulPlan.tune``):
    every execution then runs the tuned ``(variant, bm, bn)`` directly —
    no per-call cache lookup, no cost-model evaluation. An explicit
    ``variant=`` at call time overrides it.
    """
    spec: SparseSpec
    meta: Any                 # family meta; CRSPlanMeta; None for dense
    tuned: Optional[_autotune.TunedConfig] = None

    def __call__(self, values, b, *, variant: Optional[str] = None,
                 interpret: Optional[bool] = None):
        return _adapter(self.spec).call(self.meta, values, b, variant,
                                        interpret, config=self.tuned)

    # -- kernel tuning --------------------------------------------------
    def _tuning_arrays(self):
        """(idx, section, shard?) of the InCRS stripes this plan executes
        with, or None for non-InCRS formats."""
        meta = self.meta
        if meta is None or not hasattr(meta, "fwd_idx"):
            return None
        idx = meta.fwd_idx
        if idx.ndim == 4:              # sharded: tune the per-shard panel
            idx = idx[0]
        return idx, meta.section

    def lookup_tuned(self, n_cols: int,
                     interpret: Optional[bool] = None
                     ) -> Optional[_autotune.TunedConfig]:
        """Cached tuned config for an ``n_cols``-wide RHS, if one exists
        (memory or disk) — never measures."""
        arrs = self._tuning_arrays()
        if arrs is None:
            return None
        idx, section = arrs
        interpret = ops.resolve_interpret(interpret)
        return _autotune.lookup(_autotune.cache_key(
            idx.shape[1], idx.shape[0], idx.shape[2], section, n_cols,
            _autotune.backend_name(interpret)))

    def tune(self, n_cols: int, *, interpret: Optional[bool] = None,
             reps: int = 3, persist: bool = True) -> "MatmulPlan":
        """Measure-tune this plan's kernel for an ``n_cols``-wide RHS and
        return a plan carrying the winning config (also persisted to the
        tuning cache unless ``persist=False``). Values do not matter for
        timing, so the sweep runs on zeros."""
        arrs = self._tuning_arrays()
        if arrs is None:
            raise ValueError(f"format {self.spec.format!r} has no tunable "
                             f"fused kernel")
        idx, section = arrs
        interpret = ops.resolve_interpret(interpret)
        cfg = _autotune.tune(
            idx, jnp.zeros(idx.shape, jnp.float32),
            jnp.zeros((idx.shape[0] * section, n_cols), jnp.float32),
            section=section, interpret=interpret, reps=reps,
            persist=persist)
        return dataclasses.replace(self, tuned=cfg)

    def check_feasible(self, n_cols: int) -> None:
        """Prove this plan's tuned config against the static VMEM
        budgets *and* the grid interpreter's bounds proof
        (``analysis.kernel_check.LAUNCH_RULES``) for an ``n_cols``-wide
        RHS.

        Raises :class:`repro.analysis.KernelConfigError` naming the
        violated budget term — e.g. a tuned-cache entry swept under a
        larger ``REPRO_VMEM_BUDGET`` than the current one. No-op for
        untuned plans and non-InCRS formats."""
        cfg = self.tuned
        arrs = self._tuning_arrays()
        if cfg is None or arrs is None:
            return
        idx, section = arrs
        _kernel_check.require_feasible(
            cfg.variant, m=idx.shape[1], n=int(n_cols), bm=cfg.bm,
            bn=cfg.bn, n_sections=idx.shape[0], smax=idx.shape[2],
            section=section, rules=_kernel_check.LAUNCH_RULES,
            context=f"plan tuned config ({cfg.variant}, bm={cfg.bm}, "
                    f"bn={cfg.bn})")

    def pack(self, w) -> jnp.ndarray:
        """Dense W (d_in, d_out) -> packed plan values (for 'dense' the
        A = W^T array itself, pattern-masked)."""
        return _adapter(self.spec).pack(self.meta, w)

    def bind(self, values) -> "BoundPlan":
        return BoundPlan(self, values)

    @property
    def pattern(self) -> Optional[SparsityPattern]:
        if self.meta is not None and \
                getattr(self.meta, "pattern", None) is not None:
            return self.meta.pattern
        return self.spec.pattern

    @property
    def shape(self) -> Optional[Tuple[int, int]]:
        """(M, K) of the sparse operand A = W^T; None for an unpatterned
        dense plan (the bound values carry the shape)."""
        if isinstance(self.meta, CRSPlanMeta):
            return self.meta.shape
        if self.meta is not None and hasattr(self.meta, "d_out"):
            return (self.meta.d_out, self.meta.d_in)
        pat = self.pattern
        return (pat.d_out, pat.d_in) if pat is not None else None


@dataclasses.dataclass(eq=False)
class BoundPlan:
    """A ``MatmulPlan`` closed over one values array — a self-contained
    serving operand: ``bound(B)`` executes, ``.shape``/``.pattern`` are
    what engines validate and version against."""
    plan: MatmulPlan
    values: Any

    def __call__(self, b, *, variant: Optional[str] = None,
                 interpret: Optional[bool] = None):
        return self.plan(self.values, b, variant=variant,
                         interpret=interpret)

    @property
    def shape(self) -> Tuple[int, int]:
        s = self.plan.shape
        return tuple(np.shape(self.values)) if s is None else s

    @property
    def pattern(self) -> Optional[SparsityPattern]:
        return self.plan.pattern


def plan(spec: SparseSpec, rhs_shape: Optional[Tuple[int, ...]] = None, *,
         mesh: Optional[Mesh] = None, tune: str = "cache") -> MatmulPlan:
    """Build the static half of C = A @ B for ``spec`` — prep once,
    execute many.

    The spec must pin the operand concretely: a ``pattern`` or ``mask``
    for sparse formats (a density-only spec needs values to select on —
    use ``Linear.from_dense`` or ``plan_for_operand``), nothing for plain
    ``dense``. ``rhs_shape``, when given, is validated against the
    operand's K. ``mesh`` overrides/sets the spec's mesh (row-sharded
    InCRS).

    ``tune`` decides how the plan picks kernel tiles when ``rhs_shape``
    pins the RHS width (InCRS formats only): ``"cache"`` (default)
    attaches a previously tuned config if the tuning cache has one —
    free; ``"measure"`` runs the autotuner sweep now (cache hit included)
    and attaches the winner; ``"off"`` attaches nothing (execution falls
    back to per-call auto dispatch).
    """
    if tune not in ("cache", "measure", "off"):
        raise ValueError(f"tune must be 'cache', 'measure' or 'off', "
                         f"got {tune!r}")
    if mesh is not None:
        spec = dataclasses.replace(spec, mesh=mesh)
    if spec.format == "dense" and spec.pattern is None and \
            spec.mask is None:
        return MatmulPlan(spec, None)
    pat = spec.pattern if spec.pattern is not None else (
        SparsityPattern(np.asarray(spec.mask, bool))
        if spec.mask is not None else None)
    if pat is None:
        raise ValueError(
            "plan() needs a concrete pattern (pattern= or mask= on the "
            "spec) — a density/policy selection depends on values; use "
            "Linear.from_dense(w, spec) or plan_for_operand(a, spec)")
    if rhs_shape is not None and rhs_shape and rhs_shape[0] != pat.d_in:
        raise ValueError(f"rhs_shape {tuple(rhs_shape)} does not contract "
                         f"with K={pat.d_in}")
    spec = dataclasses.replace(spec, density=None, mask=None, pattern=pat,
                               policy="magnitude")
    if spec.format == "crs":
        return MatmulPlan(spec, _crs_plan_meta(pat, spec.rounds,
                                               rhs_format=spec.rhs_format))
    inner = _adapter(spec).make(np.zeros(pat.shape, np.float32), spec)
    built = MatmulPlan(spec, inner.meta)
    if spec.format == "incrs" and rhs_shape is not None \
            and len(rhs_shape) >= 2 and tune != "off":
        n_cols = int(rhs_shape[1])
        if tune == "measure":
            built = built.tune(n_cols)
        else:
            built = dataclasses.replace(
                built, tuned=built.lookup_tuned(n_cols))
        # Fail at plan time, not launch time: a tuned config that violates
        # the (configurable) VMEM budgets raises a structured
        # KernelConfigError naming the violated term.
        built.check_feasible(n_cols)
    return built


def plan_for_operand(a, spec: Optional[SparseSpec] = None) -> BoundPlan:
    """Spec-drive a CONCRETE sparse operand A (M, K) into a bound,
    servable plan: ``plan_for_operand(a, spec)(B)`` is C = A @ B.

    ``a`` may be a dense array, ``CRS``, ``InCRS`` or ``BSR``; its
    transpose is the weight the spec selects on (no selection set -> the
    operand's own non-zeros, i.e. serve A exactly as given). This is the
    one-liner the serving launcher uses for every ``--format``.
    """
    spec = SparseSpec() if spec is None else spec
    if isinstance(a, InCRS):
        a = a.crs
    if isinstance(a, (CRS, BSR)):
        a = a.to_dense()
    a = np.asarray(a, np.float32)
    if a.ndim != 2:
        raise ValueError(f"operand must be 2-D, got shape {a.shape}")
    w = np.ascontiguousarray(a.T)                      # W = A^T
    if spec.format != "dense" and spec.density is None and \
            spec.mask is None and spec.pattern is None and \
            spec.policy == "magnitude":
        spec = dataclasses.replace(spec, mask=np.ascontiguousarray(a != 0).T)
    if spec.format == "crs":
        pat = spec.resolve_pattern(w)
        p = MatmulPlan(
            dataclasses.replace(spec, density=None, mask=None, pattern=pat,
                                policy="magnitude"),
            _crs_plan_meta(pat, spec.rounds, rhs_format=spec.rhs_format))
        return p.bind(p.pack(w))
    return Linear.from_dense(w, spec).bound()


# ----------------------------------------------------------------------
@dataclasses.dataclass
class Linear:
    """ONE sparse/dense linear layer node: y = x @ W behind a spec.

    ``inner`` is the format-specific params object (the registered family
    node the legacy constructors used to hand out); the wrapper is itself
    a registered pytree node whose only child is ``inner``, so optimizer
    state, jit, pipeline stacking, checkpointing and the sparsity
    lifecycle all see through it unchanged.
    """
    inner: Any

    # -- one constructor family ---------------------------------------
    @classmethod
    def init(cls, key, d_in: int, d_out: int,
             spec: SparseSpec = SparseSpec(), *, scale: float = 0.02,
             dtype=jnp.float32) -> "Linear":
        """Random-normal init (std ``scale``) packed under ``spec``."""
        w = np.asarray(jax.random.normal(key, (d_in, d_out))) * scale
        return cls.from_dense(w, spec, dtype=dtype)

    @classmethod
    def from_dense(cls, w, spec: SparseSpec = SparseSpec(), *,
                   dtype=jnp.float32) -> "Linear":
        """Pack a dense W (d_in, d_out) under ``spec`` — the spec's
        selection (density / mask / pattern / n:m policy) decides which
        slots stay live."""
        return cls(_adapter(spec).make(np.asarray(w, np.float32), spec,
                                       dtype=dtype))

    # -- one apply ------------------------------------------------------
    def __call__(self, x):
        return apply(self, x)

    # -- views ----------------------------------------------------------
    @property
    def values(self):
        return self.inner.values

    @property
    def meta(self):
        return self.inner.meta

    @property
    def pattern(self) -> Optional[SparsityPattern]:
        return get_pattern(self.inner)

    @property
    def spec(self) -> SparseSpec:
        return adapter_of(self.inner).spec_of(self.inner.meta)

    @property
    def format(self) -> str:
        return adapter_of(self.inner).name

    @property
    def d_in(self) -> int:
        return self.inner.meta.d_in

    @property
    def d_out(self) -> int:
        return self.inner.meta.d_out

    @property
    def nnz(self) -> int:
        pat = self.pattern
        return pat.nnz if pat is not None else self.d_in * self.d_out

    @property
    def density(self) -> float:
        return self.nnz / float(self.d_in * self.d_out)

    @property
    def prep(self):
        """Device-ready serving-operand view (InCRS families only) — what
        ``serve.SpMMEngine`` consumes zero-copy."""
        return self.inner.prep

    @property
    def plan(self) -> MatmulPlan:
        return MatmulPlan(self.spec, self.inner.meta)

    def bound(self) -> BoundPlan:
        """Servable C = A @ B view over the CURRENT values (A = W^T)."""
        return self.plan.bind(adapter_of(self.inner).plan_values(self.inner))

    def to_dense(self) -> np.ndarray:
        """Densify W (d_in, d_out) from the current values."""
        return _FAMILIES[type(self.inner)].to_dense(self.inner)

    def shard(self, mesh: Optional[Mesh] = None, axis=None) -> "Linear":
        """Re-shard a trained single-device InCRS layer across a mesh —
        values and pattern lineage preserved (train on one device, deploy
        the SAME weights into multi-device serving)."""
        if not isinstance(self.inner, _lin.InCRSLinearParams):
            raise ValueError(f"shard() re-shards the single-device InCRS "
                             f"family; this layer is {self.format!r}")
        return Linear(_lin._incrs_shard(self.inner, mesh=mesh, axis=axis))


jax.tree_util.register_pytree_with_keys(
    Linear,
    lambda p: (((jax.tree_util.GetAttrKey("inner"), p.inner),), None),
    lambda aux, children: Linear(children[0]))


def apply(p, x):
    """THE layer apply: dispatches any ``Linear`` (or raw family params
    node — pipeline stages slice those out of stacks) through its family's
    forward/custom-VJP path."""
    node = p.inner if isinstance(p, Linear) else p
    ad = adapter_of(node)
    if ad.apply is None:                   # pragma: no cover - no such fam
        raise ValueError(f"format {ad.name!r} has no layer apply")
    return ad.apply(node, x)


def stack_init(key, n_stages: int, d_in: int, d_out: int,
               spec: SparseSpec = SparseSpec(), *,
               scale: float = 0.02) -> Linear:
    """Shared-pattern parameter stack for pipeline-parallel stages: ONE
    sparsity pattern (a single static meta serves every stage), per-stage
    values stacked along a leading stage axis. InCRS format only — see
    ``train.pipeline``. The stacked node is NOT individually repackable
    (``pattern.is_stacked_node``); the prune callback warns and skips it.
    """
    if spec.format != "incrs" or spec.sharded:
        raise ValueError("stack_init stacks the single-device InCRS "
                         "family (pipeline stages)")
    if spec.density is None:
        raise ValueError("stack_init needs density= on the spec")
    return Linear(_lin._incrs_stack_init(
        key, n_stages, d_in, d_out, spec.density, scale,
        section=spec.section, block=spec.block))


__all__ = [
    "FORMATS", "SparseSpec", "MatmulPlan", "BoundPlan", "Linear",
    "DenseLinearParams", "DenseLinearMeta", "CRSPlanMeta",
    "FormatAdapter", "register_format", "adapter_of",
    "plan", "plan_for_operand", "apply", "stack_init",
]
