"""SparseLinear: block-sparse weights on the BSR Pallas kernel, trainable.

The forward pass is the paper's SpMM (block-sparse weight times dense
activations) through ``kernels.bsr_spmm``; the backward pass is defined with
``jax.custom_vjp``:

  y  = x @ W            with W^T stored as BSR (out-major blocks)
  dx = dy @ W^T         -> a second BSR spmm with the TRANSPOSED metadata
                           (precomputed at init; transposing BSR is a
                           permutation of blocks + swap of block dims)
  dW = x^T dy, restricted to the live blocks -> per-block outer products
                           gathered by (row_of, col_of) — compute scales
                           with nnz blocks, exactly the paper's "only
                           useful computation" property, in the backward
                           pass too.

Metadata (row_of/col_of and the transpose permutation) is static numpy —
it never enters the jit trace as data dependencies; only block VALUES are
traced, so the whole layer is differentiable and jit/scan-compatible.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import shard_map
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from .._deprecation import deprecated
from ..core.bsr import BSR, magnitude_block_mask
from ..core.crs import CRS
from ..kernels import ops
from .pattern import (FamilyOps, SparsityPattern, expand_block_mask,
                      magnitude_mask, register_family)


@dataclasses.dataclass(frozen=True)
class SparseLinearMeta:
    """Static metadata for one sparse weight (hashable, jit-static).

    ``row_of``/``col_of`` (and their ``t_`` twins) are the KERNEL block
    lists: they include one explicit zero tile per empty block-row (the
    kernel writes each output block-row from its block run — an absent row
    would stay unwritten) plus the trailing sentinel. ``vpos[q]`` is the
    slot of real (trainable) block ``q`` inside that padded sequence; pad
    slots hold zeros and receive no gradient.
    """
    d_in: int
    d_out: int
    block: int
    row_of: Tuple[int, ...]          # fwd BSR (W^T: out-major) + sentinel
    col_of: Tuple[int, ...]
    vpos: Tuple[int, ...]            # real block -> slot in padded fwd list
    t_perm: Tuple[int, ...]          # permutation fwd blocks -> bwd blocks
    t_row_of: Tuple[int, ...]        # bwd BSR (W: in-major) + sentinel
    t_col_of: Tuple[int, ...]
    t_vpos: Tuple[int, ...]          # real block -> slot in padded bwd list
    # the lifecycle pattern this meta was packed for; compare=False keeps
    # it out of the generated __eq__/__hash__ (two equal metas from the
    # same pattern snapshot still hit one jit cache entry)
    pattern: Any = dataclasses.field(default=None, compare=False,
                                     repr=False)

    @property
    def nnz(self) -> int:
        return len(self.vpos)

    @property
    def n_block_rows(self) -> int:
        return self.d_out // self.block

    @property
    def n_block_rows_t(self) -> int:
        return self.d_in // self.block


@dataclasses.dataclass
class SparseLinearParams:
    values: jnp.ndarray              # (nnz, block, block) — W^T blocks
    meta: SparseLinearMeta

    @property
    def pattern(self) -> "SparsityPattern | None":
        return self.meta.pattern


def _register_params_pytree(cls) -> None:
    """Values is the one traced leaf; the meta rides as aux data with
    identity hash/eq. Registered WITH keys so checkpoint key-paths name
    the leaf ``.../values`` instead of a bare flat index."""
    jax.tree_util.register_pytree_with_keys(
        cls,
        lambda p: (((jax.tree_util.GetAttrKey("values"), p.values),),
                   p.meta),
        lambda meta, children: cls(children[0], meta))


_register_params_pytree(SparseLinearParams)


# Kernel block lists with explicit zero tiles for empty block-rows — the
# single source of this invariant lives next to the kernel prep.
_bsr_meta = ops.bsr_kernel_meta


def real_blocks(meta: SparseLinearMeta) -> Tuple[np.ndarray, np.ndarray]:
    """(block-row, block-col) of each real (trainable) block, in values
    order — the padded kernel lists minus the injected zero tiles."""
    vpos = np.asarray(meta.vpos, dtype=np.int64)
    return (np.asarray(meta.row_of[:-1], np.int32)[vpos],
            np.asarray(meta.col_of, np.int32)[vpos])


def _bsr_init(key, d_in: int, d_out: int, block: int,
              density: float, scale: float = 0.02,
              dtype=jnp.float32) -> SparseLinearParams:
    """Initialize a dense weight, magnitude-prune to block density, pack."""
    w = np.asarray(jax.random.normal(key, (d_in, d_out))) * scale
    wt = np.ascontiguousarray(w.T)                     # (out, in)
    mask = magnitude_block_mask(wt, (block, block), density)
    return _bsr_from_mask(w, mask, block, dtype=dtype)


def _bsr_from_mask(w: np.ndarray, mask: np.ndarray, block: int,
                   dtype=jnp.float32, *,
                   _pattern: "SparsityPattern | None" = None
                   ) -> SparseLinearParams:
    """Pack a dense W (d_in, d_out) under an explicit block-occupancy mask
    of W^T (out-major, shape (d_out//block, d_in//block)).

    ``_pattern`` is the lifecycle-internal path (``pattern.repack``): the
    evolved pattern rides in instead of being minted from ``mask``."""
    d_in, d_out = w.shape
    wt = np.ascontiguousarray(np.asarray(w).T)         # (out, in)
    fwd = BSR.from_mask(wt, mask, (block, block))      # W^T blocks
    bwd = BSR.from_mask(np.ascontiguousarray(np.asarray(w)),
                        mask.T, (block, block))        # W blocks
    row_of, col_of, vpos = _bsr_meta(fwd)
    t_row_of, t_col_of, t_vpos = _bsr_meta(bwd)
    # permutation: fwd block p at (r, c) -> bwd block at (c, r)
    fwd_pos = {}
    p = 0
    for r in range(fwd.n_block_rows):
        for q in range(fwd.row_ptr[r], fwd.row_ptr[r + 1]):
            fwd_pos[(r, int(fwd.col_idx[q]))] = p
            p += 1
    perm = []
    for r in range(bwd.n_block_rows):
        for q in range(bwd.row_ptr[r], bwd.row_ptr[r + 1]):
            perm.append(fwd_pos[(int(bwd.col_idx[q]), r)])
    if _pattern is None:
        _pattern = SparsityPattern(expand_block_mask(mask, block))
    meta = SparseLinearMeta(
        d_in, d_out, block,
        tuple(int(x) for x in row_of), tuple(int(x) for x in col_of),
        tuple(int(x) for x in vpos),
        tuple(perm),
        tuple(int(x) for x in t_row_of), tuple(int(x) for x in t_col_of),
        tuple(int(x) for x in t_vpos), pattern=_pattern)
    _pattern.packed["bsr"] = meta
    return SparseLinearParams(jnp.asarray(fwd.values, dtype), meta)


# ----------------------------------------------------------------------
_BN = 128        # token-tile width of the kernel's N dimension


def _pad_tokens(xt: jnp.ndarray) -> jnp.ndarray:
    t = xt.shape[1]
    tp = -(-t // _BN) * _BN
    return jnp.pad(xt, ((0, 0), (0, tp - t)))


def _pad_slots(values: jnp.ndarray, vpos: Tuple[int, ...],
               n_slots: int) -> jnp.ndarray:
    """Scatter real block values into the zero-tile-padded kernel slot
    sequence (identity when no block-row was empty)."""
    if n_slots == values.shape[0]:
        return values
    return jnp.zeros((n_slots,) + values.shape[1:], values.dtype
                     ).at[jnp.asarray(vpos, jnp.int32)].set(values)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sparse_mm(values, x, meta: SparseLinearMeta):
    """y[T, out] = x[T, in] @ W, W^T stored as BSR values."""
    yt = ops.bsr_matmul_arrays(
        jnp.asarray(meta.row_of, jnp.int32),
        jnp.asarray(meta.col_of, jnp.int32),
        _pad_slots(values, meta.vpos, len(meta.col_of)),
        _pad_tokens(x.T), n_block_rows=meta.n_block_rows)
    return yt[:, :x.shape[0]].T


def _sparse_mm_fwd(values, x, meta):
    return _sparse_mm(values, x, meta), (values, x)


def _sparse_mm_bwd(meta, res, dy):
    values, x = res
    blk = meta.block
    # dx = dy @ W^T : spmm with transposed metadata; block values are the
    # fwd blocks permuted + per-block transposed.
    tvals = jnp.transpose(values[jnp.asarray(meta.t_perm, jnp.int32)],
                          (0, 2, 1))
    dxt = ops.bsr_matmul_arrays(
        jnp.asarray(meta.t_row_of, jnp.int32),
        jnp.asarray(meta.t_col_of, jnp.int32),
        _pad_slots(tvals, meta.t_vpos, len(meta.t_col_of)),
        _pad_tokens(dy.T), n_block_rows=meta.n_block_rows_t)
    dx = dxt[:, :dy.shape[0]].T
    # dW^T blocks: block p at (r=out-block, c=in-block):
    #   dWt[p] = dy_block(r)^T ... careful: y^T = Wt x^T; dWt[p] =
    #   dy^T[r-block rows] @ x^T[c-block cols]^T = dy[:, r]^T x[:, c]
    # Gradients only for the REAL blocks — injected zero tiles stay frozen.
    g_rows, g_cols = real_blocks(meta)
    row_of = jnp.asarray(g_rows, jnp.int32)
    col_of = jnp.asarray(g_cols, jnp.int32)
    t = dy.shape[0]
    dyb = dy.T.reshape(meta.n_block_rows, blk, t)          # (R, blk, T)
    xb = x.T.reshape(meta.n_block_rows_t, blk, t)          # (C, blk, T)
    dvals = jnp.einsum("pbt,pct->pbc", dyb[row_of], xb[col_of],
                       preferred_element_type=jnp.float32)
    return dvals.astype(values.dtype), dx.astype(x.dtype)


_sparse_mm.defvjp(_sparse_mm_fwd, _sparse_mm_bwd)


def _bsr_apply(p: SparseLinearParams, x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., d_in) -> (..., d_out); differentiable wrt values and x."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, p.meta.d_in)
    y = _sparse_mm(p.values, x2, p.meta)
    return y.reshape(*lead, p.meta.d_out)


# ----------------------------------------------------------------------
# InCRS-backed linear: unstructured sparsity through the FUSED SpMM kernel,
# TRAINABLE end-to-end.
#
# Where SparseLinear needs block structure (whole MXU tiles skipped),
# InCRSLinear handles element-level sparsity: the weight is stored as
# section stripes (built once at init from the packed counter-vectors via
# ``ops.prep_sections``) and multiplied through ``ops.incrs_spmm``. The
# backward pass keeps the paper's "only useful computation" property:
#
#   y  = x @ W            fused SpMM over W^T stripes (d_out, d_in)
#   dx = dy @ W^T         a SECOND fused SpMM over the TRANSPOSED stripes
#                         (d_in, d_out), whose values are a precomputed
#                         gather (``t_gather``) of the forward values
#   dW^T                  restricted to the live non-zeros via a gather
#                         over the section-stripe ``idx`` — T MACs per
#                         non-zero, never the dense (d_out, d_in) outer
#                         product
#
# The stripe ``idx`` arrays are static metadata (never traced as data
# dependencies); only ``values`` is a pytree leaf, so the layer is an
# optimizer-visible differentiable parameter like any dense weight.


@dataclasses.dataclass(frozen=True, eq=False)
class InCRSLinearMeta:
    """Static metadata of one trainable InCRS weight.

    ``eq=False`` -> identity hash/eq: the meta rides as pytree aux data and
    as a ``custom_vjp`` nondiff argument, where identity semantics keep jit
    caches stable (array-valued fields would make generated __eq__ raise).
    """
    fwd_idx: jnp.ndarray      # (Si, Op, smax) int32 — W^T stripes, -1 pad
    bwd_idx: jnp.ndarray      # (So, Ip, smax_t) int32 — W stripes, -1 pad
    t_gather: jnp.ndarray     # (So*Ip*smax_t,) int32 — bwd slot -> flat fwd
    #                           slot (the one-past-the-end slot reads 0.0)
    d_in: int
    d_out: int
    section: int
    nnz: int                  # live non-zeros (the host InCRS itself is NOT
    #                           kept — it would pin a duplicate weight copy)
    block: int = 32           # InCRS counter block (B_DEFAULT) — a repack
    #                           rebuilds the counters at the same granularity
    pattern: Any = None       # the lifecycle SparsityPattern of this meta


@dataclasses.dataclass
class InCRSLinearParams:
    values: jnp.ndarray       # (Si, Op, smax) f32 — the trainable leaf
    meta: InCRSLinearMeta

    @property
    def pattern(self) -> "SparsityPattern | None":
        return self.meta.pattern

    @property
    def d_in(self) -> int:
        return self.meta.d_in

    @property
    def d_out(self) -> int:
        return self.meta.d_out

    @property
    def nnz(self) -> int:
        return self.meta.nnz

    @property
    def density(self) -> float:
        return self.meta.nnz / float(self.meta.d_in * self.meta.d_out)

    @property
    def prep(self) -> "ops.PreparedOperand":
        """Device-ready W^T operand view over the CURRENT values — what
        ``serve.SpMMEngine`` consumes."""
        return ops.PreparedOperand(self.meta.fwd_idx, self.values,
                                   (self.meta.d_out, self.meta.d_in),
                                   self.meta.section)


_register_params_pytree(InCRSLinearParams)


def _transpose_gather(fwd_idx: np.ndarray, bwd_idx: np.ndarray,
                      section: int, d_in: int) -> np.ndarray:
    """Map every bwd stripe slot to the flat fwd slot holding the same
    non-zero (pad slots -> the extra zero slot at index fwd_idx.size).

    Keys are the global (out, in) coordinates: fwd slot (s, r, k) holds
    W^T[r, idx + s*section]; bwd slot (s', r', k') holds W[r', idx' +
    s'*section] = W^T[idx' + s'*section, r'].
    """
    s_f, r_f, _ = np.indices(fwd_idx.shape)
    fmask = fwd_idx >= 0
    fkey = (r_f[fmask].astype(np.int64) * d_in
            + fwd_idx[fmask] + s_f[fmask].astype(np.int64) * section)
    fpos = np.flatnonzero(fmask.ravel())
    order = np.argsort(fkey)
    fkey, fpos = fkey[order], fpos[order]
    s_b, r_b, _ = np.indices(bwd_idx.shape)
    bmask = bwd_idx >= 0
    bkey = ((bwd_idx[bmask].astype(np.int64)
             + s_b[bmask].astype(np.int64) * section) * d_in + r_b[bmask])
    where = np.searchsorted(fkey, bkey)
    # Clip before the probe: a bkey beyond every fkey must surface as the
    # invariant message below, not as an IndexError inside it.
    ok = bkey.size == fkey.size and np.array_equal(
        fkey[np.clip(where, 0, max(fkey.size - 1, 0))] if fkey.size
        else fkey, bkey)
    # Internal invariant of the packer, not caller input; -O strips it
    # but the gather below still lands on the sentinel row and the
    # transpose-check test catches regressions.  # lint: allow-assert
    assert ok, \
        "fwd/bwd stripe non-zero sets must be transposes of each other"
    t_gather = np.full(bwd_idx.size, fwd_idx.size, dtype=np.int32)
    t_gather[np.flatnonzero(bmask.ravel())] = fpos[where]
    return t_gather


def _resolve_pattern(w: np.ndarray, density, mask,
                     _pattern) -> SparsityPattern:
    """One rule for every constructor: an explicit lifecycle pattern wins;
    else an explicit element mask of W (slots it keeps stay live even at
    value 0.0); else a global-threshold magnitude selection at ``density``
    (None -> exactly the non-zeros, the historical from-dense behavior)."""
    if _pattern is not None:
        return _pattern
    if mask is not None:
        if density is not None:
            raise ValueError("pass density OR mask, not both")
        return SparsityPattern(mask)
    return SparsityPattern(magnitude_mask(w, density))


def _pack_incrs(w: np.ndarray, pat: SparsityPattern, section: int,
                block: int) -> InCRSLinearParams:
    """Pack dense W values under ``pat`` into the trainable fused-kernel
    form — THE single-device InCRS packer; the public constructors are
    thin wrappers that only decide where the pattern comes from."""
    from ..core.incrs import InCRS
    d_in, d_out = w.shape
    if pat.shape != (d_in, d_out):
        raise ValueError(f"pattern mask shape {pat.shape} != weight shape "
                         f"{(d_in, d_out)}")
    wt = np.ascontiguousarray(np.asarray(w, np.float32).T)
    maskt = np.ascontiguousarray(pat.mask.T)
    incrs = InCRS.from_crs(CRS.from_mask(wt, maskt),
                           section=section, block=block)
    incrs_t = InCRS.from_crs(
        CRS.from_mask(np.ascontiguousarray(wt.T),
                      np.ascontiguousarray(maskt.T)),
        section=section, block=block)
    fwd_idx, fwd_val = ops.prep_sections(incrs, pad_rows_to=128)
    bwd_idx, _ = ops.prep_sections(incrs_t, pad_rows_to=128)
    t_gather = _transpose_gather(np.asarray(fwd_idx), np.asarray(bwd_idx),
                                 section, d_in)
    meta = InCRSLinearMeta(fwd_idx, bwd_idx, jnp.asarray(t_gather),
                           d_in, d_out, section, incrs.crs.nnz,
                           block=block, pattern=pat)
    pat.packed["incrs"] = meta
    return InCRSLinearParams(fwd_val, meta)


def _incrs_from_dense(w: np.ndarray, density: float | None = None,
                      section: int | None = None,
                      block: int | None = None, *,
                      mask: np.ndarray | None = None,
                      _pattern: SparsityPattern | None = None
                      ) -> InCRSLinearParams:
    """Pack a dense W (d_in, d_out) — optionally magnitude-pruned to
    element ``density``, or under an explicit element ``mask`` of W whose
    slots stay live even at value 0.0 — into the trainable fused-kernel
    form. For a fixed selection this is bit-identical to the historical
    prune-then-``InCRS.from_dense`` path."""
    from ..core.incrs import S_DEFAULT, B_DEFAULT
    section = S_DEFAULT if section is None else section
    block = B_DEFAULT if block is None else block
    w = np.asarray(w, np.float32)
    return _pack_incrs(w, _resolve_pattern(w, density, mask, _pattern),
                       section, block)


def _incrs_init(key, d_in: int, d_out: int, density: float,
                scale: float = 0.02, **kw) -> InCRSLinearParams:
    w = np.asarray(jax.random.normal(key, (d_in, d_out))) * scale
    return _incrs_from_dense(w, density, **kw)


def _incrs_stack_init(key, n_stages: int, d_in: int, d_out: int,
                      density: float, scale: float = 0.02,
                      **kw) -> InCRSLinearParams:
    """Shared-pattern parameter stack for pipeline-parallel stages: ONE
    InCRS sparsity pattern (so a single static meta serves every stage and
    the values leaf stacks along the stage axis, as ``train.pipeline``
    requires), independent per-stage values on that pattern."""
    k0, kv = jax.random.split(key)
    p0 = _incrs_init(k0, d_in, d_out, density, scale, **kw)
    live = np.asarray(p0.meta.fwd_idx) >= 0
    noise = np.asarray(jax.random.normal(
        kv, (n_stages - 1,) + p0.values.shape)) * scale
    rest = jnp.asarray((noise * live[None]).astype(np.float32))
    return InCRSLinearParams(
        jnp.concatenate([p0.values[None], rest], axis=0), p0.meta)


# Bound on the gathered-x block of one ``_stripe_dw`` step.
_DW_BLOCK_BYTES = 128 * 1024 * 1024


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _incrs_mm(values, x, meta: InCRSLinearMeta):
    """y[T, d_out] = x[T, d_in] @ W, with W^T stored as section stripes."""
    prep = ops.PreparedOperand(meta.fwd_idx, values,
                               (meta.d_out, meta.d_in), meta.section)
    return ops.spmm(prep, x.T).T


def _incrs_mm_fwd(values, x, meta):
    return _incrs_mm(values, x, meta), (values, x)


def _stripe_dw(idx: jnp.ndarray, section: int, x, dy) -> jnp.ndarray:
    """dW^T restricted to the live non-zeros of one stripe set.

    dW^T[r, c] = sum_t dy[t, r] x[t, c], evaluated ONLY at the live
    non-zeros: gather x's columns by the stripe idx, one T-length MAC per
    stored value — compute scales with nnz, not d_out*d_in. Scanned one
    section and one row block at a time so the gathered-x intermediate
    stays under ``_DW_BLOCK_BYTES``, not the whole padded-nnz x T (one
    whole section of a 4096 -> 14336 layer at 512 tokens is a 5 GB
    gather). Shared by the
    single-device and row-sharded VJPs (the sharded one calls it with a
    shard-local ``idx``/``dy`` panel).
    """
    n_sections, op, smax = idx.shape
    t = x.shape[0]
    gcol = jnp.where(
        idx >= 0,
        idx + section * jnp.arange(n_sections,
                                   dtype=jnp.int32)[:, None, None], 0)
    kp = n_sections * section
    xpt = jnp.pad(x.astype(jnp.float32),
                  ((0, 0), (0, kp - x.shape[1]))).T          # (kp, T)
    dyp = jnp.pad(dy.astype(jnp.float32),
                  ((0, 0), (0, op - dy.shape[1])))            # (T, Op)
    # Rows per step: the largest divisor of Op whose gathered-x block
    # (rows, smax, T) f32 stays under _DW_BLOCK_BYTES.
    rows = max((r for r in range(1, op + 1) if op % r == 0
                and r * smax * t * 4 <= _DW_BLOCK_BYTES), default=1)
    n_chunks = op // rows
    dyc = dyp.T.reshape(n_chunks, rows, t)           # (chunks, rows, T)

    def chunk_dw(_, c):
        gs, dyr = c                                  # (rows, smax), (rows, T)
        xg = jnp.take(xpt, gs, axis=0)               # (rows, smax, T)
        return None, jnp.einsum("rkt,rt->rk", xg, dyr,
                                preferred_element_type=jnp.float32)

    def section_dw(_, gs):                           # gs: (Op, smax)
        _, dv = jax.lax.scan(chunk_dw, None,
                             (gs.reshape(n_chunks, rows, smax), dyc))
        return None, dv.reshape(op, smax)

    _, dvals = jax.lax.scan(section_dw, None, gcol)
    return jnp.where(idx >= 0, dvals, 0.0)


def _incrs_mm_bwd(meta, res, dy):
    values, x = res
    # dx^T = W @ dy^T: the second fused SpMM, over the transposed stripes.
    # Their values are a gather of the forward values (t_gather maps pad
    # slots to the appended zero).
    flat = jnp.concatenate([values.reshape(-1),
                            jnp.zeros((1,), values.dtype)])
    tvals = flat[meta.t_gather].reshape(meta.bwd_idx.shape)
    tprep = ops.PreparedOperand(meta.bwd_idx, tvals,
                                (meta.d_in, meta.d_out), meta.section)
    dx = ops.spmm(tprep, dy.T).T
    dvals = _stripe_dw(meta.fwd_idx, meta.section, x, dy)
    return dvals.astype(values.dtype), dx.astype(x.dtype)


_incrs_mm.defvjp(_incrs_mm_fwd, _incrs_mm_bwd)


def _incrs_apply(p: InCRSLinearParams, x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., d_in) -> (..., d_out) through the fused InCRS SpMM;
    differentiable wrt ``p.values`` and ``x``."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, p.meta.d_in)
    y = _incrs_mm(p.values, x2, p.meta)
    return y.reshape(*lead, p.meta.d_out)


def incrs_to_dense_weight(p: InCRSLinearParams) -> np.ndarray:
    """Densify W (d_in, d_out) from the CURRENT values, for oracles/tests."""
    idx = np.asarray(p.meta.fwd_idx)
    vals = np.asarray(p.values)
    wt = np.zeros((idx.shape[1], idx.shape[0] * p.meta.section), np.float32)
    s, r, k = np.nonzero(idx >= 0)
    wt[r, idx[s, r, k] + s * p.meta.section] = vals[s, r, k]
    return wt[:p.meta.d_out, :p.meta.d_in].T


# ----------------------------------------------------------------------
# Row-sharded InCRSLinear: the paper's mesh scales by giving each row of the
# comparator array its OWN slice of the sparse operand while the dense input
# is shared (§IV). Here W^T (d_out, d_in) is split into n_shards contiguous
# OUTPUT-row panels — one per mesh device along the shard axes — and:
#
#   y  = x @ W      per-shard fused SpMM under shard_map; each device
#                   computes its own (T, shard_width) output panel, panels
#                   concatenate along d_out (no collective in forward)
#   dx = dy @ W^T   per-shard fused SpMM over the shard's TRANSPOSED
#                   stripes with the shard's dy panel, then ALL-REDUCED
#                   (psum) across the row shards — the contraction dim
#                   d_out is what the sharding split
#   dW^T            shard-LOCAL (no collective): a shard's weight rows only
#                   ever see its own dy panel
#
# Per-row arithmetic is identical to the single-device fused path (same
# stripe content, same tile shapes), so forward and dW match it bitwise;
# dx sums the same per-section contributions with a cross-device reduction
# tree, exact to reassociation of the f32 accumulation.


@dataclasses.dataclass(frozen=True, eq=False)
class ShardedInCRSLinearMeta:
    """Static metadata of one row-sharded trainable InCRS weight.

    All per-shard stripe arrays carry a leading shard axis placed with a
    ``NamedSharding`` over ``axes`` of ``mesh`` — a device only ever holds
    its own panel's metadata. ``eq=False`` -> identity hash/eq, same
    rationale as ``InCRSLinearMeta``.
    """
    fwd_idx: jnp.ndarray      # (S, Si, Op_s, smax) int32 — W^T shard stripes
    bwd_idx: jnp.ndarray      # (S, So_s, Ip, smax_t) int32 — W shard stripes
    t_gather: jnp.ndarray     # (S, So_s*Ip*smax_t) int32 — per-shard bwd
    #                           slot -> shard-local flat fwd slot
    d_in: int
    d_out: int
    section: int
    nnz: int
    mesh: Mesh
    axes: Tuple[str, ...]     # mesh axes the shard dim is split over
    shard_width: int          # d_out // n_shards output rows per shard
    block: int = 32           # InCRS counter block (B_DEFAULT)
    pattern: Any = None       # the lifecycle SparsityPattern of this meta

    @property
    def n_shards(self) -> int:
        return self.fwd_idx.shape[0]


@dataclasses.dataclass
class ShardedInCRSLinearParams:
    values: jnp.ndarray       # (S, Si, Op_s, smax) f32 — trainable leaf,
    #                           NamedSharding over the shard axes
    meta: ShardedInCRSLinearMeta

    @property
    def pattern(self) -> "SparsityPattern | None":
        return self.meta.pattern

    @property
    def d_in(self) -> int:
        return self.meta.d_in

    @property
    def d_out(self) -> int:
        return self.meta.d_out

    @property
    def nnz(self) -> int:
        return self.meta.nnz

    @property
    def density(self) -> float:
        return self.meta.nnz / float(self.meta.d_in * self.meta.d_out)

    @property
    def prep(self) -> "ops.ShardedPreparedOperand":
        """Row-sharded device-ready W^T operand over the CURRENT values —
        what a multi-device ``serve.SpMMEngine`` consumes directly."""
        return ops.ShardedPreparedOperand(
            self.meta.fwd_idx, self.values,
            (self.meta.d_out, self.meta.d_in), self.meta.section,
            self.meta.shard_width, self.meta.mesh, self.meta.axes)


_register_params_pytree(ShardedInCRSLinearParams)


def _resolve_shard_axes(mesh: Mesh | None, axis):
    """Pick the mesh + shard-axis spec (for ``ops.shard_axes``): explicit
    args win; otherwise the active ``models.sharding`` context supplies the
    mesh and its "incrs_shard" logical rule supplies the axes (falling
    back to every mesh axis)."""
    from ..models import sharding as sh
    if mesh is None:
        mesh = sh.current_mesh()
        if mesh is None:
            raise ValueError(
                "row-sharded InCRSLinear needs a mesh — pass mesh= or "
                "construct inside models.sharding.axis_rules(...)")
    if axis is None and sh.current_mesh() is mesh:
        rule = sh.resolve(sh.INCRS_STRIPE_AXES)[0]
        if rule is not None:
            axis = rule
    return mesh, axis


def _incrs_sharded_from_dense(
        w: np.ndarray, density: float | None = None, *,
        mask: np.ndarray | None = None, mesh: Mesh | None = None,
        axis=None, section: int | None = None,
        block: int | None = None,
        _pattern: SparsityPattern | None = None
        ) -> ShardedInCRSLinearParams:
    """Pack a dense W (d_in, d_out) — optionally magnitude-pruned with the
    SAME global threshold as the single-device packer — into the
    row-sharded trainable form: one contiguous d_out panel per device of
    ``mesh`` along ``axis`` (default: the "incrs_shard" logical rule of the
    active sharding context, else every mesh axis).

    ``mask`` (bool, same shape as ``w``, mutually exclusive with
    ``density``) fixes the sparsity pattern explicitly — slots the mask
    keeps stay live even at value 0.0 (used by ``incrs_linear_shard`` to
    preserve a trained layer's pattern exactly). ``_pattern`` is the
    lifecycle-internal path: the already-evolved pattern rides in."""
    from ..core.incrs import InCRS, S_DEFAULT, B_DEFAULT
    section = S_DEFAULT if section is None else section
    block = B_DEFAULT if block is None else block
    mesh, axis = _resolve_shard_axes(mesh, axis)
    axes, n_shards = ops.shard_axes(mesh, axis)
    w = np.asarray(w, np.float32)
    d_in, d_out = w.shape
    if d_out % n_shards:
        raise ValueError(f"d_out={d_out} must divide into {n_shards} "
                         f"row shards (mesh axes {axes})")
    sw = d_out // n_shards
    pat = _resolve_pattern(w, density, mask, _pattern)
    if pat.shape != (d_in, d_out):
        raise ValueError(f"pattern mask shape {pat.shape} != weight shape "
                         f"{(d_in, d_out)}")
    wt = np.ascontiguousarray(w.T)
    maskt = np.ascontiguousarray(pat.mask.T)
    per = []
    for s in range(n_shards):
        wts = np.ascontiguousarray(wt[s * sw:(s + 1) * sw])
        ms = np.ascontiguousarray(maskt[s * sw:(s + 1) * sw])
        inc = InCRS.from_crs(CRS.from_mask(wts, ms),
                             section=section, block=block)
        inc_t = InCRS.from_crs(
            CRS.from_mask(np.ascontiguousarray(wts.T),
                          np.ascontiguousarray(ms.T)),
            section=section, block=block)
        fi, fv = ops.prep_sections(inc, pad_rows_to=128)
        bi, _ = ops.prep_sections(inc_t, pad_rows_to=128)
        per.append((np.asarray(fi), np.asarray(fv), np.asarray(bi),
                    inc.crs.nnz))
    # Stack per-shard preps on a common slot width (extra slots are -1/0.0
    # pads, which expand to exact +0.0 in the kernel — per-row results stay
    # bit-identical to the unsharded prep).
    smax = max(p[0].shape[2] for p in per)
    smax_t = max(p[2].shape[2] for p in per)

    def pad3(a, s, fill):
        return np.pad(a, ((0, 0), (0, 0), (0, s - a.shape[2])),
                      constant_values=fill)

    fis = np.stack([pad3(p[0], smax, -1) for p in per])
    fvs = np.stack([pad3(p[1], smax, 0.0) for p in per])
    bis = np.stack([pad3(p[2], smax_t, -1) for p in per])
    tgs = np.stack([_transpose_gather(fis[s], bis[s], section, d_in)
                    for s in range(n_shards)])
    sharding = NamedSharding(mesh, P(axes))
    put = lambda a: jax.device_put(jnp.asarray(a), sharding)
    meta = ShardedInCRSLinearMeta(
        put(fis), put(bis), put(tgs), d_in, d_out, section,
        sum(p[3] for p in per), mesh, axes, sw, block=block, pattern=pat)
    pat.packed["incrs_sharded"] = meta
    return ShardedInCRSLinearParams(put(fvs), meta)


def _incrs_sharded_init(key, d_in: int, d_out: int, density: float,
                        scale: float = 0.02,
                        **kw) -> ShardedInCRSLinearParams:
    w = np.asarray(jax.random.normal(key, (d_in, d_out))) * scale
    return _incrs_sharded_from_dense(w, density, **kw)


def _incrs_shard(p: InCRSLinearParams, *, mesh: Mesh | None = None,
                 axis=None) -> ShardedInCRSLinearParams:
    """Re-shard a trained single-device ``InCRSLinearParams`` across a mesh
    (values and pattern preserved — e.g. train on one device, deploy the
    SAME weights into multi-device serving). The layer's
    ``SparsityPattern`` rides along unchanged (same lineage uid and
    version — the sharded pack registers as a SECOND packed form of the
    same snapshot), so a trained value that happens to be exactly 0.0
    stays a trainable slot instead of silently leaving the pattern."""
    return _incrs_sharded_from_dense(
        incrs_to_dense_weight(p), mesh=mesh, axis=axis,
        section=p.meta.section, block=p.meta.block, _pattern=p.pattern)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _incrs_mm_sharded(values, x, meta: ShardedInCRSLinearMeta):
    """y[T, d_out] = x[T, d_in] @ W with W^T row-sharded: each device runs
    the fused SpMM over its own stripe panel; panels concatenate on d_out."""
    ax = meta.axes

    def local(v, fidx, xl):
        prep1 = ops.PreparedOperand(fidx[0], v[0],
                                    (meta.shard_width, meta.d_in),
                                    meta.section)
        return ops.spmm(prep1, xl.T).T                # (T, shard_width)

    return shard_map(local, mesh=meta.mesh,
                     in_specs=(P(ax), P(ax), P()),
                     out_specs=P(None, ax), check_vma=False)(
        values, meta.fwd_idx, x)


def _incrs_mm_sharded_fwd(values, x, meta):
    return _incrs_mm_sharded(values, x, meta), (values, x)


def _incrs_mm_sharded_bwd(meta, res, dy):
    values, x = res
    ax = meta.axes

    def local(v, fidx, bidx, tg, dyl, xl):
        v1, fidx1, bidx1, tg1 = v[0], fidx[0], bidx[0], tg[0]
        # dx: the shard's transposed-stripe fused SpMM sees only the
        # shard's dy panel (its slice of the d_out contraction), so the
        # partial products MUST be summed across row shards.
        flat = jnp.concatenate([v1.reshape(-1), jnp.zeros((1,), v1.dtype)])
        tvals = flat[tg1].reshape(bidx1.shape)
        tprep = ops.PreparedOperand(bidx1, tvals,
                                    (meta.d_in, meta.shard_width),
                                    meta.section)
        dx = jax.lax.psum(ops.spmm(tprep, dyl.T).T, ax)
        # dW: shard-local — this shard's weight rows only ever meet its
        # own dy panel; no collective.
        dvals = _stripe_dw(fidx1, meta.section, xl, dyl)
        return dvals[None], dx

    dvals, dx = shard_map(local, mesh=meta.mesh,
                          in_specs=(P(ax), P(ax), P(ax), P(ax),
                                    P(None, ax), P()),
                          out_specs=(P(ax), P()), check_vma=False)(
        values, meta.fwd_idx, meta.bwd_idx, meta.t_gather, dy, x)
    return dvals.astype(values.dtype), dx.astype(x.dtype)


_incrs_mm_sharded.defvjp(_incrs_mm_sharded_fwd, _incrs_mm_sharded_bwd)


def _incrs_sharded_apply(p: ShardedInCRSLinearParams,
                         x: jnp.ndarray) -> jnp.ndarray:
    """x: (..., d_in) -> (..., d_out) through per-shard fused SpMMs;
    differentiable wrt ``p.values`` and ``x``."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, p.meta.d_in)
    y = _incrs_mm_sharded(p.values, x2, p.meta)
    return y.reshape(*lead, p.meta.d_out)


def incrs_sharded_to_dense_weight(p: ShardedInCRSLinearParams) -> np.ndarray:
    """Densify W (d_in, d_out) from the CURRENT sharded values (gathers to
    host — for oracles/tests only)."""
    idx = np.asarray(p.meta.fwd_idx)                 # (S, Si, Op_s, smax)
    vals = np.asarray(p.values)
    sw, section = p.meta.shard_width, p.meta.section
    wt = np.zeros((p.meta.d_out, idx.shape[1] * section), np.float32)
    for s in range(idx.shape[0]):
        ss, r, k = np.nonzero(idx[s] >= 0)
        wt[s * sw + r, idx[s][ss, r, k] + ss * section] = vals[s][ss, r, k]
    return wt[:, :p.meta.d_in].T


def to_dense(p: SparseLinearParams) -> jnp.ndarray:
    """Densify W (d_in, d_out) for oracles/tests."""
    blk = p.meta.block
    out = jnp.zeros((p.meta.d_out, p.meta.d_in), p.values.dtype)
    rows, cols = real_blocks(p.meta)
    for q, (r, c) in enumerate(zip(rows, cols)):
        out = out.at[r * blk:(r + 1) * blk, c * blk:(c + 1) * blk].set(
            p.values[q])
    return out.T


# ----------------------------------------------------------------------
# Lifecycle family registrations: every params class above plugs into the
# shared ``sparse.pattern`` lifecycle through the same four operations —
# repack / magnitude_repack / repack_onto never branch on the family.


def _bsr_pack_values(meta: SparseLinearMeta, w: np.ndarray) -> jnp.ndarray:
    """Dense W -> (nnz, block, block) W^T tiles of meta's REAL blocks."""
    blk = meta.block
    wt = np.ascontiguousarray(np.asarray(w, np.float32).T)
    tiles = wt.reshape(meta.n_block_rows, blk, meta.d_in // blk,
                       blk).transpose(0, 2, 1, 3)
    rows, cols = real_blocks(meta)
    return jnp.asarray(tiles[rows, cols])


def _incrs_pack_values(meta: InCRSLinearMeta, w: np.ndarray) -> jnp.ndarray:
    """Dense W -> (Si, Op, smax) stripe values of meta's live slots."""
    idx = np.asarray(meta.fwd_idx)
    wt = np.asarray(w, np.float32).T
    kp = idx.shape[0] * meta.section
    wtp = np.zeros((idx.shape[1], kp), np.float32)
    wtp[:wt.shape[0], :wt.shape[1]] = wt
    vals = np.zeros(idx.shape, np.float32)
    s, r, k = np.nonzero(idx >= 0)
    vals[s, r, k] = wtp[r, idx[s, r, k] + s * meta.section]
    return jnp.asarray(vals)


def _sharded_pack_values(meta: ShardedInCRSLinearMeta,
                         w: np.ndarray) -> jnp.ndarray:
    """Dense W -> (S, Si, Rp, smax) per-shard stripe values, placed with
    the meta's NamedSharding like the packer's values leaf."""
    idx = np.asarray(meta.fwd_idx)
    wt = np.asarray(w, np.float32).T
    sw, section = meta.shard_width, meta.section
    kp = idx.shape[1] * section
    vals = np.zeros(idx.shape, np.float32)
    for s in range(idx.shape[0]):
        panel = np.zeros((idx.shape[2], kp), np.float32)
        rows = wt[s * sw:(s + 1) * sw]
        panel[:rows.shape[0], :rows.shape[1]] = rows
        ss, r, k = np.nonzero(idx[s] >= 0)
        vals[s][ss, r, k] = panel[r, idx[s][ss, r, k] + ss * section]
    return jax.device_put(jnp.asarray(vals),
                          NamedSharding(meta.mesh, P(meta.axes)))


register_family(SparseLinearParams, FamilyOps(
    "bsr",
    to_dense=lambda n: np.asarray(to_dense(n), np.float32),
    pack=lambda w, pat, like: _bsr_from_mask(
        w, pat.block_mask(like.meta.block), like.meta.block,
        dtype=like.values.dtype, _pattern=pat),
    pack_values=_bsr_pack_values,
    default_mask=lambda w, d, n: magnitude_mask(w, d, block=n.meta.block),
    granularity="block"))

register_family(InCRSLinearParams, FamilyOps(
    "incrs",
    to_dense=incrs_to_dense_weight,
    pack=lambda w, pat, like: _pack_incrs(
        w, pat, like.meta.section, like.meta.block),
    pack_values=_incrs_pack_values,
    default_mask=lambda w, d, n: magnitude_mask(w, d)))

register_family(ShardedInCRSLinearParams, FamilyOps(
    "incrs_sharded",
    to_dense=incrs_sharded_to_dense_weight,
    pack=lambda w, pat, like: _incrs_sharded_from_dense(
        w, mesh=like.meta.mesh, axis=like.meta.axes,
        section=like.meta.section, block=like.meta.block, _pattern=pat),
    pack_values=_sharded_pack_values,
    default_mask=lambda w, d, n: magnitude_mask(w, d)))


# ----------------------------------------------------------------------
# One-release deprecation shims: the historical per-family constructor and
# apply names delegate to the implementations above (bit-identical outputs
# — the parity suite in tests/test_api.py pins this). New code goes through
# ``sparse.SparseSpec`` / ``sparse.Linear`` / ``sparse.apply``.
sparse_linear_init = deprecated(
    "sparse_linear_init", _bsr_init,
    "sparse.Linear.init(key, d_in, d_out, SparseSpec('bsr', block=...))")
sparse_linear_from_mask = deprecated(
    "sparse_linear_from_mask", _bsr_from_mask,
    "sparse.Linear.from_dense(w, SparseSpec('bsr', mask=..., block=...))")
sparse_linear_apply = deprecated(
    "sparse_linear_apply", _bsr_apply, "sparse.apply(p, x)")
incrs_linear_from_dense = deprecated(
    "incrs_linear_from_dense", _incrs_from_dense,
    "sparse.Linear.from_dense(w, SparseSpec('incrs', ...))")
incrs_linear_init = deprecated(
    "incrs_linear_init", _incrs_init,
    "sparse.Linear.init(key, d_in, d_out, SparseSpec('incrs', ...))")
incrs_linear_stack_init = deprecated(
    "incrs_linear_stack_init", _incrs_stack_init,
    "sparse.stack_init(key, n_stages, d_in, d_out, spec)")
incrs_linear_apply = deprecated(
    "incrs_linear_apply", _incrs_apply, "sparse.apply(p, x)")
incrs_linear_from_dense_sharded = deprecated(
    "incrs_linear_from_dense_sharded", _incrs_sharded_from_dense,
    "sparse.Linear.from_dense(w, SparseSpec('incrs', mesh=...))")
incrs_linear_sharded_init = deprecated(
    "incrs_linear_sharded_init", _incrs_sharded_init,
    "sparse.Linear.init(key, d_in, d_out, SparseSpec('incrs', mesh=...))")
incrs_linear_shard = deprecated(
    "incrs_linear_shard", _incrs_shard, "sparse.Linear.shard(mesh=...)")
incrs_linear_sharded_apply = deprecated(
    "incrs_linear_sharded_apply", _incrs_sharded_apply, "sparse.apply(p, x)")
