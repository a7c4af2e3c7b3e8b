"""SpGEMM drivers: prep → condense → merge, plus output-density estimate.

``condense_merge_prepped`` is the traced core used by ``ops.spmm`` and the
plan layer: it takes both operands already in per-round padded form
(``ops.prep_rounds`` output), pads them to a common rmax exactly like
``ops.index_match_prepped`` does (this is what makes the two-pass result
bitwise identical to the fused reference), gates the launch through the
PR 8 ``LAUNCH_RULES`` static checks, and runs the two kernels.

``spgemm`` is the standalone convenience entry for CRS × CRS with the
output-density estimator choosing sparse-CRS vs dense output allocation.
"""
from __future__ import annotations

from typing import Optional, Tuple, Union

import numpy as np
import jax.numpy as jnp

from ..core.crs import CRS
from ..core.incrs import InCRS
from ..kernels import ops as _ops
from .kernels import spgemm_condense, spgemm_merge

#: estimated output density below which ``spgemm(output="auto")`` returns CRS
SPARSE_OUTPUT_THRESHOLD = 0.25


def _check_launch(stage: str, *, m: int, n: int, bm: int, bn: int,
                  rounds: int, n_rounds: int, rmax_a: int, rmax_b: int):
    from ..analysis import kernel_check as _kc
    vs = _kc.check_matched_config(
        stage, m=m, n=n, bm=bm, bn=bn, rounds=rounds, n_rounds=n_rounds,
        rmax_a=rmax_a, rmax_b=rmax_b, rules=_kc.LAUNCH_RULES)
    if vs:
        raise _kc.KernelConfigError(vs, context=f"spgemm {stage} launch")


def condense_merge_prepped(ai, av, bi, bv, *, rounds: int = 128,
                           bm: int = 128, bn: int = 128,
                           out_dtype=None,
                           interpret: bool | None = None,
                           check: bool = True):
    """C = A @ B.T from PRE-PREPPED per-round operands, via two passes.

    Pads both sides to a common rmax (same as ``index_match_prepped``),
    condenses every round window into its partial stripe, then merges the
    stripes in ascending round order. Returns the PADDED output — callers
    trim to the real (M, N). Bitwise identical to the fused reference on
    identical inputs.
    """
    interpret = _ops.resolve_interpret(interpret)
    if out_dtype is None:
        out_dtype = jnp.result_type(av.dtype, bv.dtype)
    rmax = max(ai.shape[2], bi.shape[2])
    ai = jnp.pad(ai, ((0, 0), (0, 0), (0, rmax - ai.shape[2])),
                 constant_values=-1)
    av = jnp.pad(av, ((0, 0), (0, 0), (0, rmax - av.shape[2])))
    bi = jnp.pad(bi, ((0, 0), (0, 0), (0, rmax - bi.shape[2])),
                 constant_values=-1)
    bv = jnp.pad(bv, ((0, 0), (0, 0), (0, rmax - bv.shape[2])))
    n_rounds, m, _ = ai.shape
    n = bi.shape[1]
    if check:
        _check_launch("condense", m=m, n=n, bm=bm, bn=bn, rounds=rounds,
                      n_rounds=n_rounds, rmax_a=rmax, rmax_b=rmax)
        _check_launch("merge", m=m, n=n, bm=bm, bn=bn, rounds=rounds,
                      n_rounds=n_rounds, rmax_a=rmax, rmax_b=rmax)
    stripes = spgemm_condense(ai, av, bi, bv, rounds=rounds, bm=bm, bn=bn,
                              interpret=interpret)
    return spgemm_merge(stripes, bm=bm, bn=bn, out_dtype=jnp.dtype(out_dtype),
                        interpret=interpret)


def estimate_output_density(a: CRS, bt: CRS, rounds: int = 128) -> float:
    """Estimated density of C = A @ Bt.T from per-round nnz counts alone.

    Within round window t a non-zero of A row i meets a non-zero of Bt
    row j iff they share a slot; modeling slots as uniform over R, the
    expected matched pairs for (i, j) are sum_t ca[i,t]*cb[j,t]/R, and
    P[C_ij != 0] ~= 1 - exp(-pairs). Aggregated over all (i, j) without
    materializing the M x N pair matrix.
    """
    m, k = a.shape
    if m == 0 or bt.shape[0] == 0:
        return 0.0
    n_rounds = max(1, -(-k // rounds))

    def _counts(crs):
        c = np.zeros((crs.shape[0], n_rounds), dtype=np.float64)
        if crs.nnz:
            row_of = np.repeat(np.arange(crs.shape[0]),
                               np.diff(crs.row_ptr).astype(np.int64))
            np.add.at(c, (row_of, crs.col_idx // rounds), 1)
        return c

    ca, cb = _counts(a), _counts(bt)
    # E[pairs] summed over all (i, j) = sum_t (sum_i ca) * (sum_j cb) / R
    pairs = float((ca.sum(axis=0) * cb.sum(axis=0)).sum()) / rounds
    mean_pairs = pairs / (m * bt.shape[0])
    return float(1.0 - np.exp(-mean_pairs))


def spgemm(a: CRS, b: Union[CRS, InCRS], *, rounds: int = 128,
           bm: int = 128, bn: int = 128,
           output: str = "auto",
           sparse_threshold: float = SPARSE_OUTPUT_THRESHOLD,
           interpret: bool | None = None
           ) -> Tuple[Union[CRS, np.ndarray], float]:
    """C = A @ B.T for sparse A and sparse B (row-stored), returning
    ``(C, estimated_density)`` where C is a CRS when the estimator
    predicts a sparse output (``output="auto"``) or as forced by
    ``output="crs"`` / ``output="dense"``.
    """
    if output not in ("auto", "crs", "dense"):
        raise ValueError(f"output must be 'auto', 'crs' or 'dense', "
                         f"got {output!r}")
    bt = b.crs if isinstance(b, InCRS) else b
    if a.shape[1] != bt.shape[1]:
        raise ValueError(f"inner dims disagree: A is {a.shape}, "
                         f"Bt is {bt.shape} (expected equal col counts)")
    est = estimate_output_density(a, bt, rounds)
    ai, av = _ops.prep_rounds(a, rounds, pad_rows_to=bm)
    bi, bv = _ops.prep_rounds(bt, rounds, pad_rows_to=bn)
    out = condense_merge_prepped(ai, av, bi, bv, rounds=rounds,
                                 bm=bm, bn=bn, interpret=interpret)
    dense = np.asarray(out[:a.shape[0], :bt.shape[0]])
    want_crs = output == "crs" or (output == "auto"
                                   and est < sparse_threshold)
    if want_crs:
        return CRS.from_dense(dense), est
    return dense, est
