"""Round-synchronized index-matching SpMM — the faithful Alg. 2 port.

The paper's synchronized mesh consumes both sparse operand streams in
lockstep *rounds* of R column indices, matching equal indices via per-node
comparators/buffers. A TPU has no per-lane comparator mesh, but the round
structure maps exactly onto the grid's k-dimension:

  per round k, each row's non-zeros falling in [k*R, (k+1)*R) are
  DENSIFIED into an R-wide VMEM stripe (one-hot scatter on the VPU: the
  comparator array), and the (bm, R) x (R, bn) product runs on the MXU.

The index comparison `a_index == b_index` of Alg. 2 is realized as the
one-hot expansion: two non-zeros multiply iff they land in the same round
slot — a (bm*R)-lane comparator per cycle instead of the paper's per-node
comparator, and the MXU plays the accumulator mesh. The operand buffers of
Alg. 2 (depth R) become the R-wide stripes themselves; the round barrier is
the grid step.

Inputs are padded per-round sparse rows from ``ops.prep_rounds``:
  idx (n_rounds, M, rmax) int32 local index in [0, R), -1 = padding
  val (n_rounds, M, rmax) values
Since at most R non-zeros fit in a round window, rmax <= R.

Computes C = A @ B.T (both operands row-stored — the paper's A x A^T
experiment setting).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .incrs_spmm import _expand_stripe


def _kernel(a_idx_ref, a_val_ref, b_idx_ref, b_val_ref, o_ref, acc_ref, *,
            rounds: int):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    da = _expand_stripe(a_idx_ref[0], a_val_ref[0], rounds)  # (bm, R)
    db = _expand_stripe(b_idx_ref[0], b_val_ref[0], rounds)  # (bn, R)
    acc_ref[...] += jax.lax.dot_general(
        da, db, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("rounds", "bm", "bn", "out_dtype",
                                    "interpret"))
def index_match_spmm(a_idx: jnp.ndarray, a_val: jnp.ndarray,
                     b_idx: jnp.ndarray, b_val: jnp.ndarray, *,
                     rounds: int = 128, bm: int = 128, bn: int = 128,
                     out_dtype=None, interpret: bool = False) -> jnp.ndarray:
    """C[M, N] = A[M, K] @ B[N, K].T from per-round padded sparse rows.

    The paper uses R=32; on TPU the stripe is the lane dimension so R=128
    is the hardware-aligned default (tests sweep both in interpret mode).

    Accumulation is always f32 in VMEM scratch; the single cast to
    ``out_dtype`` happens at the final flush (promote-in-wave, return in
    the operands' own dtype — same contract as the serve path since PR 3).
    ``out_dtype=None`` returns ``result_type(a_val, b_val)``.
    """
    if out_dtype is None:
        out_dtype = jnp.result_type(a_val.dtype, b_val.dtype)
    n_rounds, m, rmax_a = a_idx.shape
    n_rounds_b, n, rmax_b = b_idx.shape
    if n_rounds != n_rounds_b:
        raise ValueError(
            f"operand round counts differ: {n_rounds} vs {n_rounds_b}")
    if m % bm or n % bn:
        raise ValueError(f"shape {(m, n)} must align to tiles "
                         f"{(bm, bn)} (ops.spmm_index_match pads)")
    grid = (m // bm, n // bn, n_rounds)

    kernel = functools.partial(_kernel, rounds=rounds)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, rmax_a), lambda i, j, t: (t, i, 0)),
            pl.BlockSpec((1, bm, rmax_a), lambda i, j, t: (t, i, 0)),
            pl.BlockSpec((1, bn, rmax_b), lambda i, j, t: (t, j, 0)),
            pl.BlockSpec((1, bn, rmax_b), lambda i, j, t: (t, j, 0)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.dtype(out_dtype)),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a_idx, a_val, b_idx, b_val)
