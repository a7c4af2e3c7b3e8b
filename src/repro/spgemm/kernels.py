"""Condense/merge SpGEMM kernels — sparse × sparse via round stripes.

The fused ``index_match_spmm`` kernel densifies both operands' round
windows and accumulates the (bm, R) x (R, bn) product in a VMEM scratch
across the grid's round dimension. SpArch-style SpGEMM splits that into
two passes so each stage stays simple and independently provable:

  condense  per (i, j, t) grid step, densify A's and B's round-t windows
            and write the partial product into its own stripe of a
            (n_rounds, M, N) array — no scratch, no cross-step state,
            every grid axis parallel.
  merge     round-synchronized accumulation of the stripes back into the
            (M, N) output: classic init/accumulate/flush over the round
            axis with a f32 VMEM accumulator.

Summing stripe t in ascending round order in f32 reproduces *exactly* the
accumulation order of the fused kernel, so condense+merge is bitwise
identical to ``index_match_spmm`` on identically prepped operands — the
fused kernel stays the reference oracle (see tests/test_spgemm.py).

Inputs are per-round padded sparse rows from ``ops.prep_rounds`` for BOTH
operands (the RHS is sparse too — this is the A[M,K] @ B[N,K].T row-wise
product formulation, B row-stored like A):
  idx (n_rounds, rows, rmax) int32 local index in [0, R), -1 = padding
  val (n_rounds, rows, rmax) values
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..kernels.incrs_spmm import _expand_stripe


def _condense_kernel(a_idx_ref, a_val_ref, b_idx_ref, b_val_ref, s_ref, *,
                     rounds: int):
    da = _expand_stripe(a_idx_ref[0], a_val_ref[0], rounds)  # (bm, R)
    db = _expand_stripe(b_idx_ref[0], b_val_ref[0], rounds)  # (bn, R)
    s_ref[0, :, :] = jax.lax.dot_general(
        da, db, (((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32)


@functools.partial(jax.jit,
                   static_argnames=("rounds", "bm", "bn", "interpret"))
def spgemm_condense(a_idx: jnp.ndarray, a_val: jnp.ndarray,
                    b_idx: jnp.ndarray, b_val: jnp.ndarray, *,
                    rounds: int = 128, bm: int = 128, bn: int = 128,
                    interpret: bool = False) -> jnp.ndarray:
    """Partial stripes S[n_rounds, M, N]: S[t] = A_t @ B_t.T per round t.

    Each stripe holds the contribution of round window t; summing over the
    first axis (in ascending order — see ``spgemm_merge``) yields
    C = A @ B.T. Fully parallel: each grid step owns its output block.
    """
    n_rounds, m, rmax_a = a_idx.shape
    n_rounds_b, n, rmax_b = b_idx.shape
    if n_rounds != n_rounds_b:
        raise ValueError(
            f"operand round counts differ: {n_rounds} vs {n_rounds_b}")
    if m % bm or n % bn:
        raise ValueError(f"shape {(m, n)} must align to tiles "
                         f"{(bm, bn)} (spgemm.condense_merge_prepped pads)")
    grid = (m // bm, n // bn, n_rounds)

    kernel = functools.partial(_condense_kernel, rounds=rounds)
    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, rmax_a), lambda i, j, t: (t, i, 0)),
            pl.BlockSpec((1, bm, rmax_a), lambda i, j, t: (t, i, 0)),
            pl.BlockSpec((1, bn, rmax_b), lambda i, j, t: (t, j, 0)),
            pl.BlockSpec((1, bn, rmax_b), lambda i, j, t: (t, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, bm, bn), lambda i, j, t: (t, i, j)),
        out_shape=jax.ShapeDtypeStruct((n_rounds, m, n), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel")),
    )(a_idx, a_val, b_idx, b_val)


def _merge_kernel(s_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += s_ref[0, :, :]

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "out_dtype", "interpret"))
def spgemm_merge(stripes: jnp.ndarray, *,
                 bm: int = 128, bn: int = 128,
                 out_dtype=jnp.float32,
                 interpret: bool = False) -> jnp.ndarray:
    """C[M, N] = sum_t S[t] over the round axis, in ascending round order.

    Ascending-order f32 accumulation matches the fused reference kernel's
    accumulation order bit for bit; the cast to ``out_dtype`` happens once
    at flush, exactly like the fused kernel's final store.
    """
    n_rounds, m, n = stripes.shape
    if m % bm or n % bn:
        raise ValueError(f"stripe shape {(m, n)} must align to tiles "
                         f"{(bm, bn)}")
    grid = (m // bm, n // bn, n_rounds)

    return pl.pallas_call(
        _merge_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, bn), lambda i, j, t: (t, i, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), out_dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(stripes)
