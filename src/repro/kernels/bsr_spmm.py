"""Block-sparse x dense matmul steered by InCRS-style prefix counters.

This is the paper's core insight adapted to the TPU memory/compute model:

* The paper's comparator mesh finds the "useful computation" at element
  granularity. The MXU is a dense 128x128 systolic array, so usefulness is
  decided at BLOCK granularity instead: only non-zero (bm, bk) tiles of A
  flow through the MXU.

* The paper's InCRS counter-vectors answer "how many non-zeros precede this
  block?" in O(1). Here the BSR ``row_ptr`` prefix counters answer "how many
  non-zero blocks precede this block-row" and are *scalar-prefetched* so the
  pipeline can compute every tile's HBM address one grid-step ahead —
  exactly the role the counter-vector plays in the paper's access engine.

* The grid iterates over the NON-ZERO blocks only (row-major), so compute
  and HBM traffic scale with nnz_blocks, not with the dense shape. Output
  revisiting is legal because consecutive grid steps hit the same output
  tile until the (prefetched) row id changes.

Inputs are the flat arrays prepared by ``ops.prep_bsr`` (which guarantees
at least one block per block-row, padding empty rows with a zero tile, so
every output row is written).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(row_of_ref, col_of_ref, values_ref, b_ref, o_ref, acc_ref):
    t = pl.program_id(1)
    n_blk = pl.num_programs(1)

    # Start of a new output row of blocks? (prefix-counter semantics:
    # row_of is the expansion of the InCRS-style row_ptr counters.)
    first = (t == 0) | (row_of_ref[t] != row_of_ref[jnp.maximum(t - 1, 0)])

    @pl.when(first)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(values_ref[0], b_ref[...],
                            preferred_element_type=jnp.float32)

    # Last block of this output row -> write back.
    last = (t == n_blk - 1) | (row_of_ref[t + 1] != row_of_ref[t])

    @pl.when(last)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("n_block_rows", "bn", "interpret"))
def bsr_spmm(row_of: jnp.ndarray, col_of: jnp.ndarray, values: jnp.ndarray,
             b: jnp.ndarray, *, n_block_rows: int, bn: int = 128,
             interpret: bool = False) -> jnp.ndarray:
    """C[M, N] = BSR(A)[M, K] @ B[K, N].

    row_of  : (nnz_blocks + 1,) int32 — block-row of each stored block
              (sorted, one sentinel repeat at the end)
    col_of  : (nnz_blocks,) int32 — block-column of each stored block
    values  : (nnz_blocks, bm, bk) — the dense non-zero tiles
    b       : (K, N) dense right operand
    """
    nnz, bm, bk = values.shape
    k, n = b.shape
    if n % bn != 0:
        raise ValueError(f"n={n} must be a multiple of bn={bn} "
                         "(ops.spmm_bsr pads)")
    grid = (n // bn, nnz)

    return pl.pallas_call(
        _kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,          # row_of, col_of
            grid=grid,
            in_specs=[
                # one non-zero tile per step
                pl.BlockSpec((1, bm, bk),
                             lambda j, t, row_of, col_of: (t, 0, 0)),
                # the B tile this block multiplies: block-row col_of[t]
                pl.BlockSpec((bk, bn),
                             lambda j, t, row_of, col_of: (col_of[t], j)),
            ],
            out_specs=pl.BlockSpec(
                (bm, bn), lambda j, t, row_of, col_of: (row_of[t], j)),
            scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((n_block_rows * bm, n), b.dtype),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(row_of, col_of, values, b)
