"""Conventional tiled MXU matmul — the paper's "conventional MM" baseline.

Classic three-level tiling: grid (M/bm, N/bn, K/bk); each step streams one
(bm, bk) x (bk, bn) pair through the MXU and accumulates into a VMEM f32
scratch tile, written back once per output tile. This is the Fig. 2a design
mapped to the TPU: the 128x128 MXU *is* the systolic mesh, and the k-grid
dimension is the operand stream.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _kernel(a_ref, b_ref, o_ref, acc_ref):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(a_ref[...], b_ref[...],
                            preferred_element_type=jnp.float32)

    @pl.when(pl.program_id(2) == pl.num_programs(2) - 1)
    def _done():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bm", "bn", "bk", "interpret"))
def dense_mm(a: jnp.ndarray, b: jnp.ndarray, *, bm: int = 128, bn: int = 128,
             bk: int = 128, interpret: bool = False) -> jnp.ndarray:
    """C = A @ B with explicit (bm, bn, bk) VMEM tiling.

    Shapes must be multiples of the tile sizes (ops.dense_mm pads).
    Output dtype follows A; accumulation is always f32.
    """
    m, k = a.shape
    k2, n = b.shape
    if k != k2 or m % bm or n % bn or k % bk:
        raise ValueError(f"shapes {(m, k, n)} must align to tiles "
                         f"{(bm, bn, bk)} (ops.dense_mm pads)")
    grid = (m // bm, n // bn, k // bk)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, t: (i, t)),
            pl.BlockSpec((bk, bn), lambda i, j, t: (t, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, t: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, n), a.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(a, b)
