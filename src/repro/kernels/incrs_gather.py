"""Counter-vector-driven column gather: InCRS section -> dense VMEM slab.

The paper's InCRS counters make *column-order* access to a row-stored sparse
matrix O(1)-locatable. On TPU, the consumer of such access is a matmul that
wants a dense (rows, section) slab in VMEM. This kernel performs the
decompression: per (row-tile, section) grid cell it scatters the section's
non-zeros (located on the host via the packed counter-vectors, see
``ops.prep_sections``) into a dense stripe with the same VPU expansion the
fused SpMM kernels use (``incrs_spmm._expand_stripe``).

The counter-vectors' role survives intact: the host-side ``prep_sections``
uses ONLY the 64-bit counter words (prefix + per-block counts) to compute
each section's value range — never scanning a row — which is exactly the
paper's b/2+1 access path, then the kernel turns sections into MXU-ready
dense slabs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .incrs_spmm import _expand_stripe


def _kernel(idx_ref, val_ref, o_ref, *, section: int):
    o_ref[...] = _expand_stripe(idx_ref[0], val_ref[0],
                                section).astype(o_ref.dtype)


@functools.partial(jax.jit,
                   static_argnames=("section", "bm", "interpret"))
def incrs_gather(idx: jnp.ndarray, val: jnp.ndarray, *, section: int = 256,
                 bm: int = 8, interpret: bool = False) -> jnp.ndarray:
    """Dense[M, n_sections * section] from padded per-section sparse rows.

    idx : (n_sections, M, smax) int32 local column within section, -1 = pad
    val : (n_sections, M, smax)
    """
    n_sections, m, smax = idx.shape
    if m % bm != 0:
        raise ValueError(f"m={m} must be a multiple of bm={bm}")
    grid = (m // bm, n_sections)
    return pl.pallas_call(
        functools.partial(_kernel, section=section),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, bm, smax), lambda i, s: (s, i, 0)),
            pl.BlockSpec((1, bm, smax), lambda i, s: (s, i, 0)),
        ],
        out_specs=pl.BlockSpec((bm, section), lambda i, s: (i, s)),
        out_shape=jax.ShapeDtypeStruct((m, n_sections * section),
                                       jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(idx, val)
