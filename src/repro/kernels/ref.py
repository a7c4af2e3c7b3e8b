"""Pure-jnp reference oracles for every Pallas kernel in this package.

Each function is the mathematically-obvious implementation the kernels are
tested against (tests/test_kernels.py sweeps shapes/dtypes and asserts
allclose between the kernel in interpret mode and these oracles).
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np


def matmul(a: jnp.ndarray, b: jnp.ndarray) -> jnp.ndarray:
    """Dense C = A @ B in f32 accumulation."""
    return jnp.dot(a.astype(jnp.float32), b.astype(jnp.float32),
                   preferred_element_type=jnp.float32)


def bsr_to_dense(values, col_idx, row_ptr, shape, block):
    """Densify a BSR matrix (numpy, host-side)."""
    bm, bk = block
    out = np.zeros(shape, dtype=np.asarray(values).dtype)
    values = np.asarray(values)
    col_idx = np.asarray(col_idx)
    row_ptr = np.asarray(row_ptr)
    for br in range(shape[0] // bm):
        for p in range(row_ptr[br], row_ptr[br + 1]):
            bc = col_idx[p]
            out[br * bm:(br + 1) * bm, bc * bk:(bc + 1) * bk] = values[p]
    return out


def bsr_spmm(values, col_idx, row_ptr, shape, block, b) -> jnp.ndarray:
    """C = BSR(A) @ B via densify-then-matmul."""
    a = bsr_to_dense(values, col_idx, row_ptr, shape, block)
    return matmul(jnp.asarray(a), b)


def round_densify(idx, val, n_cols: int, rounds: int) -> jnp.ndarray:
    """Densify padded per-round sparse rows.

    idx : (n_rounds, M, rmax) int32 — LOCAL index in [0, rounds), -1 = pad
    val : (n_rounds, M, rmax)
    Returns dense (M, n_rounds * rounds)[:, :n_cols].
    """
    n_rounds, m, rmax = idx.shape
    iota = jnp.arange(rounds, dtype=jnp.int32)
    oh = (idx[..., None] == iota) & (idx[..., None] >= 0)
    dense = jnp.sum(oh * val[..., None].astype(jnp.float32), axis=2)
    return dense.transpose(1, 0, 2).reshape(m, n_rounds * rounds)[:, :n_cols]


def index_match_spmm(a_idx, a_val, b_idx, b_val, n_cols: int,
                     rounds: int) -> jnp.ndarray:
    """C = A @ B.T from the padded per-round sparse-row representation —
    the oracle for the round-synchronized index-matching kernel."""
    da = round_densify(a_idx, a_val, n_cols, rounds)
    db = round_densify(b_idx, b_val, n_cols, rounds)
    return matmul(da, db.T)


def incrs_decompress(idx, val, n_cols: int, section: int) -> jnp.ndarray:
    """Densify padded per-(section, row) sparse data (local column index
    within the section, -1 = pad) — oracle for the InCRS gather kernel."""
    return round_densify(idx, val, n_cols, section)
