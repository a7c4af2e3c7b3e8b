"""Property tests for prep_rounds and the matched-index product family.

Hypothesis drives random shapes/densities when installed (skips cleanly
via the ``_hyp`` shim otherwise); the parametrized tests below carry the
same coverage deterministically across density {0, 0.03, 0.5} x
R {32, 128}, so the guarantees hold even without hypothesis.
"""
import warnings

import numpy as np
import pytest

from _hyp import given, settings, st
from repro.core.crs import CRS
from repro.kernels import ops

DENSITIES = (0.0, 0.03, 0.5)
ROUNDS = (32, 128)


def _rand_pair(rng, m, n, k, density):
    A = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
    Bt = (rng.random((n, k)) < density) * rng.standard_normal((n, k))
    return A.astype(np.float32), Bt.astype(np.float32)


def _unprep(idx, val, rounds, k):
    """Invert prep_rounds: scatter per-round local slots back to dense."""
    n_rounds, mp, rmax = idx.shape
    out = np.zeros((mp, k), dtype=np.asarray(val).dtype)
    idx, val = np.asarray(idx), np.asarray(val)
    for t in range(n_rounds):
        live = idx[t] >= 0
        rows, slots = np.nonzero(live)
        cols = t * rounds + idx[t, rows, slots]
        keep = cols < k
        out[rows[keep], cols[keep]] = val[t, rows[keep], slots[keep]]
    return out


# ----------------------------------------------------------------------
@pytest.mark.parametrize("rounds", ROUNDS)
@pytest.mark.parametrize("density", DENSITIES)
def test_prep_rounds_roundtrip(rng, density, rounds):
    A, _ = _rand_pair(rng, 24, 1, 200, density)
    a = CRS.from_dense(A)
    ai, av = ops.prep_rounds(a, rounds, pad_rows_to=8)
    assert ai.shape == av.shape and ai.shape[1] % 8 == 0
    back = _unprep(ai, av, rounds, 200)
    np.testing.assert_array_equal(back[:24], A)
    assert (back[24:] == 0).all()
    # local indices stay inside the round window, pads are exactly -1
    ai_np = np.asarray(ai)
    assert ai_np.max(initial=-1) < rounds and ai_np.min(initial=-1) >= -1


@pytest.mark.parametrize("rounds", ROUNDS)
@pytest.mark.parametrize("density", DENSITIES)
def test_matched_product_vs_dense_oracle(rng, density, rounds):
    A, Bt = _rand_pair(rng, 16, 24, 200, density)
    a, bt = CRS.from_dense(A), CRS.from_dense(Bt)
    want = A @ Bt.T
    ref = np.asarray(ops._spmm_index_match(a, bt, rounds=rounds, bm=8,
                                           bn=8))
    two_pass = np.asarray(ops._spmm_spgemm(a, bt, rounds=rounds, bm=8,
                                           bn=8,
                                           variant="condense_merge"))
    np.testing.assert_allclose(ref, want, rtol=1e-3, atol=1e-3)
    assert (two_pass.view(np.uint32) == ref.view(np.uint32)).all()


def test_prep_rounds_overflow_drop_warns(rng):
    A = rng.standard_normal((4, 64)).astype(np.float32)  # fully dense
    a = CRS.from_dense(A)
    with pytest.raises(ValueError, match="rmax"):
        ops.prep_rounds(a, 32, rmax=4)
    with pytest.warns(UserWarning, match="dropping"):
        ai, av = ops.prep_rounds(a, 32, rmax=4, on_overflow="drop",
                                 pad_rows_to=4)
    assert ai.shape[2] == 4
    # survivors are a subset of the original matrix
    back = _unprep(ai, av, 32, 64)
    live = back != 0
    np.testing.assert_array_equal(back[live], A[:4][live])


def test_empty_row_operands(rng):
    A = np.zeros((8, 96), dtype=np.float32)
    A[3] = rng.standard_normal(96)            # single live row
    Bt = np.zeros((8, 96), dtype=np.float32)  # all-empty RHS
    Bt[0, :4] = 1.0
    a, bt = CRS.from_dense(A), CRS.from_dense(Bt)
    out = np.asarray(ops._spmm_spgemm(a, bt, rounds=32, bm=8, bn=8,
                                      variant="condense_merge"))
    np.testing.assert_allclose(out, A @ Bt.T, rtol=1e-4, atol=1e-4)
    zero = CRS.from_dense(np.zeros((8, 96), dtype=np.float32))
    out0 = np.asarray(ops._spmm_spgemm(a, zero, rounds=32, bm=8, bn=8,
                                       variant="condense_merge"))
    assert (out0 == 0).all()


# ----------------------------------------------------------------------
# Hypothesis-driven variants (skip cleanly when hypothesis is absent).
@settings(max_examples=25, deadline=None)
@given(st.integers(3, 20), st.integers(3, 20), st.integers(8, 160),
       st.sampled_from([0.0, 0.05, 0.4]), st.sampled_from([32, 128]),
       st.integers(0, 2 ** 31 - 1))
def test_prep_rounds_roundtrip_hyp(m, n, k, density, rounds, seed):
    rng = np.random.default_rng(seed)
    A, _ = _rand_pair(rng, m, n, k, density)
    a = CRS.from_dense(A)
    ai, av = ops.prep_rounds(a, rounds, pad_rows_to=8)
    back = _unprep(ai, av, rounds, k)
    np.testing.assert_array_equal(back[:m], A)


@settings(max_examples=15, deadline=None)
@given(st.integers(2, 12), st.integers(2, 12), st.integers(8, 120),
       st.sampled_from([0.0, 0.05, 0.4]), st.sampled_from([32, 128]),
       st.integers(0, 2 ** 31 - 1))
def test_spgemm_matches_dense_oracle_hyp(m, n, k, density, rounds, seed):
    rng = np.random.default_rng(seed)
    A, Bt = _rand_pair(rng, m, n, k, density)
    a, bt = CRS.from_dense(A), CRS.from_dense(Bt)
    out = np.asarray(ops._spmm_spgemm(a, bt, rounds=rounds, bm=8, bn=8,
                                      variant="condense_merge"))
    np.testing.assert_allclose(out, A @ Bt.T, rtol=1e-3, atol=1e-3)
