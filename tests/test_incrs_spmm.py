"""Fused InCRS SpMM kernel + vectorized format-prep layer.

Covers: interpret-mode equivalence of ``incrs_spmm`` against dense matmul
across densities and non-aligned shapes, empty rows/sections, the
PreparedOperand cache, and bit-identical equivalence of the vectorized
``prep_sections``/``prep_rounds``/``InCRS.from_crs`` against the seed's
per-row loop implementations (kept here as references).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.crs import CRS
from repro.core.incrs import InCRS, _pack64
from repro.kernels import ops


def _random_sparse(rng, m, n, d):
    return np.where(rng.random((m, n)) < d,
                    rng.normal(size=(m, n)), 0.0).astype(np.float32)


# ----------------------------------------------------------------------
# Seed (loop) implementations, verbatim — the vectorized paths must match
# them bit-for-bit.
def _loop_from_crs_counters(crs, section, block, prefix_bits=16,
                            count_bits=6):
    m, n = crs.shape
    n_blocks = section // block
    n_sections = -(-n // section)
    prefix = np.zeros((m, n_sections), dtype=np.int64)
    blocks = np.zeros((m, n_sections, n_blocks), dtype=np.int64)
    for i in range(m):
        s, e = crs.row_ptr[i], crs.row_ptr[i + 1]
        cols = crs.col_idx[s:e]
        sec = cols // section
        blk = (cols % section) // block
        np.add.at(blocks, (i, sec, blk), 1)
        per_sec = np.bincount(sec, minlength=n_sections)
        prefix[i] = np.concatenate([[0], np.cumsum(per_sec)[:-1]])
    lo, hi = _pack64(prefix, blocks, prefix_bits, count_bits)
    return np.stack([lo, hi], axis=-1)


def _loop_prep_sections(incrs, pad_rows_to=8):
    m, n = incrs.shape
    crs = incrs.crs
    n_sections = incrs.n_sections
    smax = 1
    spans = np.zeros((m, n_sections, 2), dtype=np.int64)
    for i in range(m):
        base = int(crs.row_ptr[i])
        for s in range(n_sections):
            prefix, blocks = incrs.counter(i, s)
            cnt = int(blocks.sum())
            spans[i, s] = (base + prefix, cnt)
            smax = max(smax, cnt)
    mp = -(-m // pad_rows_to) * pad_rows_to
    idx = np.full((n_sections, mp, smax), -1, dtype=np.int32)
    val = np.zeros((n_sections, mp, smax), dtype=np.float32)
    for i in range(m):
        for s in range(n_sections):
            start, cnt = spans[i, s]
            if cnt:
                cols = crs.col_idx[start:start + cnt]
                idx[s, i, :cnt] = cols - s * incrs.section
                val[s, i, :cnt] = crs.values[start:start + cnt]
    return idx, val


def _loop_prep_rounds(crs, rounds, rmax=None, pad_rows_to=128):
    m, n = crs.shape
    n_rounds = max(1, -(-n // rounds))
    counts = np.zeros((m, n_rounds), dtype=np.int64)
    if crs.nnz:
        row_of = np.repeat(np.arange(m), np.diff(crs.row_ptr).astype(np.int64))
        np.add.at(counts, (row_of, crs.col_idx // rounds), 1)
    rmax = int(counts.max(initial=1)) if rmax is None else rmax
    rmax = max(1, min(rmax, rounds))
    mp = -(-m // pad_rows_to) * pad_rows_to
    idx = np.full((n_rounds, mp, rmax), -1, dtype=np.int32)
    val = np.zeros((n_rounds, mp, rmax), dtype=np.float32)
    for i in range(m):
        s, e = crs.row_ptr[i], crs.row_ptr[i + 1]
        cols = crs.col_idx[s:e]
        r = cols // rounds
        slot = np.zeros_like(cols)
        for rr in np.unique(r):
            sel = r == rr
            slot[sel] = np.arange(sel.sum())
        idx[r, i, slot] = cols % rounds
        val[r, i, slot] = crs.values[s:e]
    return idx, val


# ----------------------------------------------------------------------
@pytest.mark.parametrize("density", [0.01, 0.05, 0.2, 0.5])
def test_incrs_spmm_matches_dense(rng, density):
    d = _random_sparse(rng, 96, 700, density)
    b = rng.normal(size=(700, 130)).astype(np.float32)
    inc = InCRS.from_dense(d)
    out = np.asarray(ops.spmm(inc, jnp.asarray(b)))
    np.testing.assert_allclose(out, d @ b, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("m,k,n", [(1, 300, 1), (50, 257, 96),
                                   (128, 1024, 256), (7, 31, 5)])
def test_incrs_spmm_nonaligned_shapes(rng, m, k, n):
    """Padding paths: none of these dims align to the 128/256 tiles."""
    d = _random_sparse(rng, m, k, 0.1)
    b = rng.normal(size=(k, n)).astype(np.float32)
    inc = InCRS.from_dense(d)
    out = np.asarray(ops.spmm(inc, jnp.asarray(b)))
    np.testing.assert_allclose(out, d @ b, rtol=1e-4, atol=1e-4)


def test_incrs_spmm_empty_rows_and_sections(rng):
    d = _random_sparse(rng, 40, 600, 0.08)
    d[3] = 0.0                     # empty row
    d[:, 256:512] = 0.0            # a fully-empty section (S=256)
    b = rng.normal(size=(600, 33)).astype(np.float32)
    inc = InCRS.from_dense(d)
    out = np.asarray(ops.spmm(inc, jnp.asarray(b)))
    np.testing.assert_allclose(out, d @ b, rtol=1e-4, atol=1e-4)


def test_incrs_spmm_all_zero(rng):
    d = np.zeros((16, 300), np.float32)
    b = rng.normal(size=(300, 8)).astype(np.float32)
    out = np.asarray(ops.spmm(InCRS.from_dense(d), jnp.asarray(b)))
    assert out.shape == (16, 8)
    np.testing.assert_array_equal(out, 0.0)


def test_incrs_spmm_small_section_params(rng):
    d = _random_sparse(rng, 24, 500, 0.07)
    b = rng.normal(size=(500, 64)).astype(np.float32)
    inc = InCRS.from_dense(d, section=64, block=8)
    out = np.asarray(ops.spmm(inc, jnp.asarray(b)))
    np.testing.assert_allclose(out, d @ b, rtol=1e-4, atol=1e-4)


def test_fused_matches_twopass(rng):
    """Fused single-pass == incrs_to_dense -> dense_mm to fp32 tolerance."""
    d = _random_sparse(rng, 64, 520, 0.05)
    b = jnp.asarray(rng.normal(size=(520, 96)).astype(np.float32))
    inc = InCRS.from_dense(d)
    fused = np.asarray(ops.spmm(inc, b))
    twopass = np.asarray(ops.dense_mm(ops.incrs_to_dense(inc), b))
    np.testing.assert_allclose(fused, twopass, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
def test_prep_rounds_small_rmax_raises(rng):
    d = _random_sparse(rng, 8, 64, 0.5)
    crs = CRS.from_dense(d)
    true_max = int(np.asarray(
        ops.prep_rounds(crs, 32, pad_rows_to=8)[0]).shape[2])
    assert true_max > 1
    with pytest.raises(ValueError, match="rmax"):
        ops.prep_rounds(crs, 32, rmax=true_max - 1, pad_rows_to=8)


def test_prep_rounds_small_rmax_drop_warns(rng):
    d = _random_sparse(rng, 8, 64, 0.5)
    crs = CRS.from_dense(d)
    gi_full, gv_full = ops.prep_rounds(crs, 32, pad_rows_to=8)
    rmax = gi_full.shape[2] - 1
    with pytest.warns(UserWarning, match="dropping"):
        gi, gv = ops.prep_rounds(crs, 32, rmax=rmax, pad_rows_to=8,
                                 on_overflow="drop")
    assert gi.shape[2] == rmax
    # kept slots are exactly the first rmax of the full prep
    np.testing.assert_array_equal(np.asarray(gi),
                                  np.asarray(gi_full)[:, :, :rmax])
    np.testing.assert_array_equal(np.asarray(gv),
                                  np.asarray(gv_full)[:, :, :rmax])


def test_prep_rounds_rejects_bad_on_overflow(rng):
    crs = CRS.from_dense(np.eye(4, dtype=np.float32))
    with pytest.raises(ValueError, match="on_overflow"):
        ops.prep_rounds(crs, 4, on_overflow="clamp")


# ----------------------------------------------------------------------
def test_prepared_operand_cache(rng):
    d = _random_sparse(rng, 16, 300, 0.1)
    inc = InCRS.from_dense(d)
    p1 = ops.prepare_incrs(inc)
    p2 = ops.prepare_incrs(inc)
    assert p1 is p2                               # prep ran once
    assert ops.prepare_incrs(inc, pad_rows_to=8) is not p1
    inc2 = InCRS.from_dense(d)
    assert ops.prepare_incrs(inc2) is not p1      # different live object


def test_prep_cache_evicts_lru_not_fifo(rng, monkeypatch):
    """A hot operand prepped EARLY must survive eviction; the coldest
    (least-recently-used) entry goes first."""
    monkeypatch.setattr(ops, "_PREP_CACHE_MAX", 3)
    ops._PREP_CACHE.clear()
    mats = [InCRS.from_dense(_random_sparse(rng, 8, 64, 0.2))
            for _ in range(4)]
    hot = ops.prepare_incrs(mats[0])              # oldest insertion...
    ops.prepare_incrs(mats[1])
    ops.prepare_incrs(mats[2])                    # cache full
    assert ops.prepare_incrs(mats[0]) is hot      # ...promoted on hit
    ops.prepare_incrs(mats[3])                    # evicts ONE entry
    assert ops.prepare_incrs(mats[0]) is hot      # hot entry survived
    # mats[1] (the true LRU) was the one evicted: re-prep builds anew
    keys = {k[0] for k in ops._PREP_CACHE}
    assert id(mats[1]) not in keys and id(mats[0]) in keys


# ----------------------------------------------------------------------
@pytest.mark.parametrize("seed", range(4))
def test_from_crs_counters_bit_identical_to_loop(rng, seed):
    r = np.random.default_rng(seed)
    m, n = int(r.integers(1, 40)), int(r.integers(1, 900))
    d = _random_sparse(r, m, n, float(r.uniform(0.0, 0.2)))
    crs = CRS.from_dense(d)
    inc = InCRS.from_crs(crs)
    want = _loop_from_crs_counters(crs, inc.section, inc.block)
    np.testing.assert_array_equal(inc.counters, want)


@pytest.mark.parametrize("seed", range(4))
def test_prep_sections_bit_identical_to_loop(rng, seed):
    r = np.random.default_rng(100 + seed)
    m, n = int(r.integers(1, 40)), int(r.integers(1, 900))
    d = _random_sparse(r, m, n, float(r.uniform(0.0, 0.25)))
    inc = InCRS.from_dense(d, section=64, block=8)
    gi, gv = ops.prep_sections(inc, pad_rows_to=8)
    wi, wv = _loop_prep_sections(inc, pad_rows_to=8)
    np.testing.assert_array_equal(np.asarray(gi), wi)
    np.testing.assert_array_equal(np.asarray(gv), wv)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("rounds", [32, 128])
def test_prep_rounds_bit_identical_to_loop(rng, seed, rounds):
    r = np.random.default_rng(200 + seed)
    m, n = int(r.integers(1, 50)), int(r.integers(1, 700))
    d = _random_sparse(r, m, n, float(r.uniform(0.0, 0.3)))
    crs = CRS.from_dense(d)
    gi, gv = ops.prep_rounds(crs, rounds, pad_rows_to=8)
    wi, wv = _loop_prep_rounds(crs, rounds, pad_rows_to=8)
    np.testing.assert_array_equal(np.asarray(gi), wi)
    np.testing.assert_array_equal(np.asarray(gv), wv)


def test_from_crs_rejects_oversized_block_count():
    crs = CRS.from_dense(np.eye(4, dtype=np.float32))
    with pytest.raises(ValueError):
        InCRS.from_crs(crs, section=256, block=128)   # 128 > 2^6 - 1


# ----------------------------------------------------------------------
def test_incrs_linear_matches_dense(rng):
    from repro.sparse import Linear, SparseSpec, apply
    from repro.sparse.linear import incrs_to_dense_weight
    p = Linear.init(jax.random.PRNGKey(0), 300, 64,
                    SparseSpec("incrs", density=0.05)).inner
    x = jnp.asarray(rng.normal(size=(3, 5, 300)).astype(np.float32))
    y = apply(p, x)
    w = incrs_to_dense_weight(p)
    want = np.asarray(x).reshape(-1, 300) @ w
    np.testing.assert_allclose(np.asarray(y).reshape(-1, 64), want,
                               rtol=1e-4, atol=1e-4)
    assert abs(p.density - 0.05) < 0.01


def test_spmm_engine_serves_and_reuses_prep(rng):
    from repro.serve.engine import SpMMEngine, SpMMRequest
    d = _random_sparse(rng, 48, 600, 0.05)
    inc = InCRS.from_dense(d)
    eng = SpMMEngine(inc, max_wave_cols=128)
    assert eng.prep is ops.prepare_incrs(inc)     # prep-once via the cache
    reqs = [SpMMRequest(i, rng.normal(size=(600, 48 + i)).astype(np.float32))
            for i in range(5)]
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    assert len(done) == 5 and all(r.done for r in done)
    assert eng.stats["waves"] >= 2                # 250 cols over 128-col waves
    for r in done:
        np.testing.assert_allclose(r.out, d @ r.b, rtol=1e-4, atol=1e-4)


def test_incrs_linear_shard_preserves_zero_valued_slots(rng):
    """Resharding a trained layer must keep a live slot whose value landed
    on exactly 0.0 — the pattern rides along as an explicit mask, not
    re-derived from non-zeros."""
    from jax.sharding import Mesh
    from repro.sparse import Linear, SparseSpec
    from repro.sparse.linear import (incrs_to_dense_weight,
                                     incrs_sharded_to_dense_weight)
    p = Linear.init(jax.random.PRNGKey(0), 40, 64,
                    SparseSpec("incrs", density=0.2, section=32,
                               block=8)).inner
    live = np.asarray(p.meta.fwd_idx) >= 0
    r, s, k = np.nonzero(live)
    vals = np.asarray(p.values).copy()
    vals[r[0], s[0], k[0]] = 0.0                  # a trained-to-zero weight
    import dataclasses
    p = dataclasses.replace(p, values=jnp.asarray(vals))
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    ps = Linear(p).shard(mesh=mesh).inner
    assert ps.nnz == p.nnz                        # slot still in the pattern
    np.testing.assert_array_equal(incrs_to_dense_weight(p),
                                  incrs_sharded_to_dense_weight(ps))


def test_spmm_engine_submit_rejects_bad_shapes(rng):
    """Shape validation must be a real error (asserts vanish under -O)."""
    from repro.serve.engine import SpMMEngine, SpMMRequest
    inc = InCRS.from_dense(_random_sparse(rng, 16, 300, 0.1))
    eng = SpMMEngine(inc)
    with pytest.raises(ValueError, match="expected"):
        eng.submit(SpMMRequest(0, rng.normal(size=(299, 4))
                               .astype(np.float32)))
    with pytest.raises(ValueError, match="expected"):
        eng.submit(SpMMRequest(1, rng.normal(size=300).astype(np.float32)))
    assert not eng.queue


def test_spmm_engine_preserves_request_dtypes(rng):
    """A wave computes at the PROMOTED dtype (up to the kernel's f32
    accumulation ceiling) and each request's panel comes back in its own
    dtype — no silent f32 blanket relabeling. A wider-than-f32 wave warns
    that compute stays f32."""
    import warnings as _w
    from repro.serve.engine import SpMMEngine, SpMMRequest
    d = _random_sparse(rng, 32, 300, 0.1)
    inc = InCRS.from_dense(d)
    eng = SpMMEngine(inc, max_wave_cols=64)
    bf16 = np.asarray(jnp.asarray(
        rng.normal(size=(300, 8)).astype(np.float32), jnp.bfloat16))
    f32 = rng.normal(size=(300, 8)).astype(np.float32)
    for i, b in enumerate((bf16, f32)):
        eng.submit(SpMMRequest(i, b))
    with _w.catch_warnings():
        _w.simplefilter("error")                      # f32-wave: no warning
        done = {r.rid: r for r in eng.run()}
    assert done[0].out.dtype == bf16.dtype            # bf16 in, bf16 out
    assert done[1].out.dtype == np.float32
    f64 = rng.normal(size=(300, 8)).astype(np.float64)
    eng.submit(SpMMRequest(2, f64))
    with pytest.warns(UserWarning, match="f32 precision"):
        done[2] = eng.run()[-1]
    assert done[2].out.dtype == np.float64            # dtype kept, f32 math
    for i, b in enumerate((bf16, f32, f64)):
        np.testing.assert_allclose(
            done[i].out.astype(np.float32),
            d @ b.astype(np.float32), rtol=1e-2, atol=1e-2)


def test_invalidate_prepared_after_mutation(rng):
    d = _random_sparse(rng, 16, 300, 0.1)
    inc = InCRS.from_dense(d)
    b = jnp.asarray(rng.normal(size=(300, 8)).astype(np.float32))
    y1 = np.asarray(ops.spmm(inc, b))
    inc.crs.values = inc.crs.values * 2.0     # in-place operand mutation
    ops.invalidate_prepared(inc)
    y2 = np.asarray(ops.spmm(inc, b))
    np.testing.assert_allclose(y2, 2.0 * y1, rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("n", [520, 640, 1032])
def test_incrs_spmm_bn_autoselect_odd_widths(rng, n):
    d = _random_sparse(rng, 32, 400, 0.08)
    b = rng.normal(size=(400, n)).astype(np.float32)
    out = np.asarray(ops.spmm(InCRS.from_dense(d), jnp.asarray(b)))
    np.testing.assert_allclose(out, d @ b, rtol=1e-4, atol=1e-4)
