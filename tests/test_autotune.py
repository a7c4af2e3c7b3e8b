"""Autotuner + roofline-push kernel tests: tuned-vs-default bitwise
equivalence, tuning-cache round-trip and versioned invalidation, row-tile
resolution (no gcd collapse), plan tune modes, sharded per-shard clamps,
and the machine-relative bench regression gate."""
import dataclasses
import importlib.util
import json
import logging
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.incrs import InCRS
from repro.core.mesh_sim import fused_spmm_cost
from repro.kernels import autotune, ops
from repro.kernels.incrs_spmm import (_resolve_row_tile, incrs_spmm,
                                      incrs_spmm_pipelined, incrs_spmm_reuse)
from repro.sparse import SparseSpec
from repro.sparse.api import plan
from repro.serve.engine import SpMMEngine, SpMMRequest


def _sparse_dense(rng, m, k, density):
    a = rng.normal(size=(m, k)).astype(np.float32)
    if density <= 0.0:
        return np.zeros((m, k), np.float32)
    mask = rng.random((m, k)) < density
    return np.where(mask, a, 0.0).astype(np.float32)


def _own_cache(monkeypatch, tmp_path):
    """Point the tuning cache at a test-private file (the session-wide
    conftest file would let earlier tests' entries leak in)."""
    path = tmp_path / "autotune.json"
    monkeypatch.setenv(autotune.CACHE_ENV, str(path))
    autotune.clear_memory_cache()
    return path


# ----------------------------------------------------------------------
# Row-tile resolution (satellite: gcd collapse removed).
def test_resolve_row_tile():
    assert _resolve_row_tile(127, 128) == (128, 128)   # pad, don't shrink
    assert _resolve_row_tile(32, 128) == (32, 32)      # clamp to panel
    assert _resolve_row_tile(4, 128) == (8, 8)         # sublane floor
    assert _resolve_row_tile(1000, 128) == (128, 1024)
    # The old gcd rule degraded odd panels to bm=1; now they pad.
    bm, mp = _resolve_row_tile(17, 128)
    assert bm == 24 and mp == 24


@pytest.mark.parametrize("variant", ["expand", "reuse", "pipelined"])
def test_odd_row_panel_pads_instead_of_collapsing(rng, variant):
    """17 rows (odd, non-sublane) must run at a real tile size and still
    produce exact results — the pad rows expand to zeros and are trimmed."""
    a = _sparse_dense(rng, 17, 64, 0.3)
    b = rng.normal(size=(64, 32)).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc, pad_rows_to=1)
    out = ops.spmm(prep, b, variant=variant, bm=128)
    np.testing.assert_allclose(np.asarray(out), a @ b, rtol=1e-5,
                               atol=1e-5)


def test_kernel_rejects_bad_tiles_and_ops_rejects_bad_k(rng):
    a = _sparse_dense(rng, 16, 64, 0.3)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc, pad_rows_to=8)
    # bn must divide the (padded) RHS width at the kernel layer — a hard
    # ValueError, not an assert, so it survives ``python -O``.
    b_bad = jnp.zeros((64, 100), jnp.float32)
    with pytest.raises(ValueError):
        incrs_spmm(prep.idx, prep.val, b_bad, section=32, bm=8, bn=64,
                   interpret=True)
    # K mismatch at the dispatcher layer.
    with pytest.raises(ValueError):
        ops.spmm(prep, jnp.zeros((63, 8), jnp.float32))


# ----------------------------------------------------------------------
# Tentpole: variant/tile choice never changes the numbers.
@pytest.mark.parametrize("density", [0.0, 0.03, 0.5])
def test_variants_bitwise_identical(rng, density):
    a = _sparse_dense(rng, 64, 128, density)
    b = rng.normal(size=(128, 96)).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc, pad_rows_to=8)
    ref = np.asarray(ops.spmm(prep, b, variant="expand"))
    for variant in ("reuse", "pipelined"):
        out = np.asarray(ops.spmm(prep, b, variant=variant))
        assert (out == ref).all(), f"{variant} diverged at d={density}"
    np.testing.assert_allclose(ref, a @ b, rtol=1e-4, atol=1e-4)


def test_tile_sizes_bitwise_identical(rng):
    """Autotuned (bm, bn) picks are safe: every tiling is bitwise equal,
    because each output row's section-axis reduction order is fixed."""
    a = _sparse_dense(rng, 48, 128, 0.1)
    b = rng.normal(size=(128, 96)).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc, pad_rows_to=8)
    ref = np.asarray(ops.spmm(prep, b, variant="reuse"))
    for variant in ("expand", "reuse", "pipelined"):
        for bm, bn in ((32, 32), (128, 96), (8, 48)):
            out = np.asarray(ops.spmm(prep, b, variant=variant, bm=bm,
                                      bn=bn))
            assert (out == ref).all(), (variant, bm, bn)


# ----------------------------------------------------------------------
# Tuning cache: round-trip, versioned invalidation, corruption tolerance.
def test_cache_roundtrip_and_invalidation(rng, monkeypatch, tmp_path):
    path = _own_cache(monkeypatch, tmp_path)
    a = _sparse_dense(rng, 16, 64, 0.2)
    b = rng.normal(size=(64, 48)).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc, pad_rows_to=8)
    cfg = autotune.tune(prep.idx, prep.val, b, section=prep.section,
                        interpret=True, reps=1, top_k=1)
    assert cfg.variant in ("expand", "reuse", "pipelined")
    assert cfg.measured_us > 0 and cfg.predicted_us > 0
    assert cfg.overhead_factor == cfg.measured_us / cfg.predicted_us

    key = autotune.cache_key(prep.padded_rows, prep.n_sections,
                             prep.idx.shape[2], prep.section, b.shape[1],
                             autotune.backend_name(True))
    # Round-trip through disk: forget process state, re-load from file.
    autotune.clear_memory_cache()
    assert autotune.lookup(key) == cfg
    # Second tune() is a pure cache hit — identical config, no sweep.
    again = autotune.tune(prep.idx, prep.val, b, section=prep.section,
                          interpret=True, reps=1)
    assert again == cfg

    # Versioned invalidation: a bumped AUTOTUNE_VERSION drops every entry.
    blob = json.loads(path.read_text())
    assert blob["version"] == autotune.AUTOTUNE_VERSION
    blob["version"] = autotune.AUTOTUNE_VERSION + 1
    path.write_text(json.dumps(blob))
    autotune.clear_memory_cache()
    assert autotune.lookup(key) is None

    # Corrupt cache file is tolerated (treated as empty), not fatal.
    path.write_text("{not json")
    autotune.clear_memory_cache()
    assert autotune.lookup(key) is None


def test_spmm_auto_rides_tuned_entry(rng, monkeypatch, tmp_path):
    """variant="auto" adopts a tuned config when one is cached (no cost
    model call), and falls back to the model exactly once otherwise."""
    _own_cache(monkeypatch, tmp_path)
    a = _sparse_dense(rng, 16, 64, 0.2)
    b = rng.normal(size=(64, 48)).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    prep = ops.prepare_incrs(inc, pad_rows_to=8)

    calls = []
    real_pick = autotune.model_pick_variant

    def counting_pick(*args, **kw):
        calls.append(args)
        return real_pick(*args, **kw)

    monkeypatch.setattr(autotune, "model_pick_variant", counting_pick)
    out_model = np.asarray(ops.spmm(prep, b, variant="auto"))
    assert len(calls) == 1             # no tuned entry -> model fallback

    autotune.tune(prep.idx, prep.val, b, section=prep.section,
                  interpret=True, reps=1, top_k=1)
    out_tuned = np.asarray(ops.spmm(prep, b, variant="auto"))
    assert len(calls) == 1             # tuned entry -> model never re-ran
    assert (out_tuned == out_model).all()


def test_model_pick_one_time_log(caplog):
    with caplog.at_level(logging.INFO, logger="repro.kernels.autotune"):
        kw = dict(n_sections=4, smax=32, section=256, bm=128, bn=128,
                  interpret=True)
        autotune.model_pick_variant(128, 1024, **kw)
        n_logged = len(caplog.records)
        assert n_logged >= 1
        autotune.model_pick_variant(128, 1024, **kw)   # same shape: silent
        assert len(caplog.records) == n_logged


# ----------------------------------------------------------------------
# Cost model: the prior prefers what the measurements confirmed.
def test_cost_model_prefers_pipelined_for_wide_rhs():
    kw = dict(n_sections=4, smax=32, section=256, bm=128, bn=128,
              interpret=True)
    assert autotune.model_pick_variant(128, 1024, **kw) == "pipelined"
    # A panel too big for VMEM leaves only the expand order.
    assert autotune.model_pick_variant(
        128, 8192, n_sections=4, smax=32, section=256, bm=128, bn=512,
        interpret=True) == "expand"


def test_fused_spmm_cost_shapes():
    kw = dict(n_sections=4, smax=32, section=256, bm=128, bn=128)
    exp = fused_spmm_cost("expand", 128, 1024, **kw)
    reu = fused_spmm_cost("reuse", 128, 1024, **kw)
    pip = fused_spmm_cost("pipelined", 128, 1024, **kw)
    assert pip.grid_steps == 1                      # one step per row tile
    assert pip.grid_steps < reu.grid_steps <= exp.grid_steps
    assert reu.expansions == pip.expansions == 4    # once per section
    assert exp.expansions == 32                     # once per (section, bn)
    assert exp.flops == reu.flops == pip.flops
    for c in (exp, reu, pip):
        assert c.cycles > 0 and c.hbm_bytes > 0


def test_candidates_respect_vmem_budgets():
    cands = autotune.candidates(128, 1024, section=256, n_sections=4)
    variants = {(v, bm, bn) for v, bm, bn in cands}
    assert ("pipelined", 128, 128) in variants
    # 128-row panel at 8192 padded cols busts PANEL_BYTES -> expand only.
    wide = autotune.candidates(128, 8192, section=256, n_sections=4)
    assert all(v == "expand" for v, bm, bn in wide if bm == 128
               and bn >= 512)


# ----------------------------------------------------------------------
# Plan persistence: plan(tune=...) modes and MatmulPlan.tune.
def test_plan_tune_modes(rng, monkeypatch, tmp_path):
    _own_cache(monkeypatch, tmp_path)
    w = _sparse_dense(rng, 64, 32, 0.3)            # W (d_in, d_out)
    spec = SparseSpec("incrs", mask=w != 0, section=32, block=8)
    b = rng.normal(size=(64, 48)).astype(np.float32)

    with pytest.raises(ValueError):
        plan(spec, rhs_shape=(64, 48), tune="bogus")

    p_off = plan(spec, rhs_shape=(64, 48), tune="off")
    assert p_off.tuned is None
    p_cold = plan(spec, rhs_shape=(64, 48))        # cache mode, no entry
    assert p_cold.tuned is None

    p_meas = plan(spec, rhs_shape=(64, 48), tune="measure")
    assert isinstance(p_meas.tuned, autotune.TunedConfig)
    # The next cache-mode plan rides the persisted entry for free.
    autotune.clear_memory_cache()
    p_warm = plan(spec, rhs_shape=(64, 48))
    assert p_warm.tuned == p_meas.tuned

    vals = p_meas.pack(w)
    ref = np.asarray(p_off(p_off.pack(w), b))
    out = np.asarray(p_meas(vals, b))
    assert (out == ref).all()                      # tuned config, same bits
    # Explicit variant= at call time overrides the tuned config.
    forced = np.asarray(p_meas(vals, b, variant="expand"))
    assert (forced == ref).all()
    np.testing.assert_allclose(ref, w.T @ b, rtol=1e-4, atol=1e-4)


def test_plan_tune_rejects_untunable_format():
    with pytest.raises(ValueError):
        plan(SparseSpec("dense")).tune(8)


# ----------------------------------------------------------------------
# Sharded path: tiles clamp to the per-shard panel, not the global M.
def test_sharded_plan_clamps_tiles_per_shard(rng):
    a = _sparse_dense(rng, 17, 64, 0.3)
    b = rng.normal(size=(64, 32)).astype(np.float32)
    inc = InCRS.from_dense(a, section=32)
    mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]), ("x",))
    prep = ops.prepare_incrs_sharded(inc, mesh, pad_rows_to=8)
    # bm=128 far exceeds the 24-row shard panel; the kernel must clamp
    # per shard instead of erroring or collapsing to bm=1.
    out = ops.spmm(prep, b, bm=128, variant="reuse")
    np.testing.assert_allclose(np.asarray(out)[:17], a @ b, rtol=1e-5,
                               atol=1e-5)


# ----------------------------------------------------------------------
# Serving: the engine accepts the new variant end to end.
def test_engine_serves_pipelined_variant(rng):
    a = _sparse_dense(rng, 32, 64, 0.2)
    inc = InCRS.from_dense(a, section=32)
    with pytest.raises(ValueError):
        SpMMEngine(inc, variant="bogus")
    eng = SpMMEngine(inc, variant="pipelined")
    req = SpMMRequest(0, rng.normal(size=(64, 16)).astype(np.float32))
    eng.submit(req)
    eng.run()
    assert req.done
    np.testing.assert_allclose(req.out, a @ req.b, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# Bench regression gate (scripts/ci.sh --check): machine-relative.
def _load_kernel_bench():
    path = os.path.join(os.path.dirname(__file__), os.pardir,
                        "benchmarks", "kernel_bench.py")
    spec = importlib.util.spec_from_file_location("_kernel_bench", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_check_regressions_is_machine_relative(tmp_path):
    kb = _load_kernel_bench()
    baseline = tmp_path / "base.json"
    baseline.write_text(json.dumps({"rows": [
        {"name": "dense_mm_256", "us": 1000.0},
        {"name": "incrs_spmm_pipelined", "us": 5000.0},
        {"name": "tiny_row", "us": 50.0},
    ]}))
    # Everything 2x slower — a slower machine, not a regression.
    rows = [("dense_mm_256", 2000.0, ""),
            ("incrs_spmm_pipelined", 10000.0, ""),
            ("tiny_row", 100.0, "")]
    assert kb.check_regressions(rows, str(baseline)) == []
    # One kernel 2x slower machine-relative -> exactly that one fails.
    rows = [("dense_mm_256", 1000.0, ""),
            ("incrs_spmm_pipelined", 10000.0, ""),
            ("tiny_row", 500.0, "")]       # below baseline floor: skipped
    failures = kb.check_regressions(rows, str(baseline))
    assert len(failures) == 1 and "incrs_spmm_pipelined" in failures[0]
    # Missing norm row or unreadable baseline -> explicit failure string.
    assert kb.check_regressions([("x", 1.0, "")], str(baseline))
    assert kb.check_regressions(rows, str(tmp_path / "missing.json"))
