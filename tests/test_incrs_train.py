"""Trainable fused-InCRS path: custom VJP vs dense oracle, stripe-reuse
kernel equivalence, optimizer/pipeline integration."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.incrs import InCRS
from repro.kernels import ops
from repro.kernels.incrs_spmm import incrs_spmm as _expand_kernel
from repro.kernels.incrs_spmm import incrs_spmm_reuse as _reuse_kernel
from repro.sparse import Linear, SparseSpec, apply as sp_apply, stack_init
from repro.sparse.linear import InCRSLinearParams, incrs_to_dense_weight


def _incrs_init(key, d_in, d_out, density, scale=0.02, **kw):
    return Linear.init(key, d_in, d_out,
                       SparseSpec("incrs", density=density, **kw),
                       scale=scale).inner


def _random_sparse(rng, m, n, d):
    return np.where(rng.random((m, n)) < d,
                    rng.normal(size=(m, n)), 0.0).astype(np.float32)


# ----------------------------------------------------------------------
# Stripe-reuse kernel: bit-for-bit role-equivalent to the re-expanding
# baseline (same math, different grid order / accumulation locality).
@pytest.mark.parametrize("m,k,n,density", [
    (96, 700, 130, 0.05), (128, 1024, 512, 0.03),
    (7, 31, 5, 0.2), (40, 600, 257, 0.08),
])
def test_reuse_kernel_matches_expand(rng, m, k, n, density):
    d = _random_sparse(rng, m, k, density)
    b = rng.normal(size=(k, n)).astype(np.float32)
    inc = InCRS.from_dense(d)
    exp = np.asarray(ops.spmm(inc, jnp.asarray(b), variant="expand"))
    reu = np.asarray(ops.spmm(inc, jnp.asarray(b), variant="reuse"))
    np.testing.assert_allclose(reu, d @ b, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(reu, exp, rtol=1e-5, atol=1e-5)


def test_reuse_kernel_raw_multi_row_tiles(rng):
    """>1 row tile AND >1 col tile AND >1 section: every grid axis live."""
    d = _random_sparse(rng, 300, 600, 0.05)
    b = rng.normal(size=(600, 300)).astype(np.float32)
    inc = InCRS.from_dense(d)
    prep = ops.prepare_incrs(inc)
    kp = prep.n_sections * prep.section
    bp = jnp.asarray(np.pad(b, ((0, kp - 600), (0, 84))))
    out = _reuse_kernel(prep.idx, prep.val, bp, section=prep.section,
                        bm=128, bn=128, interpret=True)
    want = _expand_kernel(prep.idx, prep.val, bp, section=prep.section,
                          bm=128, bn=128, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(out)[:300, :300], d @ b,
                               rtol=1e-4, atol=1e-4)


def test_variant_auto_dispatch(rng):
    """auto -> reuse for wide outputs (>= 4 col tiles), expand for narrow;
    both dispatches must agree with the dense product."""
    d = _random_sparse(rng, 64, 520, 0.05)
    inc = InCRS.from_dense(d)
    for n in (64, 2048):        # 1 tile -> expand; 4x512 tiles -> reuse
        b = rng.normal(size=(520, n)).astype(np.float32)
        out = np.asarray(ops.spmm(inc, jnp.asarray(b)))
        np.testing.assert_allclose(out, d @ b, rtol=1e-4, atol=1e-4)


# ----------------------------------------------------------------------
# Custom VJP vs the dense oracle.
@pytest.mark.parametrize("density", [0.0, 0.01, 0.1, 0.5, 1.0])
def test_incrs_grad_matches_dense_oracle(rng, density):
    d_in, d_out, t = 300, 64, 9
    if density == 0.0:
        p = Linear.from_dense(np.zeros((d_in, d_out), np.float32),
                              SparseSpec("incrs")).inner
    else:
        p = _incrs_init(jax.random.PRNGKey(0), d_in, d_out,
                        density=density)
    x = jnp.asarray(rng.normal(size=(t, d_in)).astype(np.float32))
    w = jnp.asarray(incrs_to_dense_weight(p))

    def f(vals, x_):
        return (sp_apply(
            dataclasses.replace(p, values=vals), x_) ** 2).sum()

    y = sp_apply(p, x)
    np.testing.assert_allclose(np.asarray(y), np.asarray(x @ w),
                               rtol=1e-4, atol=1e-4)
    gv, gx = jax.grad(f, argnums=(0, 1))(p.values, x)
    gw, gx_ref = jax.grad(lambda w_, x_: ((x_ @ w_) ** 2).sum(),
                          argnums=(0, 1))(w, x)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(gx_ref),
                               rtol=1e-4, atol=1e-4)
    # value grads, compared on the live support after densify
    gdense = incrs_to_dense_weight(dataclasses.replace(p, values=gv))
    live = np.asarray(incrs_to_dense_weight(p)) != 0
    np.testing.assert_allclose(gdense[live], np.asarray(gw)[live],
                               rtol=1e-4, atol=1e-4)
    # pad slots (idx == -1) must carry exactly zero gradient
    pad = np.asarray(p.meta.fwd_idx) < 0
    assert np.all(np.asarray(gv)[pad] == 0.0)


def test_incrs_grad_through_jit_and_3d_batch(rng):
    p = _incrs_init(jax.random.PRNGKey(1), 130, 70, density=0.1)
    x = jnp.asarray(rng.normal(size=(2, 5, 130)).astype(np.float32))

    @jax.jit
    def f(params, x_):
        return (sp_apply(params, x_) ** 2).sum()

    g = jax.grad(f)(p, x)
    assert isinstance(g, InCRSLinearParams)
    assert g.values.shape == p.values.shape
    w = jnp.asarray(incrs_to_dense_weight(p))
    gw = jax.grad(lambda w_: ((x.reshape(-1, 130) @ w_) ** 2).sum())(w)
    gdense = incrs_to_dense_weight(dataclasses.replace(p, values=g.values))
    live = np.asarray(incrs_to_dense_weight(p)) != 0
    np.testing.assert_allclose(gdense[live], np.asarray(gw)[live],
                               rtol=1e-4, atol=1e-4)


def test_incrs_training_converges(rng):
    """Gradient descent on the fused path reaches toward the best loss
    achievable under the fixed sparsity pattern."""
    d_in = d_out = 64
    p = _incrs_init(jax.random.PRNGKey(2), d_in, d_out, density=0.3,
                    scale=0.3, section=64, block=8)
    w_true = rng.normal(size=(d_in, d_out)).astype(np.float32) * 0.3
    x = jnp.asarray(rng.normal(size=(128, d_in)).astype(np.float32))
    y = x @ jnp.asarray(w_true)

    def loss(vals):
        pred = sp_apply(dataclasses.replace(p, values=vals), x)
        return jnp.mean((pred - y) ** 2)

    # achievable floor: the target restricted to the live pattern
    live = np.asarray(incrs_to_dense_weight(p)) != 0
    idx = np.asarray(p.meta.fwd_idx)
    opt_vals = np.zeros_like(np.asarray(p.values))
    s, r, k = np.nonzero(idx >= 0)
    wt_true = w_true.T
    opt_vals[s, r, k] = wt_true[r, idx[s, r, k] + s * p.meta.section]
    floor = float(loss(jnp.asarray(opt_vals)))

    vals = p.values
    l0 = float(loss(vals))
    g = jax.jit(jax.grad(loss))
    for _ in range(200):
        vals = vals - 0.5 * g(vals)
    final = float(loss(vals))
    assert final < l0
    assert final < floor + 0.5 * (l0 - floor)


def test_incrs_adamw_roundtrip(rng):
    """InCRSLinearParams is a plain pytree to the optimizer: moments mirror
    the values leaf, meta survives the update untouched."""
    from repro.train.optimizer import AdamWConfig, adamw_init, adamw_update
    p = {"l": _incrs_init(jax.random.PRNGKey(3), 96, 48, density=0.2,
                          section=64, block=8)}
    x = jnp.asarray(rng.normal(size=(8, 96)).astype(np.float32))
    opt = AdamWConfig(lr=1e-2, weight_decay=0.0, warmup_steps=0,
                      total_steps=10)
    state = adamw_init(opt, p)
    loss0 = float((sp_apply(p["l"], x) ** 2).sum())
    g = jax.grad(lambda q: (sp_apply(q["l"], x) ** 2).sum())(p)
    p2, state, _ = adamw_update(opt, g, state, p)
    assert p2["l"].meta is p["l"].meta
    loss1 = float((sp_apply(p2["l"], x) ** 2).sum())
    assert loss1 < loss0
    # pad slots stay exactly zero through the update
    pad = np.asarray(p["l"].meta.fwd_idx) < 0
    assert np.all(np.asarray(p2["l"].values)[pad] == 0.0)


def test_incrs_stack_init_shared_pattern(rng):
    ps = stack_init(jax.random.PRNGKey(4), 3, 64, 64,
                    SparseSpec("incrs", density=0.2, section=64,
                               block=8)).inner
    assert ps.values.shape[0] == 3
    live = np.asarray(ps.meta.fwd_idx) >= 0
    vals = np.asarray(ps.values)
    for i in range(3):
        assert np.all(vals[i][~live] == 0.0)
    # stages hold different values on the SAME pattern
    assert not np.allclose(vals[0], vals[1])


def test_trained_values_flow_into_serving(rng):
    """params.prep exposes the CURRENT values to SpMMEngine."""
    from repro.serve.engine import SpMMEngine, SpMMRequest
    p = _incrs_init(jax.random.PRNGKey(5), 200, 64, density=0.1)
    p = dataclasses.replace(p, values=p.values * 3.0)    # "trained"
    eng = SpMMEngine(p.prep)
    req = SpMMRequest(0, rng.normal(size=(200, 16)).astype(np.float32))
    eng.submit(req)
    eng.run()
    w = incrs_to_dense_weight(p)
    np.testing.assert_allclose(req.out, w.T @ req.b, rtol=1e-4, atol=1e-4)
