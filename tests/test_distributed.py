"""Multi-device substrate tests on fake CPU devices (subprocesses, so the
main test process keeps its single-device view)."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(code: str, n_devices: int = 8, timeout: int = 560) -> str:
    env = dict(os.environ,
               XLA_FLAGS=f"--xla_force_host_platform_device_count={n_devices}",
               PYTHONPATH=os.path.join(_ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                         capture_output=True, text=True, timeout=timeout,
                         env=env)
    assert out.returncode == 0, out.stderr[-4000:]
    return out.stdout


def test_pipeline_forward_backward():
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.train.pipeline import pipeline_apply
        from jax.sharding import Mesh
        mesh = Mesh(np.array(jax.devices()[:4]), ("pipe",))
        ws = jax.random.normal(jax.random.PRNGKey(0), (4, 16, 16)) * 0.3
        stage = lambda w, h: jnp.tanh(h @ w["w"])
        x = jax.random.normal(jax.random.PRNGKey(1), (6, 8, 16))
        out = pipeline_apply(stage, {"w": ws}, x, n_stages=4, n_micro=6,
                             mesh=mesh)
        ref = x
        for i in range(4): ref = jnp.tanh(ref @ ws[i])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        g1 = jax.grad(lambda w: (pipeline_apply(stage, {"w": w}, x,
                      n_stages=4, n_micro=6, mesh=mesh) ** 2).sum())(ws)
        def ref_loss(w):
            r = x
            for i in range(4): r = jnp.tanh(r @ w[i])
            return (r ** 2).sum()
        g2 = jax.grad(ref_loss)(ws)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=1e-4, atol=1e-4)
        print("PIPELINE_OK")
    """))


def test_pipeline_incrs_stages_forward_backward():
    """Shared-pattern InCRS stages through the pipeline: the fused-SpMM
    custom VJP must transpose cleanly through shard_map/scan/ppermute."""
    print(_run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.train.pipeline import pipeline_apply, incrs_stage_fn
        from repro.sparse import SparseSpec, stack_init
        from repro.sparse.linear import incrs_to_dense_weight
        mesh = Mesh(np.array(jax.devices()[:2]), ("pipe",))
        ps = stack_init(jax.random.PRNGKey(0), 2, 64, 64,
                        SparseSpec("incrs", density=0.2,
                                   section=64, block=8), scale=0.3).inner
        stage = incrs_stage_fn()
        x = jax.random.normal(jax.random.PRNGKey(1), (4, 8, 64))
        out = pipeline_apply(stage, ps, x, n_stages=2, n_micro=4, mesh=mesh)
        ws = [jnp.asarray(incrs_to_dense_weight(
                  dataclasses.replace(ps, values=ps.values[i])))
              for i in range(2)]
        ref = x
        for w in ws: ref = jnp.tanh(ref @ w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)
        g = jax.grad(lambda p: (pipeline_apply(stage, p, x, n_stages=2,
                     n_micro=4, mesh=mesh) ** 2).sum())(ps)
        gws = jax.grad(lambda wl: ((lambda r: (r ** 2).sum())(
            jnp.tanh(jnp.tanh(x @ wl[0]) @ wl[1]))))(ws)
        for i in range(2):
            gd = incrs_to_dense_weight(
                dataclasses.replace(ps, values=g.values[i]))
            live = np.abs(np.asarray(ws[i])) > 0
            np.testing.assert_allclose(gd[live], np.asarray(gws[i])[live],
                                       rtol=1e-3, atol=1e-3)
        print("PIPELINE_INCRS_OK")
    """, n_devices=2))


def test_sharded_incrs_linear_matches_single_device():
    """Row-sharded InCRSLinear on an 8-way mesh vs the single-device fused
    path at densities {0, 0.03, 0.5}: forward and dW are BITWISE equal
    (identical per-row arithmetic, dW is shard-local); dx is bitwise here
    too because shard_width == section (each shard's partial IS one section
    contribution, so the cross-device sum reassociates nothing)."""
    print(_run("""
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.sparse import Linear, SparseSpec
        from repro.sparse import apply as sp_apply
        from repro.sparse.linear import (incrs_to_dense_weight,
                                         incrs_sharded_to_dense_weight)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        spec1 = SparseSpec("incrs", section=64, block=8)
        spec8 = SparseSpec("incrs", section=64, block=8, mesh=mesh)
        rng = np.random.default_rng(0)
        for d in (0.0, 0.03, 0.5):
            w = np.where(rng.random((96, 512)) < d,
                         rng.normal(size=(96, 512)), 0.0).astype(np.float32)
            p1 = Linear.from_dense(w, spec1).inner
            ps = Linear.from_dense(w, spec8).inner
            assert ps.values.sharding.num_devices == 8
            np.testing.assert_array_equal(
                incrs_to_dense_weight(p1), incrs_sharded_to_dense_weight(ps))
            x = jnp.asarray(rng.normal(size=(16, 96)).astype(np.float32))
            np.testing.assert_array_equal(
                np.asarray(sp_apply(p1, x)),
                np.asarray(sp_apply(ps, x)))
            l1 = lambda v, xx: (sp_apply(
                dataclasses.replace(p1, values=v), xx) ** 2).sum()
            ls = lambda v, xx: (sp_apply(
                dataclasses.replace(ps, values=v), xx) ** 2).sum()
            g1v, g1x = jax.grad(l1, argnums=(0, 1))(p1.values, x)
            gsv, gsx = jax.grad(ls, argnums=(0, 1))(ps.values, x)
            np.testing.assert_array_equal(
                incrs_to_dense_weight(dataclasses.replace(p1, values=g1v)),
                incrs_sharded_to_dense_weight(
                    dataclasses.replace(ps, values=gsv)))
            np.testing.assert_array_equal(np.asarray(g1x), np.asarray(gsx))
        # Non-section-aligned shards (2 sections per shard): dx partials
        # cross section groups, so only reassociation-level differences are
        # allowed — still exact to ~1e-5 relative.
        w = np.where(rng.random((100, 1024)) < 0.1,
                     rng.normal(size=(100, 1024)), 0.0).astype(np.float32)
        p1 = Linear.from_dense(w, spec1).inner
        ps = Linear.from_dense(w, spec8).inner
        x = jnp.asarray(rng.normal(size=(8, 100)).astype(np.float32))
        np.testing.assert_array_equal(
            np.asarray(sp_apply(p1, x)),
            np.asarray(sp_apply(ps, x)))
        g1 = jax.grad(lambda xx: (sp_apply(p1, xx) ** 2).sum())(x)
        gs = jax.grad(lambda xx: (sp_apply(ps, xx)
                                  ** 2).sum())(x)
        np.testing.assert_allclose(np.asarray(g1), np.asarray(gs),
                                   rtol=1e-5, atol=1e-6)
        print("SHARDED_INCRS_LINEAR_OK")
    """))


def test_spmm_engine_sharded_wave_roundtrip():
    """Multi-device SpMMEngine: waves against a row-sharded PreparedOperand
    — per-device stripe panels (no device holds A whole), dense RHS
    broadcast per wave, per-shard output panels concatenated. Results must
    match the single-device fused path bitwise."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.core.incrs import InCRS
        from repro.kernels import ops
        from repro.serve.engine import SpMMEngine, SpMMRequest
        rng = np.random.default_rng(0)
        d = np.where(rng.random((96, 600)) < 0.05,
                     rng.normal(size=(96, 600)), 0.0).astype(np.float32)
        inc = InCRS.from_dense(d)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        eng = SpMMEngine(inc, mesh=mesh, max_wave_cols=128)
        assert eng.sharded
        # Every device holds exactly its own shard of the stripes — the
        # sparse operand is never gathered onto one device.
        shards = eng.prep.idx.addressable_shards
        assert len({s.device for s in shards}) == 8
        assert all(s.data.shape[0] == 1 for s in shards)
        reqs = [SpMMRequest(i, rng.normal(size=(600, 48 + i))
                            .astype(np.float32)) for i in range(5)]
        for r in reqs:
            eng.submit(r)
        done = eng.run()
        assert len(done) == 5 and all(r.done for r in done)
        assert eng.stats["waves"] >= 2
        single = ops.prepare_incrs(inc)
        for r in done:
            np.testing.assert_allclose(r.out, d @ r.b, rtol=1e-4, atol=1e-4)
            np.testing.assert_array_equal(
                r.out, np.asarray(ops.spmm(single, jnp.asarray(r.b))))
        # Trained sharded layer -> engine, zero repacking: the values leaf
        # IS the serving operand.
        from repro.sparse import Linear, SparseSpec
        p = Linear.init(jax.random.PRNGKey(1), 600, 96,
                        SparseSpec("incrs", density=0.05, mesh=mesh,
                                   section=64, block=8)).inner
        eng2 = SpMMEngine(p.prep)
        eng2.submit(SpMMRequest(0, rng.normal(size=(600, 32))
                                .astype(np.float32)))
        out = eng2.run()[0]
        from repro.sparse.linear import incrs_sharded_to_dense_weight
        np.testing.assert_allclose(
            out.out, incrs_sharded_to_dense_weight(p).T @ out.b,
            rtol=1e-4, atol=1e-4)
        print("SPMM_ENGINE_SHARDED_OK")
    """))


def test_spmm_engine_sharded_swap_pattern():
    """Lifecycle hot-swap on a MULTI-DEVICE engine: a magnitude-repacked
    row-sharded layer deploys into the running engine between waves; the
    new pattern's panels stay one-shard-per-device and results match the
    repacked dense oracle."""
    print(_run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.serve.engine import SpMMEngine, SpMMRequest
        from repro.sparse import Linear, SparseSpec
        from repro.sparse import pattern as spat
        from repro.sparse.linear import incrs_sharded_to_dense_weight
        rng = np.random.default_rng(0)
        mesh = Mesh(np.array(jax.devices()).reshape(8), ("data",))
        p = Linear.init(jax.random.PRNGKey(1), 600, 96,
                        SparseSpec("incrs", density=0.5, mesh=mesh,
                                   section=64, block=8)).inner
        eng = SpMMEngine(p, max_wave_cols=128)
        assert eng.sharded and eng.pattern_version == 0
        def serve(rid):
            b = rng.normal(size=(600, 32)).astype(np.float32)
            eng.submit(SpMMRequest(rid, b))
            return b, [r for r in eng.run() if r.rid == rid][0].out
        b, out = serve(0)
        np.testing.assert_allclose(
            out, incrs_sharded_to_dense_weight(p).T @ b,
            rtol=1e-4, atol=1e-4)
        p2 = spat.magnitude_repack(p, 0.1)
        assert spat.get_pattern(p2).version == 1
        eng.swap_pattern(p2)
        assert eng.pattern_version == 1
        assert eng.stats["pattern_swaps"] == 1
        shards = eng.prep.idx.addressable_shards
        assert len({s.device for s in shards}) == 8
        assert all(s.data.shape[0] == 1 for s in shards)
        b, out = serve(1)
        w2 = incrs_sharded_to_dense_weight(p2)
        np.testing.assert_allclose(out, w2.T @ b, rtol=1e-4, atol=1e-4)
        # repack carried surviving values over
        w1 = incrs_sharded_to_dense_weight(p)
        live = w2 != 0
        np.testing.assert_array_equal(w2[live], w1[live])
        print("SPMM_ENGINE_SHARDED_SWAP_OK")
    """))


def test_compressed_psum_error_feedback():
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh, PartitionSpec as P
        from jax import shard_map
        from repro.train.compress import compressed_psum
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4), ("pod", "data"))
        def red(gl, el):
            r, ne = compressed_psum(gl[0], "pod", el[0])
            return r[None], ne[None]
        f = shard_map(red, mesh=mesh, in_specs=(P("pod"), P("pod")),
                      out_specs=(P("pod"), P("pod")), check_vma=False)
        acc_c = jnp.zeros(256); acc_e = jnp.zeros(256)
        err = jnp.zeros((2, 256))
        for s in range(20):
            g = jax.random.normal(jax.random.PRNGKey(s), (2, 256))
            r, err = f(g, err)
            acc_c += r[0]; acc_e += g.sum(0)
        rel = float(jnp.abs(acc_c - acc_e).max() / jnp.abs(acc_e).max())
        assert rel < 0.02, rel
        print("COMPRESS_OK", rel)
    """)
    assert "COMPRESS_OK" in out


def test_sharded_train_step_matches_single_device():
    """One train step on a 2x4 mesh == the same step on one device."""
    out = _run("""
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import Mesh
        from repro.models import sharding as sh
        from repro.models.config import ModelConfig
        from repro.train import trainer
        from repro.train.optimizer import AdamWConfig
        from repro.data.pipeline import SyntheticTokens

        cfg = ModelConfig("t", 2, 64, 4, 2, 128, 256, dtype="float32")
        opt = AdamWConfig(lr=1e-3, warmup_steps=1, total_steps=10)
        params, opt_state, axes = trainer.init_train_state(
            cfg, opt, jax.random.PRNGKey(0))
        batch = {k: jnp.asarray(v) for k, v in
                 SyntheticTokens(256, 8, 32, seed=1).batch_at(0).items()}

        # single device
        p1, o1, m1 = trainer.build_train_step(cfg, opt, axes, donate=False)(
            params, opt_state, batch)

        # 2x4 mesh
        mesh = Mesh(np.array(jax.devices()).reshape(2, 4),
                    ("data", "model"))
        with sh.axis_rules(mesh):
            step = trainer.build_train_step(cfg, opt, axes, donate=False,
                                            params_template=params,
                                            opt_template=opt_state)
            with mesh:
                p2, o2, m2 = step(params, opt_state, batch)
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-5)
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       rtol=2e-4, atol=2e-5)
        print("SHARDED_STEP_OK")
    """)
    assert "SHARDED_STEP_OK" in out
