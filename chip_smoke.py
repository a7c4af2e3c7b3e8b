#!/usr/bin/env python3
"""Run the main path once on a TPU and check it against host references.

  python chip_smoke.py                # phases A and B on one chip
  python chip_smoke.py --four-chips   # only the row-sharded path, 4 chips

Phase A serves the paper's own workload, ``incrs-docword`` at full size
(700 x 12,000, d=0.04), through ``SpMMEngine``: a continuous-mode trace of
mixed widths with ``variant="auto"``, then one wave through each explicit
InCRS grid order and through the ``bsr`` and ``dense`` formats, so every
kernel the dispatcher can pick runs. Phase B trains one sparse layer at
the Mixtral-8x7B expert FFN width (4096 -> 14336, density 0.5, 512
tokens): forward, gradients, one AdamW step, then serves the trained
layer. ``--four-chips`` serves Phase A's operand through the row-sharded
engine and runs the sharded Phase B layer forward and backward, each
against the same process's single-device result and the host reference.

Every output is compared with a float32 reference computed on the host
with numpy. An error is max |out - ref| / max |ref|. The last line of
standard output is one JSON object naming the device; it is printed only
when JAX runs on a TPU, the kernels compile to Mosaic, and every phase
passed.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.configs.paper_spmm import WORKLOADS  # noqa: E402
from repro.data.datasets import scaled, synthesize  # noqa: E402
from repro.kernels import ops  # noqa: E402
from repro.serve.engine import SpMMEngine, SpMMRequest  # noqa: E402
from repro.sparse import Linear, SparseSpec, api  # noqa: E402
from repro.sparse import apply as sp_apply  # noqa: E402
from repro.train.optimizer import (AdamWConfig, adamw_init,  # noqa: E402
                                   adamw_update)

# Relative to max |ref|. One bf16 MXU pass over these contraction depths
# errs by a few 1e-3; a dropped or misplaced non-zero errs by more.
TOL = 1e-2
# Sharded vs single-device on the same chip type: the same per-row
# arithmetic, except the cross-shard sum of dx.
TOL_SHARDED = 1e-5
WIDTHS = (256, 768, 128, 512, 768, 128, 512, 256)
MAX_WAVE_COLS = 1024
BSR_BLOCK = 64           # launch.serve's --spmm-block default
LR = 1e-3


def _log(phase: str, **kv) -> None:
    print(f"phase={phase} " + " ".join(f"{k}={v}" for k, v in kv.items()),
          flush=True)


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise RuntimeError(msg)


def rel_err(out, ref) -> float:
    out, ref = np.asarray(out, np.float32), np.asarray(ref, np.float32)
    scale = float(np.max(np.abs(ref))) if ref.size else 0.0
    return float(np.max(np.abs(out - ref))) / max(scale, 1e-30)


def _compile(fn, *args):
    """AOT-compile ``fn``; on a TPU the program must hold a Mosaic kernel
    (an interpreted kernel lowers to plain HLO and has none)."""
    t0 = time.perf_counter()
    compiled = jax.jit(fn).lower(*args).compile()
    dt = time.perf_counter() - t0
    if not ops.INTERPRET:
        _check("tpu_custom_call" in compiled.as_text(),
               "compiled program holds no Mosaic kernel")
    return compiled, dt


def _serve(engine: SpMMEngine, bs) -> list:
    reqs = [SpMMRequest(i, b) for i, b in enumerate(bs)]
    for r in reqs:
        engine.submit(r)
    engine.run()
    _check(all(r.done for r in reqs), "engine left requests unserved")
    return [r.out for r in reqs]


def _docword(scale: float):
    spec = WORKLOADS["incrs-docword"].dataset
    if scale != 1.0:
        spec = scaled(spec, scale)
    return synthesize(spec, seed=0)


# ----------------------------------------------------------------------
def phase_a(scale: float = 1.0, seed: int = 0) -> dict:
    """The paper's docword SpMM served through ``SpMMEngine``."""
    t0 = time.perf_counter()
    a = _docword(scale)
    dense_a = a.to_dense().astype(np.float32)
    m, k = dense_a.shape
    bound = api.plan_for_operand(a, SparseSpec("incrs"))
    rng = np.random.default_rng(seed)
    bs = [rng.standard_normal((k, w), dtype=np.float32) for w in WIDTHS]
    refs = [dense_a @ b for b in bs]
    _log("A", workload="incrs-docword", shape=f"{m}x{k}", nnz=a.nnz,
         setup_s=f"{time.perf_counter() - t0:.2f}")

    eng = SpMMEngine(bound, max_wave_cols=MAX_WAVE_COLS, variant="auto")
    errs, walls = [], []
    for _ in range(2):            # the first pass includes the compiles
        t0 = time.perf_counter()
        outs = _serve(eng, bs)
        walls.append(time.perf_counter() - t0)
        errs.append(max(rel_err(o, r) for o, r in zip(outs, refs)))
    err_auto = max(errs)
    _check(err_auto <= TOL, f"auto engine error {err_auto:.3e} > {TOL}")
    _log("A", path="engine-auto", requests=2 * len(bs),
         waves=eng.stats["waves"], first_pass_s=f"{walls[0]:.3f}",
         second_pass_s=f"{walls[1]:.3f}", max_rel_err=f"{err_auto:.3e}")

    # One full wave through every grid order the dispatcher can pick, and
    # through the bsr and dense formats.
    b = rng.standard_normal((k, MAX_WAVE_COLS), dtype=np.float32)
    ref = dense_a @ b
    worst = err_auto
    outs = {}
    for variant in ("expand", "reuse", "pipelined"):
        _, c_s = _compile(lambda x, v=variant: bound(x, variant=v),
                          jnp.asarray(b))
        e = SpMMEngine(bound, max_wave_cols=MAX_WAVE_COLS, variant=variant)
        t0 = time.perf_counter()
        outs[variant] = _serve(e, [b])[0]
        wall = time.perf_counter() - t0
        err = rel_err(outs[variant], ref)
        _check(err <= TOL, f"{variant} error {err:.3e} > {TOL}")
        worst = max(worst, err)
        _log("A", path=f"engine-{variant}", compile_s=f"{c_s:.3f}",
             wall_s=f"{wall:.3f}", max_rel_err=f"{err:.3e}")
    same = all(np.array_equal(outs["expand"], outs[v])
               for v in ("reuse", "pipelined"))
    _check(same, "InCRS grid orders disagree bitwise")

    # BSR tiles must divide the operand: its wave runs on A zero-padded
    # to a multiple of the tile.
    mp = -(-m // BSR_BLOCK) * BSR_BLOCK
    kp = -(-k // BSR_BLOCK) * BSR_BLOCK
    a_pad = np.zeros((mp, kp), np.float32)
    a_pad[:m, :k] = dense_a
    b_pad = np.zeros((kp, b.shape[1]), np.float32)
    b_pad[:k] = b
    for fmt, operand, rhs in (
            ("bsr", api.plan_for_operand(
                a_pad, SparseSpec("bsr", block=BSR_BLOCK)), b_pad),
            ("dense", api.plan_for_operand(a, SparseSpec("dense")), b)):
        _, c_s = _compile(lambda x, op=operand: op(x), jnp.asarray(rhs))
        e = SpMMEngine(operand, max_wave_cols=MAX_WAVE_COLS)
        t0 = time.perf_counter()
        out = _serve(e, [rhs])[0][:m]
        wall = time.perf_counter() - t0
        err = rel_err(out, ref)
        _check(err <= TOL, f"{fmt} error {err:.3e} > {TOL}")
        worst = max(worst, err)
        _log("A", path=f"engine-{fmt}", compile_s=f"{c_s:.3f}",
             wall_s=f"{wall:.3f}", max_rel_err=f"{err:.3e}")
    _log("A", result="pass", max_rel_err=f"{worst:.3e}", tol=TOL)
    return {"max_rel_err": worst}


# ----------------------------------------------------------------------
def _layer_problem(d_in, d_out, tokens, density, seed):
    lin = Linear.init(jax.random.PRNGKey(seed), d_in, d_out,
                      SparseSpec("incrs", density=density))
    rng = np.random.default_rng(seed + 1)
    x = rng.standard_normal((tokens, d_in), dtype=np.float32)
    cot = rng.standard_normal((tokens, d_out), dtype=np.float32)
    return lin, x, cot


def _linear_loss(p, x, cot):
    """sum(y * cot): dW = x^T cot and dx = cot W^T exactly, and the loss
    is linear in the weights, so one optimizer step has a closed form."""
    return jnp.sum(sp_apply(p, x) * cot)


def phase_b(d_in: int = 4096, d_out: int = 14336, tokens: int = 512,
            density: float = 0.5, seed: int = 0) -> dict:
    """One sparse layer at the Mixtral-8x7B expert FFN width: forward,
    gradients, one AdamW step, then served."""
    t0 = time.perf_counter()
    lin, x, cot = _layer_problem(d_in, d_out, tokens, density, seed)
    w = lin.to_dense()
    _log("B", layer=f"{d_in}->{d_out}", tokens=tokens, nnz=lin.nnz,
         density=f"{lin.density:.4f}",
         setup_s=f"{time.perf_counter() - t0:.2f}")
    xd, cotd = jnp.asarray(x), jnp.asarray(cot)

    fwd, c_s = _compile(sp_apply, lin, xd)
    t0 = time.perf_counter()
    y = np.asarray(fwd(lin, xd))
    wall = time.perf_counter() - t0
    err_y = rel_err(y, x @ w)
    _check(err_y <= TOL, f"forward error {err_y:.3e} > {TOL}")
    _log("B", path="forward", compile_s=f"{c_s:.3f}", wall_s=f"{wall:.3f}",
         max_rel_err=f"{err_y:.3e}")

    grad = jax.grad(_linear_loss, argnums=(0, 1))
    gfn, c_s = _compile(grad, lin, xd, cotd)
    t0 = time.perf_counter()
    g_lin, g_x = gfn(lin, xd, cotd)
    g_w = g_lin.to_dense()
    wall = time.perf_counter() - t0
    live = w != 0
    g_w_ref = x.T @ cot
    err_gw = rel_err(g_w[live], g_w_ref[live])
    err_gx = rel_err(g_x, cot @ w.T)
    _check(err_gw <= TOL, f"dW error on live entries {err_gw:.3e} > {TOL}")
    _check(err_gx <= TOL, f"dx error {err_gx:.3e} > {TOL}")
    _log("B", path="grad", compile_s=f"{c_s:.3f}", wall_s=f"{wall:.3f}",
         dw_max_rel_err=f"{err_gw:.3e}", dx_max_rel_err=f"{err_gx:.3e}")

    opt = AdamWConfig(lr=LR, weight_decay=0.0, warmup_steps=0,
                      total_steps=1)

    def step(p, s):
        loss, g = jax.value_and_grad(_linear_loss)(p, xd, cotd)
        p, s, _ = adamw_update(opt, g, s, p)
        return p, s, loss

    state = adamw_init(opt, lin)
    sfn, c_s = _compile(step, lin, state)
    t0 = time.perf_counter()
    trained, state, loss0 = sfn(lin, state)
    loss1 = float(_linear_loss(trained, xd, cotd))
    wall = time.perf_counter() - t0
    # The first Adam step moves every live weight by -lr * sign(dW). Check
    # it where the host gradient's sign is beyond the gradient's error.
    w_t = trained.to_dense()
    delta = w_t - w
    sure = live & (np.abs(g_w_ref) > 4 * TOL * np.max(np.abs(g_w_ref)))
    _check(sure.any(), "no weight with a sure gradient sign")
    err_step = float(np.max(np.abs(
        delta[sure] + LR * np.sign(g_w_ref[sure])))) / LR
    _check(err_step <= TOL, f"AdamW step error {err_step:.3e} > {TOL}")
    _check(not np.any(delta[~live]), "the step moved a pruned weight")
    _check(loss1 < float(loss0), f"loss did not fall: {loss0} -> {loss1}")
    _log("B", path="adamw-step", compile_s=f"{c_s:.3f}",
         wall_s=f"{wall:.3f}", loss=f"{float(loss0):.6e}->{loss1:.6e}",
         step_max_rel_err=f"{err_step:.3e}")

    rng = np.random.default_rng(seed + 2)
    bs = [rng.standard_normal((d_in, c), dtype=np.float32)
          for c in (128, 256, 512)]
    eng = SpMMEngine(trained, max_wave_cols=512)
    t0 = time.perf_counter()
    outs = _serve(eng, bs)
    wall = time.perf_counter() - t0
    err_s = max(rel_err(o, w_t.T @ b) for o, b in zip(outs, bs))
    _check(err_s <= TOL, f"served trained layer error {err_s:.3e} > {TOL}")
    _log("B", path="serve-trained", requests=len(bs),
         waves=eng.stats["waves"], wall_s=f"{wall:.3f}",
         max_rel_err=f"{err_s:.3e}")
    worst = max(err_y, err_gw, err_gx, err_step, err_s)
    _log("B", result="pass", max_rel_err=f"{worst:.3e}", tol=TOL)
    return {"max_rel_err": worst}


# ----------------------------------------------------------------------
def _on_devices(arr, n: int) -> None:
    devs = arr.sharding.device_set
    _check(len(devs) == n, f"array spans {len(devs)} devices, not {n}")


def phase_sharded(n_devices: int = 4, scale: float = 1.0, d_in: int = 4096,
                  d_out: int = 14336, tokens: int = 512,
                  density: float = 0.5, seed: int = 0) -> dict:
    """The row-sharded InCRS path on ``n_devices``: Phase A's operand
    through the sharded engine, the sharded Phase B layer forward and
    backward; each against the single-device run and the host."""
    devices = jax.devices()
    _check(len(devices) >= n_devices,
           f"{n_devices} devices needed, {len(devices)} found")
    mesh = Mesh(np.asarray(devices[:n_devices]), ("data",))

    a = _docword(scale)
    dense_a = a.to_dense().astype(np.float32)
    k = dense_a.shape[1]
    one = api.plan_for_operand(a, SparseSpec("incrs"))
    shard = api.plan_for_operand(a, SparseSpec("incrs", mesh=mesh))
    _on_devices(shard.values, n_devices)
    _on_devices(shard.plan.meta.fwd_idx, n_devices)
    rng = np.random.default_rng(seed)
    bs = [rng.standard_normal((k, w), dtype=np.float32) for w in WIDTHS]
    t0 = time.perf_counter()
    outs_n = _serve(SpMMEngine(shard, max_wave_cols=MAX_WAVE_COLS), bs)
    wall = time.perf_counter() - t0
    outs_1 = _serve(SpMMEngine(one, max_wave_cols=MAX_WAVE_COLS), bs)
    err_host = max(rel_err(o, dense_a @ b) for o, b in zip(outs_n, bs))
    err_one = max(rel_err(o, r) for o, r in zip(outs_n, outs_1))
    bitwise = all(np.array_equal(o, r) for o, r in zip(outs_n, outs_1))
    _check(err_host <= TOL, f"sharded engine error {err_host:.3e} > {TOL}")
    _check(err_one <= TOL_SHARDED,
           f"sharded vs single-device {err_one:.3e} > {TOL_SHARDED}")
    _log("S", path="engine-sharded", devices=n_devices,
         requests=len(bs), wall_s=f"{wall:.3f}",
         max_rel_err=f"{err_host:.3e}", vs_single=f"{err_one:.3e}",
         bitwise_vs_single=bitwise)

    lin, x, cot = _layer_problem(d_in, d_out, tokens, density, seed)
    lin_n = lin.shard(mesh)
    _on_devices(lin_n.values, n_devices)
    w = lin.to_dense()
    xd, cotd = jnp.asarray(x), jnp.asarray(cot)
    grad = jax.grad(_linear_loss, argnums=(0, 1))
    res = {}
    for name, layer in (("single", lin), ("sharded", lin_n)):
        f, c_s = _compile(sp_apply, layer, xd)
        g, c2_s = _compile(grad, layer, xd, cotd)
        t0 = time.perf_counter()
        y = np.asarray(f(layer, xd))
        g_lin, g_x = g(layer, xd, cotd)
        res[name] = (y, g_lin.to_dense(), np.asarray(g_x))
        _log("S", path=f"layer-{name}", compile_s=f"{c_s + c2_s:.3f}",
             wall_s=f"{time.perf_counter() - t0:.3f}")
    live = w != 0
    y_n, gw_n, gx_n = res["sharded"]
    y_1, gw_1, gx_1 = res["single"]
    errs_host = (rel_err(y_n, x @ w),
                 rel_err(gw_n[live], (x.T @ cot)[live]),
                 rel_err(gx_n, cot @ w.T))
    errs_one = (rel_err(y_n, y_1), rel_err(gw_n[live], gw_1[live]),
                rel_err(gx_n, gx_1))
    _check(max(errs_host) <= TOL,
           f"sharded layer vs host {max(errs_host):.3e} > {TOL}")
    _check(max(errs_one) <= TOL_SHARDED,
           f"sharded layer vs single {max(errs_one):.3e} > {TOL_SHARDED}")
    _log("S", path="layer-compare", devices=n_devices,
         y_err=f"{errs_host[0]:.3e}", dw_err=f"{errs_host[1]:.3e}",
         dx_err=f"{errs_host[2]:.3e}", y_vs_single=f"{errs_one[0]:.3e}",
         dw_vs_single=f"{errs_one[1]:.3e}",
         dx_vs_single=f"{errs_one[2]:.3e}",
         y_bitwise=bool(np.array_equal(y_n, y_1)),
         dw_bitwise=bool(np.array_equal(gw_n, gw_1)))
    worst = max((err_host,) + errs_host)
    _log("S", result="pass", max_rel_err=f"{worst:.3e}", tol=TOL)
    return {"max_rel_err": worst}


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the row-sharded path on 4 devices")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: JAX runs on {dev.platform!r}, not a TPU",
              file=sys.stderr)
        return 1
    if ops.INTERPRET:
        print("chip_smoke: kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    from repro.launch import compile_cache
    compile_cache.enable()
    t0 = time.perf_counter()
    if args.four_chips:
        phase_sharded(4, seed=args.seed)
    else:
        phase_a(seed=args.seed)
        phase_b(seed=args.seed)
    print(f"total_s={time.perf_counter() - t0:.1f}", flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
