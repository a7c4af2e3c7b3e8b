"""Kernel micro-benchmarks (interpret mode on CPU — correctness-path
timings plus DERIVED work metrics; real-TPU timing comes from the roofline
terms, not from this host).

``--json PATH`` additionally emits a machine-readable record (schema
``bench_kernels/v1``) so the perf trajectory is tracked across PRs:

  {"schema": "bench_kernels/v1",
   "rows": [{"name": ..., "us": ..., "derived": ...,
             "model": {...}?}, ...],
   "comparisons": {"incrs_spmm_fused_vs_twopass":
       {"fused_us": ..., "twopass_us": ..., "speedup": ...,
        "workload": "128x1024 d=0.03 @ 256 cols"}}}

Fused-kernel rows additionally carry a ``model`` block — the autotuner's
cycle-level cost prediction (``core.mesh_sim.fused_spmm_cost``) for that
exact launch, so ``benchmarks/roofline.py --kernels`` can report each
row's predicted-vs-measured overhead factor and fraction-of-roofline.

``--check BASELINE`` re-runs the suite and fails (exit 1) if any kernel
row regressed >25% against the committed record, after normalizing both
sides by their ``dense_mm_256`` row — interpret-mode timings scale with
host speed, so only machine-relative ratios are comparable across hosts.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis import vmem
from repro.core.bsr import BSR, magnitude_block_mask
from repro.data.datasets import DatasetSpec, synthesize
from repro.kernels import autotune, ops
from repro.launch import compile_cache


def _time(fn, *args, reps: int = 5):
    """Best-of-reps wall time in us (after one warmup). The minimum — not
    the mean — is reported: interpret-mode timings on a shared host carry
    multi-x scheduler noise, and min-of-N is the standard way to estimate
    the noise-free cost so cross-variant RATIOS stay meaningful."""
    out = fn(*args)
    jax.block_until_ready(out)
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        out = fn(*args)
        jax.block_until_ready(out)
        best = min(best, time.perf_counter() - t0)
    return best * 1e6      # us


def run(seed: int = 0):
    rng = np.random.default_rng(seed)
    rows = []
    comparisons = {}
    models = {}               # row name -> cost-model block (fused rows)
    m = k = n = 256
    a = jnp.asarray(rng.normal(size=(m, k)).astype(np.float32))
    b = jnp.asarray(rng.normal(size=(k, n)).astype(np.float32))
    us = _time(lambda x, y: ops.dense_mm(x, y), a, b)
    rows.append(("dense_mm_256", us, f"flops={2*m*k*n:.3g}"))

    d = rng.normal(size=(512, 512)).astype(np.float32)
    b512 = jnp.asarray(rng.normal(size=(512, n)).astype(np.float32))
    for density in (0.25, 0.5):
        mask = magnitude_block_mask(d, (128, 128), density)
        bsr = BSR.from_mask(d, mask, (128, 128))
        us = _time(lambda x: ops.spmm(bsr, x), b512)
        useful = 2 * bsr.nnz_blocks * 128 * 128 * n
        rows.append((f"bsr_spmm_d{density}", us,
                     f"useful_flops={useful:.3g};"
                     f"skipped={1-bsr.block_density:.2f}"))

    spec = DatasetSpec("kb", 128, 1024, 0.03)
    a_sp = synthesize(spec, seed)
    us = _time(lambda: ops.spmm(a_sp, a_sp, rounds=128))
    rows.append(("index_match_spmm", us, f"nnz={a_sp.nnz}"))

    from repro.core.incrs import InCRS
    t0 = time.perf_counter()
    inc = InCRS.from_crs(a_sp)
    prep_ms = (time.perf_counter() - t0) * 1e3
    rows.append(("incrs_from_crs", prep_ms * 1e3, f"nnz={a_sp.nnz}"))
    us = _time(lambda: ops.incrs_to_dense(inc))
    rows.append(("incrs_gather", us, f"sections={inc.n_sections}"))

    # Fused single-pass SpMM vs the incrs_to_dense -> dense_mm two-pass
    # pipeline on the SAME workload (acceptance: fused must win).
    bk = jnp.asarray(rng.normal(size=(spec.n, 256)).astype(np.float32))
    fused_us = _time(lambda x: ops.spmm(inc, x), bk)
    rows.append(("incrs_spmm_fused", fused_us,
                 f"nnz={a_sp.nnz};sections={inc.n_sections}"))
    twopass_us = _time(lambda x: ops.dense_mm(ops.incrs_to_dense(inc), x), bk)
    rows.append(("incrs_spmm_twopass", twopass_us,
                 "pipeline=incrs_to_dense+dense_mm"))
    comparisons["incrs_spmm_fused_vs_twopass"] = {
        "fused_us": fused_us,
        "twopass_us": twopass_us,
        "speedup": twopass_us / fused_us,
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 256 cols",
    }

    # Sparsity-lifecycle repack: one full magnitude re-prune of a trainable
    # InCRS Linear on the SAME workload (densify -> new mask -> rebuild
    # counters/stripes/t_gather), against the fused SpMM it amortizes over.
    # The ratio is the "how many multiplies must a pattern survive" number
    # a re-pruning schedule's cadence should beat.
    from repro.sparse import Linear, SparseSpec, api, pattern as spat
    lp = Linear.from_dense(
        a_sp.to_dense().T,
        SparseSpec("incrs", section=inc.section, block=inc.block)).inner
    dens = [0.02, 0.015, 0.01]

    def _repack_cycle():
        p = lp
        for d in dens:
            p = spat.magnitude_repack(p, d)
        return p.values

    repack_us = _time(_repack_cycle) / len(dens)
    rows.append(("incrs_repack", repack_us,
                 f"nnz={a_sp.nnz};per-repack;vs_fused="
                 f"{repack_us / fused_us:.1f}x"))
    comparisons["incrs_repack_vs_spmm"] = {
        "repack_us": repack_us,
        "fused_spmm_us": fused_us,
        # one repack costs this many fused SpMMs — the number of
        # multiplies a pattern must outlive for re-prep to amortize
        "repack_cost_in_spmms": repack_us / fused_us,
        "workload": f"{spec.m}x{spec.n} d={spec.density} magnitude "
                    f"re-prune, amortized over 256-col fused SpMM",
    }

    # Plan-once vs per-call prep: the plan–execute API (sparse.api) builds
    # the stripe metadata ONCE and streams right-hand sides against it;
    # the ad-hoc path re-preps the operand on every call (cache evicted
    # between calls). The ratio is what a caller saves by planning — the
    # steady-state serving contract SpMMEngine/PreparedOperand always
    # implemented, now visible at the API boundary.
    planned = api.plan_for_operand(a_sp, SparseSpec("incrs"))
    plan_us = _time(lambda x: planned(x), bk)
    rows.append(("spmm_planned", plan_us,
                 "plan-once (sparse.api.plan_for_operand), prep amortized"))

    def _adhoc(x):
        ops.invalidate_prepared(inc)           # forget the cached prep
        return ops.spmm(inc, x)

    adhoc_us = _time(_adhoc, bk)
    rows.append(("spmm_adhoc_prep", adhoc_us,
                 "per-call prep (cache evicted each call)"))
    comparisons["spmm_plan_vs_adhoc"] = {
        "planned_us": plan_us,
        "adhoc_us": adhoc_us,
        "prep_overhead_x": adhoc_us / plan_us,
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 256 cols, "
                    f"plan-once vs re-prep per call",
    }

    # Stripe-reuse vs per-col-tile re-expansion on the same operand, at a
    # fixed 128-wide col tiling over a 1024-col RHS (8 col tiles): the
    # baseline order expands every section stripe once PER TILE, the reuse
    # order once per (row tile, section). Each explicit-variant row also
    # records the autotuner's cost-model prediction for that exact launch
    # (predict -> measure -> overhead factor; see roofline.py --kernels).
    prep = ops.prepare_incrs(inc, pad_rows_to=128)

    def _model(variant, n_cols, bm=128, bn=128):
        nsec, mrows, smax = prep.idx.shape
        np_ = -(-n_cols // bn) * bn
        cost = autotune.kernel_cost(variant, mrows, np_, n_sections=nsec,
                                    smax=smax, section=prep.section,
                                    bm=bm, bn=bn, nnz=a_sp.nnz)
        # Static VMEM footprint from the same model the checker proves
        # against (analysis.vmem) — roofline.py --kernels reports it.
        foot = vmem.incrs_footprint(variant, m=mrows, n=n_cols, bm=bm,
                                    bn=bn, n_sections=nsec, smax=smax,
                                    section=prep.section)
        return {"variant": variant, "bm": bm, "bn": bn,
                "predicted_us": round(autotune.predict_us(
                    variant, mrows, np_, n_sections=nsec, smax=smax,
                    section=prep.section, bm=bm, bn=bn,
                    interpret=ops.INTERPRET), 1),
                "cycles": cost.cycles, "grid_steps": cost.grid_steps,
                "flops": cost.flops, "hbm_bytes": cost.hbm_bytes,
                "compute_cycles": cost.compute_cycles,
                "memory_cycles": cost.memory_cycles,
                "vmem_bytes": foot.total_bytes,
                "vmem_largest_term": foot.largest.name}

    bw = jnp.asarray(rng.normal(size=(spec.n, 1024)).astype(np.float32))
    expand_us = _time(
        lambda x: ops.spmm(inc, x, bn=128, variant="expand"),
        bw, reps=9)
    rows.append(("incrs_spmm_expand_percoltile", expand_us,
                 "variant=expand;bn=128;cols=1024"))
    models["incrs_spmm_expand_percoltile"] = _model("expand", 1024)
    reuse_us = _time(
        lambda x: ops.spmm(inc, x, bn=128, variant="reuse"),
        bw, reps=9)
    rows.append(("incrs_spmm_reuse", reuse_us,
                 "variant=reuse;bn=128;cols=1024"))
    models["incrs_spmm_reuse"] = _model("reuse", 1024)
    comparisons["incrs_spmm_reuse_vs_expand"] = {
        "reuse_us": reuse_us,
        "expand_us": expand_us,
        "speedup": expand_us / reuse_us,
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 1024 cols, "
                    f"bn=128",
    }

    # Double-buffered RHS pipelining on the same workload: one grid step
    # per row tile, the streamed (section, bn) RHS blocks double-buffered
    # behind the MXU, output-stationary (bm, N) panel (acceptance:
    # pipelined must beat reuse on this row).
    pipe_us = _time(
        lambda x: ops.spmm(inc, x, bn=128, variant="pipelined"),
        bw, reps=9)
    rows.append(("incrs_spmm_pipelined", pipe_us,
                 "variant=pipelined;bn=128;cols=1024"))
    models["incrs_spmm_pipelined"] = _model("pipelined", 1024)
    comparisons["incrs_spmm_pipelined_vs_reuse"] = {
        "pipelined_us": pipe_us,
        "reuse_us": reuse_us,
        "speedup": reuse_us / pipe_us,
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 1024 cols, "
                    f"bn=128",
    }

    # The variant="auto" DECISION POINT: default bn (512) at the 4-tile
    # threshold where auto switches to reuse — this row pair is what
    # justifies the cutover (the bn=128 pair above isolates the reuse
    # effect at a narrow tiling).
    ba = jnp.asarray(rng.normal(size=(spec.n, 2048)).astype(np.float32))
    exp_a = _time(lambda x: ops.spmm(inc, x, variant="expand"),
                  ba, reps=9)
    rows.append(("incrs_spmm_expand_autopoint", exp_a,
                 "variant=expand;bn=default(512);cols=2048"))
    models["incrs_spmm_expand_autopoint"] = _model("expand", 2048, bn=512)
    reu_a = _time(lambda x: ops.spmm(inc, x, variant="reuse"),
                  ba, reps=9)
    rows.append(("incrs_spmm_reuse_autopoint", reu_a,
                 "variant=reuse;bn=default(512);cols=2048"))
    models["incrs_spmm_reuse_autopoint"] = _model("reuse", 2048, bn=512)
    comparisons["incrs_spmm_reuse_vs_expand_default_bn"] = {
        "reuse_us": reu_a,
        "expand_us": exp_a,
        "speedup": exp_a / reu_a,
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 2048 cols, "
                    f"bn=512 (auto threshold)",
    }

    # Autotune economics on the bn=128/1024-col workload: a cold tune()
    # (model-ranked sweep, top candidates measured) vs the lookup a
    # plan-persisted config rides on every later call (memory/disk
    # cache). The gap is what `plan(spec, rhs_shape)` saves every caller
    # after the first.
    tmpdir = tempfile.mkdtemp(prefix="kb-autotune-")
    saved_env = os.environ.get(autotune.CACHE_ENV)
    os.environ[autotune.CACHE_ENV] = os.path.join(tmpdir, "cache.json")
    try:
        autotune.clear_memory_cache()
        t0 = time.perf_counter()
        autotune.tune(prep.idx, prep.val, bw, section=inc.section,
                      interpret=ops.INTERPRET, reps=1)
        miss_us = (time.perf_counter() - t0) * 1e6
        rows.append(("autotune_miss", miss_us,
                     "cold tune(): model-ranked sweep, top-4 measured"))
        hit_us = _time(lambda: autotune.tune(
            prep.idx, prep.val, bw, section=inc.section,
            interpret=ops.INTERPRET, reps=1))
        rows.append(("autotune_hit", hit_us,
                     "tuning-cache lookup (what a persisted plan pays)"))
        comparisons["autotune_hit_vs_miss"] = {
            "hit_us": hit_us,
            "miss_us": miss_us,
            "speedup": miss_us / max(hit_us, 1e-9),
            "workload": f"{spec.m}x{spec.n} d={spec.density} @ 1024 cols "
                        f"tuning sweep vs cached config",
        }
    finally:
        if saved_env is None:
            os.environ.pop(autotune.CACHE_ENV, None)
        else:
            os.environ[autotune.CACHE_ENV] = saved_env
        autotune.clear_memory_cache()

    # Static VMEM prefilter economics: at a WIDE (8192-col) RHS the
    # reuse/pipelined row panels at bm=128 are 4 MiB — over the 2 MiB
    # panel working-set budget — so the checker (analysis.vmem) drops
    # them from the sweep before anything is measured. Same cold tune,
    # fresh caches, with and without the filter; the sweep record's
    # skipped_infeasible list is the proof the skips happened.
    bwide = jnp.asarray(rng.normal(size=(spec.n, 8192)).astype(np.float32))
    autotune.clear_memory_cache()
    t0 = time.perf_counter()
    autotune.tune(prep.idx, prep.val, bwide, section=inc.section,
                  interpret=ops.INTERPRET, reps=1, persist=False)
    filt_us = (time.perf_counter() - t0) * 1e6
    sweep_on = autotune.LAST_SWEEP
    autotune.clear_memory_cache()
    t0 = time.perf_counter()
    autotune.tune(prep.idx, prep.val, bwide, section=inc.section,
                  interpret=ops.INTERPRET, reps=1, persist=False,
                  prefilter=False)
    nofilt_us = (time.perf_counter() - t0) * 1e6
    sweep_off = autotune.LAST_SWEEP
    autotune.clear_memory_cache()
    rows.append(("autotune_prefilter_sweep", filt_us,
                 f"skipped={len(sweep_on.skipped_infeasible)};"
                 f"measured={len(sweep_on.measured)};cols=8192"))
    comparisons["autotune_prefilter"] = {
        "filtered_us": filt_us,
        "unfiltered_us": nofilt_us,
        "speedup": nofilt_us / max(filt_us, 1e-9),
        "n_candidates": sweep_on.n_candidates,
        "n_skipped_infeasible": len(sweep_on.skipped_infeasible),
        "skipped_infeasible": sweep_on.skipped_infeasible,
        "measured_filtered": sweep_on.measured,
        "measured_unfiltered": sweep_off.measured,
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 8192 cols, "
                    f"cold tune with/without static VMEM prefilter",
    }

    # SpGEMM (sparse x sparse) vs densify-then-SpMM, one regime per side
    # of the modelled crossover. The sparse regime is where the row-wise
    # product should win (few matches per round window, so densifying the
    # RHS wastes HBM + gather work); the dense regime is where gathering
    # B once and streaming it through the fused InCRS kernel wins. Each
    # row records measurement; the comparison records both engines, the
    # mesh_sim oracle's pick for THIS backend, and whether the oracle
    # landed on the measured winner (acceptance: it must, on both sides).
    from repro.core import mesh_sim
    from repro.core.crs import CRS

    def _spgemm_regime(m, n, k, density):
        A = (rng.random((m, k)) < density) * rng.standard_normal((m, k))
        Bt = (rng.random((n, k)) < density) * rng.standard_normal((n, k))
        a_crs = CRS.from_dense(A.astype(np.float32))
        bt_crs = CRS.from_dense(Bt.astype(np.float32))
        cost = mesh_sim.spgemm_cost_for(a_crs, bt_crs, rounds=128)
        pick = autotune.pick_spgemm_engine(cost, ops.INTERPRET)
        # the SpGEMM side's representative: the oracle's pick when it is
        # a sparse x sparse engine, the fused one-pass engine otherwise
        sp_engine = pick if pick != "densify" else "reference"
        sp_us = _time(lambda: ops.spmm(a_crs, bt_crs, rounds=128,
                                       variant=sp_engine))
        de_us = _time(lambda: ops.spmm(a_crs, bt_crs, rounds=128,
                                       variant="densify"))
        cm_us = _time(lambda: ops.spmm(a_crs, bt_crs, rounds=128,
                                       variant="condense_merge"), reps=3)
        winner = "densify" if de_us < sp_us else sp_engine
        return {
            "workload": f"{m}x{k} @ {n}x{k}.T d={density} rounds=128",
            "spgemm_us": sp_us, "densify_us": de_us,
            "condense_merge_us": cm_us,
            "speedup_spgemm_over_densify": de_us / sp_us,
            "oracle_pick": pick,
            "oracle_cycle_pick": cost.pick,
            "measured_winner": winner,
            "oracle_correct": (pick == "densify") == (de_us < sp_us),
            "model_us": {
                "fused": autotune.engine_predict_us(cost.fused,
                                                    ops.INTERPRET),
                "condense_merge": autotune.engine_predict_us(
                    cost.spgemm, ops.INTERPRET),
                "densify": autotune.engine_predict_us(cost.densify,
                                                      ops.INTERPRET)},
        }, sp_us, de_us, cm_us

    sp_rec, sp_us, sp_de_us, sp_cm_us = _spgemm_regime(128, 256, 4096, 0.01)
    de_rec, dn_sp_us, dn_de_us, dn_cm_us = _spgemm_regime(256, 256, 512, 0.5)
    rows.append(("spgemm_condense_merge", sp_cm_us,
                 f"two-pass stripe pipeline;{sp_rec['workload']}"))
    rows.append(("spgemm_auto_sparse_regime", sp_us,
                 f"engine={sp_rec['oracle_pick']};{sp_rec['workload']}"))
    rows.append(("spgemm_densify_sparse_regime", sp_de_us,
                 f"engine=densify;{sp_rec['workload']}"))
    rows.append(("spgemm_vs_densify_crossover", dn_de_us,
                 f"engine=densify (dense-regime winner);"
                 f"{de_rec['workload']}"))
    comparisons["spgemm_vs_densify_crossover"] = {
        "sparse_regime": sp_rec,
        "dense_regime": de_rec,
        "oracle_correct_both_sides": (sp_rec["oracle_correct"]
                                      and de_rec["oracle_correct"]),
    }

    # Row-sharded fused SpMM over the first 1, 2, 4, ... of this process's
    # devices (all in this process: a child process could not reach a chip
    # the parent already holds). Same operand as the fused rows above. On
    # fake CPU devices (XLA_FLAGS=--xla_force_host_platform_device_count)
    # the shards share one host, so the rows track the shard_map data
    # path's overhead, not scaling across chips.
    sharded = _sharded_scaling(inc, bk)
    for n_dev, us in sorted(sharded.items()):
        rows.append((f"incrs_spmm_sharded_dev{n_dev}", us,
                     f"devices={n_dev};rows_per_shard={spec.m // n_dev}"))
    base = sharded[1]
    comparisons["incrs_spmm_sharded"] = {
        "us_per_device_count": {str(k): v
                                for k, v in sorted(sharded.items())},
        "relative_to_1dev": {str(k): base / v
                             for k, v in sorted(sharded.items())},
        "workload": f"{spec.m}x{spec.n} d={spec.density} @ 256 cols, "
                    f"row-sharded over {jax.default_backend()} devices",
    }
    return rows, comparisons, models


def _sharded_scaling(inc, b):
    """Time the row-sharded fused SpMM on the first 1, 2, 4, ... devices
    of this process. Returns {n_devices: best_us}; a failure is fatal."""
    from jax.sharding import Mesh
    devices = jax.devices()
    out = {}
    n_dev = 1
    while n_dev <= len(devices):
        mesh = Mesh(np.asarray(devices[:n_dev]), ("data",))
        prep = ops.prepare_incrs_sharded(inc, mesh, pad_rows_to=32)
        out[n_dev] = _time(lambda x: ops.spmm(prep, x), b)
        n_dev *= 2
    return out


# Regression gate: normalize both sides by dense_mm_256 (a pure
# machine-speed proxy) so interpret-mode timings from different hosts
# stay comparable, and ignore rows under the noise floor.
CHECK_TOLERANCE = 0.25
CHECK_FLOOR_US = 200.0
_NORM_ROW = "dense_mm_256"


def check_regressions(rows, baseline_path, tolerance=CHECK_TOLERANCE,
                      floor_us=CHECK_FLOOR_US):
    """Compare fresh rows to a committed record. Returns a list of
    failure strings (empty = pass)."""
    try:
        with open(baseline_path) as f:
            base = json.load(f)
    except (OSError, ValueError) as e:
        return [f"cannot read baseline {baseline_path}: {e}"]
    base_us = {r["name"]: float(r["us"]) for r in base.get("rows", [])}
    new_us = {name: us for name, us, _ in rows}
    norm_old, norm_new = base_us.get(_NORM_ROW), new_us.get(_NORM_ROW)
    if not norm_old or not norm_new:
        return [f"norm row {_NORM_ROW!r} missing from baseline or run"]
    failures = []
    for name, us, _ in rows:
        old = base_us.get(name)
        if old is None or old < floor_us or us < floor_us:
            continue                   # new row / noise-floor row
        rel = (us / norm_new) / (old / norm_old)
        if rel > 1.0 + tolerance:
            failures.append(
                f"{name}: {us:.0f}us vs baseline {old:.0f}us "
                f"(machine-relative {rel:.2f}x > "
                f"{1 + tolerance:.2f}x allowed)")
    return failures


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--json", default=None,
                    help="write machine-readable results to this path")
    ap.add_argument("--check", default=None, metavar="BASELINE",
                    help="fail (exit 1) if any kernel row regresses >25%% "
                         "vs this committed record (machine-relative)")
    args = ap.parse_args(argv)
    compile_cache.enable()
    rows, comparisons, models = run()
    for name, us, derived in rows:
        print(f"kernel,{name},{us:.0f}us,{derived}")
    for name, c in comparisons.items():
        if "speedup" in c:
            print(f"compare,{name},speedup={c['speedup']:.2f}x")
        else:
            print(f"compare,{name},{json.dumps(c, sort_keys=True)}")
    failures = []
    if args.check:
        failures = check_regressions(rows, args.check)
        for f in failures:
            print(f"regression,{f}", file=sys.stderr)
        if not failures:
            print(f"check,ok,vs={args.check}")
    if args.json:
        record = {
            "schema": "bench_kernels/v1",
            "backend": jax.default_backend(),
            "interpret": ops.INTERPRET,
            "rows": [dict({"name": n, "us": round(u, 1), "derived": d},
                          **({"model": models[n]} if n in models else {}))
                     for n, u, d in rows],
            "comparisons": comparisons,
        }
        with open(args.json, "w") as f:
            json.dump(record, f, indent=2)
        print(f"wrote {args.json}")
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
