"""Serving launcher: batched requests through the wave engines.

  python -m repro.launch.serve --arch recurrentgemma-2b --smoke \
      --n-requests 8 --max-new 16

SpMM mode serves the paper's own workload (one fixed sparse operand, a
queue of dense RHSs) through ``serve.SpMMEngine`` behind the plan–execute
API: ``--format {incrs,bsr,dense}`` picks the kernel family purely by
``SparseSpec`` — the engine code path is identical — and
``--spmm-shards N`` row-shards the InCRS operand across the first N local
devices (use ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` to
fake a mesh on CPU):

  python -m repro.launch.serve --spmm --spmm-shards 8 --n-requests 8
  python -m repro.launch.serve --spmm --format bsr --spmm-swap
"""
from __future__ import annotations

import argparse
import time


def _main_spmm(args):
    """The paper's SpMM workload through the plan–execute engine: ONE code
    path for every ``--format`` — the spec decides the kernel family, the
    mesh on the spec decides the sharding."""
    import dataclasses

    import jax
    import numpy as np

    from ..data.datasets import DatasetSpec, synthesize
    from ..serve.engine import SpMMEngine, SpMMRequest
    from ..sparse import api
    from ..sparse.pattern import magnitude_mask

    spec = DatasetSpec("serve", args.spmm_rows, args.spmm_cols,
                       args.spmm_density)
    a = synthesize(spec, seed=args.seed)
    mesh = None
    if args.spmm_shards > 1:
        if args.format != "incrs":
            raise SystemExit(f"--spmm-shards is the row-sharded InCRS "
                             f"data path; --format {args.format} does "
                             f"not shard")
        devs = jax.devices()
        if len(devs) < args.spmm_shards:
            raise SystemExit(
                f"--spmm-shards {args.spmm_shards} needs that many devices "
                f"(have {len(devs)}; on CPU set XLA_FLAGS="
                f"--xla_force_host_platform_device_count={args.spmm_shards})")
        mesh = jax.sharding.Mesh(
            np.asarray(devs[:args.spmm_shards]), ("data",))
    sspec = api.SparseSpec(args.format, mesh=mesh,
                           block=(args.spmm_block
                                  if args.format == "bsr" else None))
    eng = SpMMEngine(api.plan_for_operand(a, sspec),
                     max_wave_cols=args.spmm_max_wave_cols,
                     continuous=not args.spmm_wave_barrier,
                     latency_budget_us=args.spmm_latency_budget_us)
    rng = np.random.default_rng(args.seed)
    reqs = [SpMMRequest(i, rng.normal(
        size=(spec.n, args.spmm_batch_cols)).astype(np.float32))
        for i in range(args.n_requests)]
    t0 = time.time()
    for r in reqs:
        eng.submit(r)
    done = eng.run()
    dt = time.time() - t0
    where = f"{args.spmm_shards}-way row-sharded" if mesh else "single-device"
    s = eng.stats_summary()
    print(f"spmm A={spec.m}x{spec.n} d={spec.density} nnz={a.nnz} "
          f"format={args.format} ({where}, {s['mode']}): served "
          f"{len(done)} requests / {eng.stats['cols']} cols in {dt:.2f}s, "
          f"waves={eng.stats['waves']}")
    print(f"  {s['requests_per_s']:.1f} req/s, latency "
          f"p50={s['latency_ms']['p50']:.1f}ms "
          f"p99={s['latency_ms']['p99']:.1f}ms, prep overlap "
          f"{s['prep_overlap_fraction']:.0%} "
          f"(cost model: {s['cost_model']['source']}, "
          f"{s['cost_model']['n_observed']} waves observed)")
    ref = a.to_dense()
    err = max(float(np.abs(r.out - ref @ r.b).max()) for r in done)
    print(f"  max |err| vs dense oracle: {err:.2e}")
    if args.spmm_swap:
        # Live pattern swap = plan rebuild: magnitude-re-prune the operand
        # to half its density under the SAME spec and deploy the rebuilt
        # plan into the RUNNING engine between waves.
        mask_a = magnitude_mask(ref, spec.density / 2)
        swap_spec = dataclasses.replace(
            sspec, mask=np.ascontiguousarray(mask_a.T))
        bound2 = api.plan_for_operand(np.where(mask_a, ref, 0.0), swap_spec)
        eng.swap_pattern(bound2)
        reqs2 = [SpMMRequest(100 + i, rng.normal(
            size=(spec.n, args.spmm_batch_cols)).astype(np.float32))
            for i in range(args.n_requests)]
        for r in reqs2:
            eng.submit(r)
        done2 = [r for r in eng.run() if r.rid >= 100]
        ref2 = np.where(mask_a, ref, 0.0)
        err2 = max(float(np.abs(r.out - ref2 @ r.b).max()) for r in done2)
        print(f"  swapped to d={mask_a.mean():.3f} "
              f"(swaps={eng.stats['pattern_swaps']}): served "
              f"{len(done2)} more, max |err|: {err2:.2e}")
    return len(done)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="internvl2-1b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--n-requests", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--n-slots", type=int, default=4)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--spmm", action="store_true",
                    help="serve the paper's SpMM workload instead of an LM")
    ap.add_argument("--format", default="incrs",
                    choices=("incrs", "bsr", "dense"),
                    help="kernel family for the served operand (a "
                         "SparseSpec field — one engine code path for "
                         "all of them)")
    ap.add_argument("--spmm-block", type=int, default=64,
                    help="BSR tile side for --format bsr")
    ap.add_argument("--spmm-shards", type=int, default=1,
                    help="row-shard the sparse operand across this many "
                         "devices (1 = single-device)")
    ap.add_argument("--spmm-swap", action="store_true",
                    help="after the first waves, re-prune the operand to "
                         "half density and hot-swap it into the running "
                         "engine (lifecycle smoke)")
    ap.add_argument("--spmm-max-wave-cols", type=int, default=512,
                    help="hard wave cap (the feasibility-proven shape); "
                         "the cost model chooses widths up to it")
    ap.add_argument("--spmm-wave-barrier", action="store_true",
                    help="serve in the wave-barrier compatibility mode "
                         "(strict FIFO, no prep/compute overlap)")
    ap.add_argument("--spmm-latency-budget-us", type=float, default=None,
                    help="per-wave latency target: the cost model narrows "
                         "waves so each is predicted to finish inside it")
    ap.add_argument("--spmm-rows", type=int, default=256)
    ap.add_argument("--spmm-cols", type=int, default=1024)
    ap.add_argument("--spmm-density", type=float, default=0.03)
    ap.add_argument("--spmm-batch-cols", type=int, default=64)
    args = ap.parse_args(argv)
    from .compile_cache import enable
    enable()
    if args.spmm:
        return _main_spmm(args)

    import jax
    import jax.numpy as jnp
    import numpy as np

    from .. import configs
    from ..models import model as M
    from ..serve.engine import Request, ServeEngine

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    params, _ = M.init(cfg, jax.random.PRNGKey(args.seed))
    eng = ServeEngine(cfg, params, n_slots=args.n_slots,
                      cache_dtype=jnp.dtype(cfg.dtype), seed=args.seed)
    rng = np.random.default_rng(args.seed)
    for i in range(args.n_requests):
        eng.submit(Request(
            i, rng.integers(0, cfg.vocab_size,
                            args.prompt_len).astype(np.int32),
            max_new=args.max_new, temperature=args.temperature))
    t0 = time.time()
    done = eng.run()
    dt = time.time() - t0
    total_new = sum(len(r.out) for r in done)
    print(f"arch={cfg.name} served {len(done)} requests, "
          f"{total_new} tokens in {dt:.1f}s "
          f"({total_new/dt:.1f} tok/s), waves={eng.stats['waves']}")
    for r in done[:3]:
        print(f"  req {r.rid}: {r.out[:8]}...")
    return len(done)


if __name__ == "__main__":
    main()
