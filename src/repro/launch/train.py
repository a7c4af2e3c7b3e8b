"""Training launcher: config -> mesh -> data -> train loop -> checkpoints.

Single-host entry point (multi-host launch would add
``jax.distributed.initialize`` before mesh creation — the step function,
shardings and checkpoint logic are already multi-host-safe because they
only speak in global shapes + NamedShardings).

  python -m repro.launch.train --arch mixtral-8x7b --smoke --steps 50
  python -m repro.launch.train --arch granite-34b --smoke --resume ...
"""
from __future__ import annotations

import argparse
import time


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="granite-34b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--n-micro", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--int8-opt", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--prune-final-density", type=float, default=None,
                    help="magnitude-re-prune every sparse-linear layer on "
                         "the cubic schedule down to this density (no-op "
                         "for configs without sparse layers)")
    ap.add_argument("--prune-nm", default=None, metavar="N:M",
                    help="structured N:M re-pruning (e.g. 2:4): exactly N "
                         "survivors per M-group along d_in; the schedule "
                         "gates WHEN, the density is fixed at N/M "
                         "(mutually exclusive with --prune-final-density)")
    ap.add_argument("--prune-every", type=int, default=10,
                    help="re-prune cadence in steps")
    ap.add_argument("--prune-warmup-frac", type=float, default=0.1)
    args = ap.parse_args(argv)

    from .compile_cache import enable
    enable()
    import jax
    import jax.numpy as jnp

    from .. import configs
    from ..checkpoint import CheckpointManager
    from ..data.pipeline import Prefetcher, SyntheticTokens
    from ..train import trainer
    from ..train.optimizer import AdamWConfig, adamw_init

    cfg = configs.get_smoke(args.arch) if args.smoke else configs.get(args.arch)
    opt_cfg = AdamWConfig(lr=args.lr, warmup_steps=max(args.steps // 20, 2),
                          total_steps=args.steps, quantize=args.int8_opt)

    params, opt_state, axes = trainer.init_train_state(
        cfg, opt_cfg, jax.random.PRNGKey(args.seed))
    n_params = sum(p.size for p in jax.tree.leaves(params))
    print(f"arch={cfg.name} params={n_params/1e6:.1f}M "
          f"devices={jax.device_count()}")

    step_fn = trainer.build_train_step(cfg, opt_cfg, axes,
                                       n_micro=args.n_micro)

    ck = None
    start_step = 0
    if args.ckpt_dir:
        ck = CheckpointManager(args.ckpt_dir, keep=3)
        if args.resume and ck.latest_step() is not None:
            start_step = ck.latest_step()
            state = ck.restore(start_step,
                               {"params": params, "opt": opt_state})
            params, opt_state = state["params"], state["opt"]
            print(f"resumed from step {start_step}")

    src = SyntheticTokens(cfg.vocab_size, args.batch, args.seq,
                          seed=args.seed,
                          n_prefix=(cfg.n_prefix_embeds
                                    if cfg.input_mode == "embeds" else 0),
                          d_model=cfg.d_model)
    src.step = start_step
    data = Prefetcher(src, depth=2, timeout_s=60.0,
                      fallback=lambda n: src.batch_at(10**9 + n))

    prune_cb = None
    if args.prune_final_density is not None and args.prune_nm is not None:
        raise SystemExit("flag conflict: pass --prune-final-density OR "
                         "--prune-nm, not both — an N:M policy fixes the "
                         "final density at N/M")
    if args.prune_final_density is not None or args.prune_nm is not None:
        prune_flag = ("--prune-nm" if args.prune_nm is not None
                      else "--prune-final-density")
        if args.int8_opt:
            # fail NOW, not at the first due step after the dense warmup:
            # quantized moments cannot ride a slot remap.
            raise SystemExit(
                f"flag conflict: {prune_flag} cannot be combined with "
                f"--int8-opt. A pattern repack remaps value slots, and "
                f"int8-quantized AdamW moments cannot follow (their "
                f"per-block quantization scales do not survive the "
                f"remap). Drop --int8-opt so the optimizer runs with "
                f"plain f32 moments (AdamWConfig(quantize=False)) — the "
                f"sparsity lifecycle requires it.")
        from ..sparse.pattern import PruneSchedule, parse_nm
        if args.prune_nm is not None:
            n, m = parse_nm(args.prune_nm)
            final_density, policy = n / m, args.prune_nm
        else:
            final_density, policy = args.prune_final_density, "magnitude"
        prune_cb = trainer.make_prune_callback(PruneSchedule(
            final_density, args.steps,
            warmup_frac=args.prune_warmup_frac, every=args.prune_every),
            policy=policy)

    t0 = time.time()
    tokens_done = 0
    for step in range(start_step, args.steps):
        if prune_cb is not None:
            params, opt_state, pinfo = prune_cb(step, params, opt_state)
            if pinfo:
                print(f"step {step:5d}  re-pruned {pinfo['layers']} layers "
                      f"to density {pinfo['density']:.3f} "
                      f"({pinfo['nnz']} non-zeros)", flush=True)
        batch = {k: jnp.asarray(v) for k, v in next(data).items()}
        params, opt_state, metrics = step_fn(params, opt_state, batch)
        tokens_done += args.batch * args.seq
        if (step + 1) % args.log_every == 0 or step + 1 == args.steps:
            dt = time.time() - t0
            print(f"step {step+1:5d}  loss {float(metrics['loss']):.4f}  "
                  f"gnorm {float(metrics['grad_norm']):.3f}  "
                  f"lr {float(metrics['lr']):.2e}  "
                  f"tok/s {tokens_done/dt:,.0f}", flush=True)
        if ck and (step + 1) % args.ckpt_every == 0:
            ck.save(step + 1, {"params": params, "opt": opt_state})
    if ck:
        ck.save(args.steps, {"params": params, "opt": opt_state})
        ck.wait()
    data.close()
    return float(metrics["loss"])


if __name__ == "__main__":
    main()
