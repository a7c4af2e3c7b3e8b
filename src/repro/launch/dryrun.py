import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ruff: noqa: E402  (the two lines above MUST precede any jax import)
"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the step (train_step for train shapes, serve_step for
     prefill/decode shapes) with in/out shardings from the logical rules,
  3. compiles — success proves the distribution config is coherent,
  4. records memory_analysis / cost_analysis / per-collective byte counts
     parsed from the post-SPMD HLO into a JSON consumed by
     benchmarks/roofline.py and EXPERIMENTS.md.

Usage:
  python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
  python -m repro.launch.dryrun --all --multi-pod --out dryrun.json
"""
import argparse
import json
import re
import sys
import time
from typing import Dict, Optional

import jax
import jax.numpy as jnp

from .. import configs
from ..configs.shapes import SHAPES, ShapeSpec, applicable
from ..models import sharding as sh
from ..models.config import ModelConfig
from ..train.zero import FSDP_OVERRIDES
from . import specs
from .mesh import make_production_mesh

_DT_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "u64": 8,
             "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
             "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}

_COLL = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
         "collective-permute")

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|s64|u64|s32|u32|s16|u16|s8|u8|"
                       r"pred|f8e4m3fn|f8e5m2)\[([0-9,]*)\]")
_GROUPS_RE = re.compile(r"replica_groups=(?:\{\{([0-9,]+)\}|\[(\d+),(\d+)\])")


def cost_dict(compiled) -> Dict:
    """``compiled.cost_analysis()``: a dict, empty when XLA reports no
    costs."""
    return compiled.cost_analysis() or {}


def _shape_bytes(m) -> int:
    dt, dims = m.group(1), m.group(2)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DT_BYTES[dt]


def parse_collectives(hlo: str) -> Dict[str, Dict[str, float]]:
    """Per-collective-type {count, result_bytes, wire_bytes} from post-SPMD
    HLO. ``wire_bytes`` = ring-algorithm bytes through each chip:
        all-reduce        2 (g-1)/g x bytes
        all-gather          (g-1)/g x bytes   (bytes = gathered result)
        reduce-scatter      (g-1)   x bytes   (bytes = scattered result)
        all-to-all          (g-1)/g x bytes
        collective-permute          x bytes
    Shapes printed in post-SPMD HLO are PER-DEVICE shapes."""
    out = {k: {"count": 0, "result_bytes": 0.0, "wire_bytes": 0.0}
           for k in _COLL}
    for line in hlo.splitlines():
        stripped = line.strip()
        m = re.match(r"^%?[\w.\-]+\s*=\s*(.*)$", stripped)
        if not m:
            continue
        rhs = m.group(1)
        op = None
        for k in _COLL:
            if re.search(rf"\b{k}(-start|-done)?\(", rhs):
                op = k
                break
        if op is None or f"{op}-done(" in rhs:
            continue                     # -done carries no new traffic
        # result bytes: every shape before the op name (handles tuples)
        head = rhs.split(op + "(")[0]
        nbytes = sum(_shape_bytes(s) for s in _SHAPE_RE.finditer(head))
        g = None
        gm = _GROUPS_RE.search(rhs)
        if gm:
            if gm.group(1) is not None:
                g = gm.group(1).count(",") + 1
            else:
                g = int(gm.group(3))     # iota form [groups, group_size]
        g = g or 1
        if g <= 1 and op != "collective-permute":
            continue                     # degenerate group: no traffic
        if op == "all-reduce":
            wire = 2 * (g - 1) / g * nbytes
        elif op == "all-gather":
            wire = (g - 1) / g * nbytes
        elif op == "reduce-scatter":
            wire = (g - 1) * nbytes
        elif op == "all-to-all":
            wire = (g - 1) / g * nbytes
        else:
            wire = nbytes
        out[op]["count"] += 1
        out[op]["result_bytes"] += nbytes
        out[op]["wire_bytes"] += wire
    return out


# ----------------------------------------------------------------------
def serve_rules(cfg: ModelConfig) -> dict:
    """Serve-shape rule overrides: context-parallel KV cache, plus 2D
    weight sharding when TP-only parameters would blow the 16 GB/chip
    HBM (bf16 params / 16 model shards > 8 GB -> also shard over data;
    XLA inserts per-layer all-gathers, visible in the collective term)."""
    rules = {"cache_seq": "model"}
    if cfg.param_count() * 2 / 16 > 8e9:
        rules["embed"] = "data"
    return rules


def default_overrides(cfg: ModelConfig, kind: str) -> dict:
    """Optimized-default rule overrides (the EXPERIMENTS §5 winners):
    sequence-parallel attention when the head layout cannot shard over the
    16-way model axis. MEASURED decision (EXPERIMENTS §4b/5): a clear win
    for the long-sequence serve shapes (attention-heavy), a regression for
    most 4k TRAIN cells (reshard cost > replication saving) — except
    internvl2-1b, whose collective-bound train cell improves 1.3x."""
    out = {}
    if cfg.n_heads and cfg.n_heads % 16 != 0:
        if kind != "train" or cfg.name == "internvl2-1b":
            out["attn_q_seq"] = "model"
    return out


def run_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
             rules_overrides: Optional[dict] = None,
             n_micro: Optional[int] = None,
             cache_dtype: Optional[str] = None,
             verbose: bool = True) -> Dict:
    cfg = configs.get(arch)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": why}

    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = default_overrides(cfg, shape.kind)
    if shape.kind == "train":
        overrides.update(FSDP_OVERRIDES)
    else:
        overrides.update(serve_rules(cfg))
    if rules_overrides:
        overrides.update(rules_overrides)

    t0 = time.time()
    with sh.axis_rules(mesh, overrides):
        if shape.kind == "train":
            opt_cfg = specs.default_opt(cfg)
            nm = n_micro or specs.default_n_micro(cfg)
            fn, args, ins, outs, donate = specs.train_cell(
                cfg, shape, opt_cfg, n_micro=nm)
        elif shape.kind == "prefill":
            fn, args, ins, outs, donate = specs.prefill_cell(cfg, shape)
        else:
            cdt = jnp.dtype(cache_dtype) if cache_dtype else jnp.bfloat16
            fn, args, ins, outs, donate = specs.decode_cell(
                cfg, shape, cache_dtype=cdt)
        with mesh:
            jitted = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                             donate_argnums=donate)
            lowered = jitted.lower(*args)
            compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = cost_dict(compiled)
    hlo = compiled.as_text()
    coll = parse_collectives(hlo)

    n_dev = mesh.devices.size
    flops_total = float(cost.get("flops", 0.0)) if cost else 0.0
    bytes_total = float(cost.get("bytes accessed", 0.0)) if cost else 0.0
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev,
        "skipped": False,
        "compile_s": round(t_compile, 1),
        "flops_per_device": flops_total,
        "bytes_per_device": bytes_total,
        "collectives": coll,
        "wire_bytes_per_device": sum(v["wire_bytes"] for v in coll.values()),
        "params": cfg.param_count(),
        "active_params": cfg.active_param_count(),
        "tokens": shape.global_batch * (shape.seq_len if shape.kind !=
                                        "decode" else 1),
    }
    if shape.kind == "train":
        rec["n_micro"] = nm
        rec["opt_int8"] = specs.default_opt(cfg).quantize
    if mem is not None:
        for k in ("temp_size_in_bytes", "argument_size_in_bytes",
                  "output_size_in_bytes", "alias_size_in_bytes",
                  "generated_code_size_in_bytes"):
            try:
                rec[k] = int(getattr(mem, k))
            except AttributeError:
                pass
    if verbose:
        peak = (rec.get("argument_size_in_bytes", 0) +
                rec.get("temp_size_in_bytes", 0) +
                rec.get("output_size_in_bytes", 0) -
                rec.get("alias_size_in_bytes", 0))
        print(f"[{rec['mesh']}] {arch} x {shape_name}: compile {t_compile:.0f}s"
              f"  flops/dev {flops_total:.3g}  bytes/dev {bytes_total:.3g}"
              f"  wire/dev {rec['wire_bytes_per_device']:.3g}"
              f"  mem/dev {peak/1e9:.2f} GB", flush=True)
    return rec


# ----------------------------------------------------------------------
# Roofline extraction. XLA's HloCostAnalysis counts while-loop bodies ONCE
# (verified in tests/test_dryrun.py), so the compact scan-based module
# under-reports flops/bytes by the trip counts. This pass lowers depth-1
# and depth-2 UNROLLED variants (layers.unroll_scans) and extrapolates
# linearly in n_groups — exact, because groups are identical — then adds
# the optimizer update (lowered separately) and scales by n_micro.
def _analyze(fn, args, ins, outs, donate, mesh) -> Dict:
    with mesh:
        jitted = jax.jit(fn, in_shardings=ins, out_shardings=outs,
                         donate_argnums=donate)
        compiled = jitted.lower(*args).compile()
    cost = cost_dict(compiled)
    coll = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "wire": sum(v["wire_bytes"] for v in coll.values()),
            "collectives": coll}


def _grad_cell(cfg: ModelConfig, shape: ShapeSpec, n_micro: int):
    """fwd+bwd of ONE microbatch (no accumulation scan, no optimizer)."""
    import dataclasses as dc
    micro = dc.replace(shape, global_batch=shape.global_batch // n_micro)
    params_sds, axes = specs.params_specs(cfg)
    batch_sds = specs.batch_specs(cfg, micro)
    pshard = sh.sharding_tree(axes, params_sds)
    bshard = jax.tree.map(
        lambda x: sh.named_sharding(
            ("batch",) + (None,) * (x.ndim - 1), x.shape), batch_sds)
    from ..models import model as M

    def fn(params, batch):
        return jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, batch, remat=True))(params)
    return fn, (params_sds, batch_sds), (pshard, bshard), \
        (sh.named_sharding(()), pshard), ()


def _opt_cell(cfg: ModelConfig, opt_cfg):
    from ..train.optimizer import adamw_update, opt_state_axes
    params_sds, axes = specs.params_specs(cfg)
    opt_sds = specs.opt_specs(opt_cfg, params_sds)
    pshard = sh.sharding_tree(axes, params_sds)
    oshard = sh.sharding_tree(opt_state_axes(opt_cfg, axes), opt_sds)
    sc = sh.named_sharding(())

    def fn(grads, state, params):
        return adamw_update(opt_cfg, grads, state, params)
    return fn, (params_sds, opt_sds, params_sds), \
        (pshard, oshard, pshard), \
        ((pshard, oshard, {"grad_norm": sc, "lr": sc}), ), ()


def roofline_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
                  rules_overrides: Optional[dict] = None,
                  n_micro: Optional[int] = None,
                  cache_dtype: Optional[str] = None,
                  cfg_overrides: Optional[dict] = None,
                  verbose: bool = True) -> Dict:
    import dataclasses as dc

    from ..models import layers
    cfg_full = configs.get(arch)
    if cfg_overrides:
        cfg_full = dc.replace(cfg_full, **cfg_overrides)
    shape = SHAPES[shape_name]
    ok, why = applicable(cfg_full, shape)
    if not ok:
        return {"arch": arch, "shape": shape_name, "skipped": True,
                "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    overrides = default_overrides(cfg_full, shape.kind)
    if shape.kind == "train":
        overrides.update(FSDP_OVERRIDES)
    else:
        overrides.update(serve_rules(cfg_full))
    if rules_overrides:
        overrides.update(rules_overrides)

    pat = len(cfg_full.block_pattern)
    nm = 1
    if shape.kind == "train":
        nm = n_micro or specs.default_n_micro(cfg_full)

    t0 = time.time()
    per_depth = {}
    with sh.axis_rules(mesh, overrides), layers.unroll_scans():
        for g in (1, 2):
            cfg = dc.replace(cfg_full, n_layers=g * pat)
            if shape.kind == "train":
                cell = _grad_cell(cfg, shape, nm)
            elif shape.kind == "prefill":
                cell = specs.prefill_cell(cfg, shape)
            else:
                cdt = jnp.dtype(cache_dtype) if cache_dtype else jnp.bfloat16
                cell = specs.decode_cell(cfg, shape, cache_dtype=cdt)
            per_depth[g] = _analyze(*cell, mesh)
        opt_cost = {"flops": 0.0, "bytes": 0.0, "wire": 0.0}
        if shape.kind == "train":
            opt_cfg = specs.default_opt(cfg_full)
            fn, args, ins, outs, donate = _opt_cell(cfg_full, opt_cfg)
            opt_cost = _analyze(fn, args, ins, outs[0], donate, mesh)

    n_groups = cfg_full.n_groups
    out = {"arch": arch, "shape": shape_name,
           "mesh": "2x16x16" if multi_pod else "16x16",
           "n_devices": mesh.devices.size, "skipped": False,
           "n_micro": nm, "analysis_s": round(time.time() - t0, 1)}
    for term in ("flops", "bytes", "wire"):
        b = per_depth[2][term] - per_depth[1][term]     # per-group cost
        a = per_depth[1][term] - b                      # fixed cost
        total = a + b * n_groups
        out[term + "_per_device"] = nm * total + opt_cost[term]
        out[term + "_fixed"] = a
        out[term + "_per_group"] = b
        out[term + "_opt"] = opt_cost[term]
    out["collectives_depth2"] = per_depth[2]["collectives"]
    if verbose:
        print(f"[roofline {out['mesh']}] {arch} x {shape_name}: "
              f"flops/dev {out['flops_per_device']:.3g} "
              f"bytes/dev {out['bytes_per_device']:.3g} "
              f"wire/dev {out['wire_per_device']:.3g} "
              f"({out['analysis_s']}s)", flush=True)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=configs.ARCH_NAMES)
    ap.add_argument("--shape", choices=tuple(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="JSON output path")
    ap.add_argument("--n-micro", type=int, default=None)
    ap.add_argument("--cache-dtype", default=None)
    ap.add_argument("--roofline", action="store_true",
                    help="loop-corrected cost extraction instead of the "
                         "full-config compile proof")
    args = ap.parse_args(argv)

    cells = []
    if args.all:
        for a in configs.ARCH_NAMES:
            for s in SHAPES:
                cells.append((a, s))
    else:
        if not (args.arch and args.shape):
            raise SystemExit("dryrun: pass --arch and --shape, or --all")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    records = []
    runner = roofline_cell if args.roofline else run_cell
    for mp in meshes:
        for a, s in cells:
            try:
                rec = runner(a, s, multi_pod=mp, n_micro=args.n_micro,
                             cache_dtype=args.cache_dtype)
            except Exception as e:                      # noqa: BLE001
                rec = {"arch": a, "shape": s,
                       "mesh": "2x16x16" if mp else "16x16",
                       "skipped": False, "error": repr(e)[:500]}
                print(f"FAILED {a} x {s}: {e!r}", file=sys.stderr,
                      flush=True)
            records.append(rec)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(records, f, indent=1)
        print(f"wrote {args.out} ({len(records)} cells)")
    nerr = sum(1 for r in records if r.get("error"))
    return 1 if nerr else 0


if __name__ == "__main__":
    sys.exit(main())
