#!/usr/bin/env python3
"""The readings each cell's correctness limit is set from: the program's
widest gap and its controls', over many seeds, at each cell's own load.

  python3 perfbench/calibrate.py --workloads a,b --seeds 1,2,3 \
      --seconds 8 [--out FILE]

For each seed and configuration the operand is made, and prepared where it
differs from the last seed's (a fixed checkpoint is prepared once); for
each workload on it a short window runs at the cell's own load, and the
requests the check samples are compared with the reference three ways: as
the program served them, and with the reference in a lower precision put
in the program's place (``fp8``, ``bf16``; see ``check.py``), and each
reading is passed through the run's own comparison (``correct_<mode>``).
One JSON line per (seed, workload). The benchmark's own runs never run
this.
"""
import argparse
import gc
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def calibrate(workloads, seeds, seconds, emit, require_chip=True,
              root=None):
    import jax
    import numpy as np
    from perfbench import check, harness
    root = root or harness.ROOT
    cells = [harness.load_cell(w, root) for w in workloads]
    harness.init_jax(max(c.chips for c in cells), require_chip)
    by_config = {}
    for c in cells:
        by_config.setdefault(c.config_name, []).append(c)
    made = {}          # config -> (dense operand, bound plan, Operand)
    for seed in seeds:
        for name, group in by_config.items():
            operand = harness.operand_fn(group[0])
            key_op, key_rhs = jax.random.split(check.seed_key(seed))
            a = np.asarray(operand(key_op))
            prep_s = 0.0
            if name not in made or not np.array_equal(made[name][0], a):
                # A configuration whose operand does not change with the
                # seed (one checkpoint) is prepared once.
                made.pop(name, None)
                bound, op, _, _, _, _, prep_s = harness.prepare(
                    group[0], seed, operand)
                made[name] = (a, bound, op)
            _, bound, op = made[name]
            for cell in group:
                engine, pool = harness.serve(bound, op, cell.traffic,
                                             key_rhs)
                system = harness.System(engine, op, pool, operand, key_op,
                                        {})
                harness.warm(system, cell.traffic)
                sampler = check.Sampler(seed, int(cell.traffic["compare"]))
                win = harness.run_window(system, cell.traffic, seed, seconds,
                                         sampler)
                system.engine = engine = None
                gc.collect()
                line = {"workload": cell.name, "seed": seed,
                        "attempted": win.attempted,
                        "failed": win.attempted - win.completed,
                        "compared": len(sampler.kept)}
                for mode in check.MODES:
                    gaps = harness.compare(system, sampler, mode)
                    line[mode] = max(gaps) if gaps else None
                    # Each reading goes through the run's own comparison.
                    line["correct_" + mode] = harness.is_correct(
                        harness.checks(cell, win, gaps))
                line["limit"] = float(cell.config["check"]["max_rel_err"])
                line["prep_s"] = prep_s
                emit(line)
            bound = None
            gc.collect()


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workloads", required=True)
    p.add_argument("--seeds", required=True)
    p.add_argument("--seconds", type=float, default=8.0)
    p.add_argument("--out", default=None)
    args = p.parse_args(argv)
    out = open(args.out, "a") if args.out else None

    def emit(line):
        s = json.dumps(line)
        print(s, flush=True)
        if out:
            out.write(s + "\n")
            out.flush()

    calibrate(args.workloads.split(","),
              [int(s) for s in args.seeds.split(",")], args.seconds, emit)
    return 0


if __name__ == "__main__":
    sys.exit(main())
