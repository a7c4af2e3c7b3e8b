#!/usr/bin/env python3
"""Find the highest arrival rate an open-loop cell sustains: one operand,
one engine, a short window at each offered rate in turn.

  python3 perfbench/sweep.py --workload <open-loop cell> --seed <n> \
      --seconds 15 --rates 40,60,80

One JSON line per rate: requests due, served by the close, left at the
close, latency quantiles (drain included) and columns per second retired
inside the window. The benchmark's own runs never run this; the rate a
cell offers is fixed in its traffic file.
"""
import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]


def main(argv=None, require_chip=True):
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, default=15.0)
    p.add_argument("--rates", required=True)
    args = p.parse_args(argv)
    import numpy as np
    from perfbench import check, harness
    cell = harness.load_cell(args.workload)
    harness.init_jax(cell.chips, require_chip)
    system = harness.build(cell, args.seed)
    harness.warm(system, cell.traffic)
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        traffic = dict(cell.traffic, rate_per_s=rate)
        sampler = check.Sampler(args.seed, 1)
        win = harness.run_window(system, traffic, args.seed + i,
                                 args.seconds, sampler)
        lat = np.asarray(win.latencies_s) * 1e3
        print(json.dumps({
            "rate_per_s": rate, "due": win.attempted,
            "served_by_close": win.done_at_close,
            "left_at_close": win.attempted - win.done_at_close,
            "failed": win.attempted - win.completed,
            "drain_s": win.drain_s,
            "cols_per_s": win.cols / win.seconds,
            "waves": len(win.inside()),
            "wave_fill": (100.0 * sum(win.inside())
                          / (len(win.inside()) * win.max_wave_cols)
                          if win.inside() else None),
            "p50_ms": float(np.quantile(lat, .5)) if len(lat) else None,
            "p95_ms": float(np.quantile(lat, .95)) if len(lat) else None,
            "p99_ms": float(np.quantile(lat, .99)) if len(lat) else None,
            "late_p99_ms": float(np.quantile(win.lateness_s, .99)) * 1e3
            if win.lateness_s else None,
            "stalls": len(win.stalls)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
