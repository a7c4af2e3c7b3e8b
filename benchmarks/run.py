"""Benchmark orchestrator: one section per paper table/figure.

Prints ``name,key=value,...`` CSV lines. The roofline section is included
only when the dry-run JSONs exist (they are produced by
``python -m repro.launch.dryrun --all [--roofline]``, which needs the
512-fake-device environment and so runs as its own process).
"""
from __future__ import annotations

import os
import time


def _section(title):
    print(f"# --- {title} ---", flush=True)


def main() -> None:
    t0 = time.time()
    from repro.launch import compile_cache
    compile_cache.enable()
    from . import (fig3_cache_sim, fig4_sweeps, fig5_architectures,
                   kernel_bench, table1_ma_complexity, table2_incrs)
    _section("Table I: MA complexity per format")
    table1_ma_complexity.main()
    _section("Table II: InCRS cost/benefit")
    table2_incrs.main()
    _section("Fig 3: cache-hierarchy ratios (gem5-like)")
    fig3_cache_sim.main()
    _section("Fig 4: resource-matched sweeps vs FPIC")
    fig4_sweeps.main()
    _section("Fig 5 + Table V: three architectures, eight datasets")
    fig5_architectures.main()
    _section("Kernel micro-benchmarks (interpret mode)")
    kernel_bench.main([])
    if os.path.exists("roofline_all.json"):
        _section("Roofline terms per (arch x shape) [paper-faithful baseline]")
        from . import roofline
        roofline.main(["--roofline-json", "roofline_all.json",
                       "--dryrun-json", "dryrun_all.json"])
        if os.path.exists("roofline_opt.json"):
            _section("Roofline terms [beyond-paper optimized defaults]")
            roofline.main(["--roofline-json", "roofline_opt.json",
                           "--dryrun-json", "dryrun_all.json"])
    else:
        print("# roofline_all.json not found - run "
              "`python -m repro.launch.dryrun --all --roofline "
              "--out roofline_all.json` first", flush=True)
    print(f"# total {time.time()-t0:.1f}s")


if __name__ == "__main__":
    main()
