"""Benchmark entry point — one harness per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (stdout) and writes JSON to
experiments/results/. Scale with REPRO_BENCH_SCALE / REPRO_FIG*_ITERS.

  PYTHONPATH=src python -m benchmarks.run [--only fig1,fig6,...]
"""
from __future__ import annotations

import argparse
import time

ALL = ("kernels", "fig1", "fig3", "fig6", "fig8", "fig10", "fig12",
       "roofline")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of " + ",".join(ALL))
    args = ap.parse_args()
    wanted = args.only.split(",") if args.only else list(ALL)
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    print("name,us_per_call,derived")
    t_start = time.time()
    for name in wanted:
        t0 = time.time()
        print(f"# === {name} ===", flush=True)
        if name == "kernels":
            from . import kernels_bench
            kernels_bench.run()
        elif name == "fig1":
            from . import fig1_efficiency
            fig1_efficiency.run()
        elif name == "fig3":
            from . import fig3_unreachable
            fig3_unreachable.run()
        elif name == "fig6":
            from . import fig6_update_time
            fig6_update_time.run()
        elif name == "fig8":
            from . import fig8_unreachable_methods
            fig8_unreachable_methods.run()
        elif name == "fig10":
            from . import fig10_recall_after_updates
            fig10_recall_after_updates.run()
        elif name == "fig12":
            from . import fig12_backup
            fig12_backup.run()
        elif name == "roofline":
            from . import roofline
            roofline.run()
        print(f"# {name} done in {time.time() - t0:.1f}s", flush=True)
    print(f"# total {time.time() - t_start:.1f}s")


if __name__ == "__main__":
    main()
