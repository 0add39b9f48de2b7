"""Find the highest mutation rate a cell's engine sustains: one build, one
window per rate.

    python bench/sweep.py --workload msturing.churn --seed 11 --seconds 25 \\
        --rates 60,100,130,160,200

Each rate runs the cell's traffic with the open-loop mutation rate replaced,
on a fresh engine over the same built index. A rate is sustained when the
update backlog does not grow through the window. Prints one JSON line per
rate: ops applied per second, the backlog at each pump, and the query and
visibility tails. Used once, to fix the rate a cell's traffic file states.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

import numpy as np  # noqa: E402

from bench import run as bench_run  # noqa: E402
from bench.cell import load  # noqa: E402
from bench.device import CompileClock, NoChip, require_devices  # noqa: E402
from bench.oracle import Mirror  # noqa: E402
from bench.traffic import make_schedule  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated mutation units per second")
    args = ap.parse_args(argv)
    cell = load(args.workload)
    try:
        require_devices(cell.chips)
    except NoChip as e:
        bench_run.log(f"no result: {e}")
        return 3
    bench_run.enable_compile_cache()
    clock = CompileClock()
    rates = [float(r) for r in args.rates.split(",")]
    traffic = copy.deepcopy(cell.traffic)
    sched = make_schedule(cell.config, traffic, args.seed, args.seconds)
    vi = bench_run.build(cell.config, sched.rows[:sched.loaded], args.seed)
    bench_run.repair_build(vi, cell.config)
    bench_run.warm_up(vi, cell.config, sched)
    bench_run.log(f"setup_s={time.perf_counter() - T_START:.3f}")
    for rate in rates:
        traffic["mutations"]["rate_per_s"] = rate
        sched = make_schedule(cell.config, traffic, args.seed, args.seconds)
        engine = bench_run.serve(vi, cell.config)
        mirror = Mirror(sched.loaded, int(sched.row_label.max()) + 1)
        w = bench_run.drive(engine, sched, mirror, args.seconds, clock)
        pumps = w["pumps"][:w["n_window"]]
        _, latency = bench_run.served(sched, w, int(cell.config["k"]))
        lag = (w["u_pub"] - w["u_due"])[~np.isnan(w["u_pub"])]
        backlog = [p["update_backlog"] for p in pumps]
        half = len(backlog) // 2
        print(json.dumps({
            "rate_units_per_s": rate,
            "applied_ops_per_s": sum(p["applied"] for p in pumps)
            / w["t_close"],
            "pumps": len(pumps),
            "pump_s_mean": float(np.mean([p["end"] - p["start"]
                                          for p in pumps])),
            "backlog": backlog,
            "backlog_growth": float(np.mean(backlog[half:])
                                    - np.mean(backlog[:half]))
            if half else 0.0,
            "query_p50_ms": float(np.nanpercentile(latency, 50) * 1e3),
            "query_p99_ms": float(np.nanpercentile(latency, 99) * 1e3),
            "lag_p50_ms": float(np.percentile(lag, 50) * 1e3),
            "lag_p99_ms": float(np.percentile(lag, 99) * 1e3),
            "compiles_in_window": w["window_programs"]}), flush=True)
        del engine
    return 0


if __name__ == "__main__":
    sys.exit(main())
