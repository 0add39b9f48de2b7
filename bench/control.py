"""The control of ``correct``: the reference, in bfloat16, in the program's
place.

    python bench/control.py --workload msturing.churn --seeds 201,202,203 \\
        --seconds 40

For each seed, one run of the cell as ``run.py`` makes it (the program's
readings), then the same queries answered by exact search computed in
bfloat16, the precision below the float32 the configurations state, over the
live set of the epoch that served each one, judged by the same comparison.
The control has to come out not correct. One JSON line per seed and side.
The benchmark's own runs never run this.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

import numpy as np  # noqa: E402

from bench import oracle  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.cell import load  # noqa: E402
from bench.device import CompileClock, NoChip  # noqa: E402


def bf16_answers(rows: np.ndarray, row_label: np.ndarray,
                 mirror: oracle.Mirror, served: oracle.Served, k: int,
                 block: int = 256) -> oracle.Served:
    """Exact k-NN by squared L2 with every operand and result in bfloat16,
    for the same queries, over the same epochs."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def topk(Q, X, xn, live):
        qn = jnp.sum(Q * Q, axis=1, keepdims=True)
        d = qn + xn[None, :] - 2 * jnp.matmul(
            Q, X.T, preferred_element_type=jnp.bfloat16)
        d = jnp.where(live[None, :], d, jnp.inf)
        neg, idx = jax.lax.top_k(-d, k)
        return idx, -neg

    X = jnp.asarray(rows, jnp.bfloat16)
    xn = jnp.sum(X * X, axis=1)
    labels = np.full_like(served.labels, -1)
    dists = np.full(served.dists.shape, np.inf)
    for e in np.unique(served.epoch[served.epoch >= 0]):
        row_of = mirror.epochs[int(e)]
        live = np.zeros(len(rows), bool)
        live[row_of[row_of >= 0]] = True
        live = jnp.asarray(live)
        sel = np.nonzero(served.epoch == e)[0]
        for i in range(0, len(sel), block):
            s = sel[i:i + block]
            Q = np.zeros((block, rows.shape[1]), np.float32)
            Q[:len(s)] = rows[served.q_row[s]]
            idx, d = topk(jnp.asarray(Q, jnp.bfloat16), X, xn, live)
            labels[s] = row_label[np.asarray(idx)[:len(s)]]
            dists[s] = np.asarray(d.astype(jnp.float32))[:len(s)]
    return oracle.Served(served.q_row, labels, dists, served.epoch,
                         served.expected_epoch)


def control_numbers(run: dict, config: dict) -> dict:
    import importlib
    sched, mirror = run["sched"], run["mirror"]
    k = int(config["k"])
    answers = bf16_answers(sched.rows, sched.row_label, mirror,
                           run["served"], k)
    reference = importlib.import_module(
        f"bench.references.{config['reference']}")
    numbers = oracle.judge(reference, sched.rows, sched.row_label, mirror,
                           answers, run["final"], k)
    numbers["unapplied_mutations"] = run["oracle"]["unapplied_mutations"]
    return numbers


def main(argv=None, root=bench_run.ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0,
                    help="trace the program's run as run.py --trace 1 does "
                         "and print its result line too")
    args = ap.parse_args(argv)
    cell = load(args.workload, root)
    try:
        devs = bench_run.require_devices(cell.chips)
    except NoChip as e:
        bench_run.log(f"no result: {e}")
        return 3
    bench_run.enable_compile_cache(root)
    clock = CompileClock()
    t_start = T_START
    for seed in (int(s) for s in args.seeds.split(",")):
        run = bench_run.run_cell(cell, seed, args.seconds, bool(args.trace),
                                 devs, clock, t_start)
        if args.trace:
            print(json.dumps(bench_run.result(cell, run, True)), flush=True)
        for side, numbers in (("program", run["oracle"]),
                              ("control", control_numbers(run, cell.config))):
            correct, _ = oracle.verdict(numbers, cell.config["limits"])
            print(json.dumps({"seed": seed, "side": side, "correct": correct,
                              **numbers}), flush=True)
        del run
        t_start = time.perf_counter()
    return 0


if __name__ == "__main__":
    sys.exit(main())
