"""Run one cell of ``BENCHMARK.json`` on the chip and print its result line.

    python bench/run.py --workload msturing.churn --seed 7 --seconds 40 \\
        --trace 0

One process, on the chips the cell asks for. Set-up: the data and the
traffic schedule from ``--seed``, the build through
``repro.api.create(...).add_items``, then a warm-up of the cell's shapes on a
throwaway engine. The window is a host loop: submit every query and mutation
whose due time has passed, ``pump()`` the engine and block on the published
index. Latency is timed from each request's due time to the moment the
benchmark sees the answer in host memory (``AnswerClock``). After the window
closes every request that was due is still served, then the answers and the
final index are compared with the reference (``oracle.judge``). With
``--trace 1`` the window runs under the profiler and the per-layer metrics
are read.

The last line of stdout is the result; the last lines of stderr are the
numbers compared for ``correct``, each beside its limit. Exits 3, printing
no result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import queue  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import numpy as np  # noqa: E402

from bench import cell as cells  # noqa: E402
from bench import oracle, trace_reduce  # noqa: E402
from bench.device import (CompileClock, NoChip, describe,  # noqa: E402
                          require_devices)
from bench.traffic import DELETE, INSERT, make_schedule  # noqa: E402

#: seconds past the close in which a request that was due may still come
GRACE_S = 60.0


def log(*parts) -> None:
    print(*parts, file=sys.stderr, flush=True)


def enable_compile_cache(root: Path = ROOT) -> str:
    """JAX's persistent cache at ``.jax_cache`` in the checkout, whatever
    the environment names: a fixed path (the path is part of each entry's
    key, through the source locations in the programs) with no size limit,
    so that every run of a cell after the first finds all its programs."""
    import jax
    path = str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_compilation_cache_max_size", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------

def build(config: dict, X: np.ndarray, seed: int):
    from repro import api
    ix = config["index"]
    vi = api.create(space=config["space"], dim=int(config["dim"]),
                    capacity=int(config["capacity"]), M=ix["M"], M0=ix["M0"],
                    num_layers=ix["num_layers"],
                    ef_construction=ix["ef_construction"],
                    ef_search=ix["ef_search"], strategy=ix["strategy"],
                    seed=seed % 2 ** 31)
    vi.add_items(X, np.arange(len(X)))
    return vi


def repair_build(vi, config: dict) -> None:
    """Where the engine runs a maintenance policy, repair the points the
    bulk build left unreachable before serving: the policy's first consult
    would otherwise spend its first pump on them (a one-off of the load,
    not of the churn)."""
    import jax
    if config["engine"].get("maintenance") is not None:
        vi.repair_unreachable()
        jax.block_until_ready(vi.index)


def serve(vi, config: dict):
    from repro import api
    eng = config["engine"]
    policy = eng.get("maintenance")
    return vi.serve(
        k=int(config["k"]), ef=int(config["index"]["ef_search"]),
        max_batch=int(eng["max_batch"]),
        max_ops_per_drain=int(eng["max_ops_per_drain"]),
        tau=int(eng.get("tau", 0)),
        backup_capacity=int(eng.get("backup_capacity", 0)),
        maintenance=None if policy is None else api.MaintenancePolicy(
            **policy))


def push(engine, sched, u: int) -> None:
    for kind, label, row in sched.unit_ops[u]:
        if kind == DELETE:
            engine.delete(label)
        elif kind == INSERT:
            engine.insert(sched.rows[row], label)
        else:
            engine.update(sched.rows[row], label)


def pump_published(engine):
    import jax
    stats = engine.pump()
    jax.block_until_ready(engine.snapshot().index)
    return stats


def warm_up(vi, config: dict, sched) -> None:
    """Compile every program the window can call, on a throwaway engine:
    each query bucket, each power-of-two drain of the mix's units, and the
    maintenance and backup programs the engine's policy may run."""
    import jax
    import jax.numpy as jnp
    engine = serve(vi, config)
    q = sched.rows[sched.q_row[0]] if len(sched.q_row) else sched.rows[0]
    b = 1
    while b <= engine.batcher.max_batch:
        for _ in range(b):
            engine.search(q)
        pump_published(engine)
        b *= 2
    nu = len(sched.unit_ops)
    if nu and sched.ops_per_cycle:
        drain = engine.scheduler.max_ops_per_drain
        top = 1 << max(0, -(-drain // sched.ops_per_cycle) - 1).bit_length()
        u, s = 0, 1
        while s <= top:
            for _ in range(s * sched.pattern_len):
                push(engine, sched, u % nu)
                u += 1
            while engine.update_backlog:
                pump_published(engine)
            s *= 2
    index = engine.snapshot().index
    eng = config["engine"]
    if eng.get("maintenance") is not None:
        from repro.core.maintenance import repair_unreachable
        from repro.core.reach import indegree_unreachable
        jax.block_until_ready(repair_unreachable(engine.params, index))
        int(jnp.sum(indegree_unreachable(index)))
    if int(eng.get("tau", 0)) > 0 and int(eng.get("backup_capacity", 0)) > 0:
        from repro.core.backup import rebuild_backup
        jax.block_until_ready(rebuild_backup(
            engine.params, index, int(eng["backup_capacity"]),
            jnp.uint32(1)))
    del engine, index
    gc.collect()


# ---------------------------------------------------------------------------
# the measured window
# ---------------------------------------------------------------------------

def annotate(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


class AnswerClock:
    """When each query's answer reached host memory, on the benchmark's
    clock. A thread watches the open tickets, also while the loop is inside
    ``pump()``: the engine serves queued queries before it drains and
    maintains, so a stamp taken after ``pump()`` returns would charge each
    answer with the rest of its pump. A ticket counts once it is done and
    its labels and distances are host arrays. The engine serves tickets in
    the order they came, so only the oldest open one is watched: were one
    served out of order, its stamp would come late, never early."""

    POLL_S = 1e-3

    def __init__(self, n: int, t0: float):
        self.finish = np.full(n, np.nan)
        self._t0 = t0
        self._new: queue.SimpleQueue = queue.SimpleQueue()
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._watch, daemon=True)
        self._thread.start()

    def add(self, i: int, ticket) -> None:
        self._new.put((i, ticket))

    def _watch(self) -> None:
        pending: collections.deque = collections.deque()
        while True:
            stopping = self._stop.is_set()
            while not self._new.empty():
                pending.append(self._new.get())
            while pending and (pending[0][1].done or stopping):
                i, t = pending.popleft()
                if t.done:
                    np.asarray(t.labels), np.asarray(t.dists)
                    self.finish[i] = time.perf_counter() - self._t0
            if stopping:
                return
            time.sleep(self.POLL_S)

    def close(self) -> np.ndarray:
        self._stop.set()
        self._thread.join()
        return self.finish


def drive(engine, sched, mirror: oracle.Mirror, seconds: float,
          clock: CompileClock, trace_dir: str | None = None) -> dict:
    """The window, then every request that was due in it; returns what
    happened to each request and each pump.

    With ``trace_dir`` the second quarter of the window runs under the
    profiler, inside the ``bench.window`` span, and ``counters`` are the
    engine's counters over that part; else over the whole window. Stopping
    the profiler stalls the host (it writes the trace), so only a part is
    traced and the rest of the window is for the comparison alone."""
    import jax
    now = time.perf_counter
    nq, nu = len(sched.q_due), len(sched.unit_ops)
    closed = sched.closed_backlog > 0
    q_sub = np.full(nq, np.nan)
    q_pump = np.full(nq, -1)
    tickets = [None] * nq
    u_due = np.full(nu, np.nan)
    u_pub = np.full(nu, np.nan)
    u_left = np.array([len(ops) for ops in sched.unit_ops], int)
    pumps: list[dict] = []
    state = {"q": 0, "u": 0}
    part = (seconds / 4, seconds / 2) if trace_dir else None
    traced = {"on": False, "span": None, "c0": None, "c1": None}

    def counters() -> dict:
        return dict(engine.metrics.to_dict()["counters"])

    def trace_edge(t: float) -> None:
        if part is None or traced["c1"] is not None:
            return
        if not traced["on"] and t >= part[0]:
            jax.profiler.start_trace(trace_dir,
                                     profiler_options=_trace_options())
            traced["span"] = annotate("bench.window")
            traced["span"].__enter__()
            traced["c0"], traced["on"] = counters(), True
        elif traced["on"] and t >= part[1]:
            traced["span"].__exit__(None, None, None)
            traced["c1"], traced["on"] = counters(), False
            jax.profiler.stop_trace()

    t0 = now()
    answers = AnswerClock(nq, t0)

    def submit(until: float, open_job: bool) -> None:
        with annotate("bench.submit"):
            while state["q"] < nq and sched.q_due[state["q"]] <= until:
                i = state["q"]
                q_sub[i] = now() - t0
                tickets[i] = engine.search(sched.rows[sched.q_row[i]])
                answers.add(i, tickets[i])
                q_pump[i] = len(pumps)
                state["q"] += 1
            while (not closed and state["u"] < nu
                   and sched.unit_due[state["u"]] <= until) or (
                    closed and open_job
                    and engine.update_backlog < sched.closed_backlog):
                u = state["u"]
                if u >= nu:
                    raise RuntimeError(f"the closed-loop job ran out of its "
                                       f"{nu} units")
                u_due[u] = sched.unit_due[u] if not closed else now() - t0
                push(engine, sched, u)
                mirror.submit(sched.unit_ops[u], u)
                state["u"] += 1

    def pump() -> None:
        e0, ts = engine.epoch, now() - t0
        with annotate("bench.pump"):
            stats = pump_published(engine)
        te = now() - t0
        after = counters()
        did = {k: v - last.get(k, 0) for k, v in after.items()
               if v != last.get(k, 0)}
        last.update(after)
        for u in mirror.drained(stats.updates_applied):
            u_left[u] -= 1
            if u_left[u] == 0:
                u_pub[u] = te
        if engine.epoch != e0:
            mirror.published(engine.epoch)
        pumps.append({"start": ts, "end": te, "epoch": e0,
                      "applied": stats.updates_applied,
                      "update_backlog": engine.update_backlog,
                      "counts": did})

    c_start = counters()
    last = dict(c_start)
    programs0 = clock.compiles
    while (t := now() - t0) < seconds:
        trace_edge(t)
        submit(t, True)
        if engine.query_backlog or engine.update_backlog:
            pump()
            continue
        nxt = [seconds]
        if state["q"] < nq:
            nxt.append(sched.q_due[state["q"]])
        if not closed and state["u"] < nu:
            nxt.append(sched.unit_due[state["u"]])
        if part is not None and traced["c1"] is None:
            nxt.append(part[0] if not traced["on"] else part[1])
        time.sleep(max(0.0, min(nxt) - (now() - t0)))
    t_close = now() - t0
    window_programs = clock.compiles - programs0
    if traced["on"]:
        trace_edge(float("inf"))
    c0, c1 = ((traced["c0"], traced["c1"]) if part is not None
              else (c_start, counters()))
    n_window = len(pumps)
    # every request due in the window is still served; a closed-loop job's
    # queued ops were never due and stay queued
    submit(seconds, False)
    resumed = now() - t0
    while (engine.query_backlog or (not closed and engine.update_backlog)) \
            and now() - t0 < resumed + GRACE_S:
        pump()
    return {"t_close": t_close, "pumps": pumps, "n_window": n_window,
            "tickets": tickets, "q_sub": q_sub, "q_pump": q_pump,
            "q_finish": answers.close(),
            "u_due": u_due, "u_pub": u_pub, "closed": closed,
            "window_programs": window_programs,
            "counters": {k: v - (c0 or {}).get(k, 0)
                         for k, v in (c1 or {}).items()}}


def served(sched, w: dict, k: int) -> tuple[oracle.Served, np.ndarray]:
    """The answers, and each query's latency from its due time to the
    benchmark's stamp of its answer (NaN where none came)."""
    nq = len(sched.q_due)
    labels = np.full((nq, k), -1, np.int64)
    dists = np.full((nq, k), np.inf)
    epoch = np.full(nq, -1)
    expected = np.full(nq, -1)
    latency = np.full(nq, np.nan)
    for i, t in enumerate(w["tickets"]):
        if t is None or not t.done:
            continue
        lab, dist = t.result()
        labels[i], dists[i], epoch[i] = lab, dist, t.epoch
        expected[i] = w["pumps"][w["q_pump"][i]]["epoch"]
        latency[i] = w["q_finish"][i] - sched.q_due[i]
    return oracle.Served(sched.q_row, labels, dists, epoch, expected), latency


def host_index(engine) -> dict:
    ix = engine.snapshot().index
    return {f: np.asarray(getattr(ix, f)) for f in
            ("labels", "levels", "deleted", "vectors", "neighbors", "entry")}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run_cell(cell: cells.Cell, seed: int, seconds: float, trace: bool,
             devs: list, clock: CompileClock, t_start: float) -> dict:
    import importlib
    config = cell.config
    t = time.perf_counter()
    sched = make_schedule(config, cell.traffic, seed, seconds)
    data_s = time.perf_counter() - t
    t = time.perf_counter()
    vi = build(config, sched.rows[:sched.loaded], seed)
    build_s = time.perf_counter() - t
    t = time.perf_counter()
    repair_build(vi, config)
    repair_s = time.perf_counter() - t
    t = time.perf_counter()
    warm_up(vi, config, sched)
    warm_s = time.perf_counter() - t
    engine = serve(vi, config)
    del vi
    mirror = oracle.Mirror(sched.loaded, int(sched.row_label.max()) + 1)
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    setup_s = time.perf_counter() - t_start
    log(f"setup: setup_s={setup_s:.3f} data_s={data_s:.3f} "
        f"build_s={build_s:.3f} repair_s={repair_s:.3f} "
        f"warm_up_s={warm_s:.3f} compile_or_load_s={clock.compile_s:.3f} "
        f"programs={clock.compiles} cache_loads={clock.cache_loads}")
    w = drive(engine, sched, mirror, seconds, clock, trace_dir)
    reduced = None
    pumps = w["pumps"][:w["n_window"]]
    log(f"window: seconds={w['t_close']:.3f} pumps={w['n_window']} "
        f"compiles_in_window={w['window_programs']} "
        f"after_close_pumps={len(w['pumps']) - w['n_window']}")
    log(pump_log(pumps))
    t = time.perf_counter()
    device = describe(devs)
    final = host_index(engine)
    del engine
    gc.collect()
    served_, latency = served(sched, w, int(config["k"]))
    reference = importlib.import_module(
        f"bench.references.{config['reference']}")
    with annotate("bench.reference"):
        numbers = oracle.judge(reference, sched.rows, sched.row_label,
                               mirror, served_, final, int(config["k"]))
    nq = len(sched.q_due)
    # open loop: every unit due in the window; closed loop: those drained
    opened = ~np.isnan(w["u_pub"] if w["closed"] else w["u_due"])
    unapplied = int((opened & np.isnan(w["u_pub"])).sum())
    numbers["unapplied_mutations"] = unapplied
    log(f"after the window: pumps_s="
        f"{max(0.0, w['pumps'][-1]['end'] - w['t_close']):.3f}"
        f" check_s={time.perf_counter() - t:.3f}" if w["pumps"] else
        "after the window: no pumps")
    program = np.array([w["q_sub"][i] + t.latency_s if t is not None
                        and t.done else np.nan
                        for i, t in enumerate(w["tickets"])])
    behind = (w["q_finish"] - program) * 1e3
    if nq and not np.isnan(behind).all():
        log(f"answer clock: behind the program's own stamp "
            f"p50_ms={np.nanmedian(behind):.3f} "
            f"max_ms={np.nanmax(behind):.3f}")
    late = w["q_sub"] - sched.q_due
    log(f"generator: query_late_p99_ms={np.nanpercentile(late, 99) * 1e3:.3f}"
        f" query_late_max_ms={np.nanmax(late) * 1e3:.3f}" if nq else
        "generator: no queries")
    if trace:
        reduced = trace_reduce.reduce(trace_reduce.load(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        device.update(busy_s=reduced["busy_s"], window_s=reduced["window_s"])
    lag = (w["u_pub"] - w["u_due"])[opened & ~np.isnan(w["u_pub"])]
    return {
        "setup_s": setup_s, "window_s": w["t_close"],
        "query_latency_ms": latency[~np.isnan(latency)] * 1e3,
        "mutation_lag_ms": lag * 1e3,
        "applied_in_window": sum(p["applied"] for p in pumps),
        "pumps": w["pumps"], "n_window": w["n_window"],
        "window_programs": w["window_programs"],
        "counters": w["counters"], "trace": reduced, "final": final,
        "oracle": numbers, "device": device, "sched": sched,
        "mirror": mirror, "served": served_,
        "attempted": nq + int(opened.sum()),
        "failed": numbers["unanswered"] + unapplied,
    }


def pump_log(pumps: list) -> str:
    """The window's pump times, the repair passes its pumps ran, and the
    three longest pumps with the engine's counts of what each ran."""
    if not pumps:
        return "pumps: none"
    secs = np.array([p["end"] - p["start"] for p in pumps])
    passes: dict = {}
    for p in pumps:
        n = p["counts"].get("maintenance_repair_passes", 0)
        passes[n] = passes.get(n, 0) + 1
    top = [f"{secs[i]:.3f}s@{pumps[i]['start']:.1f} applied="
           f"{pumps[i]['applied']} {pumps[i]['counts']}"
           for i in np.argsort(secs)[::-1][:3]]
    return (f"pumps: p50_s={np.median(secs):.3f} max_s={secs.max():.3f} "
            f"repair_passes_per_pump={dict(sorted(passes.items()))} "
            f"longest: " + " | ".join(top))


def _trace_options():
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    return opts


def metrics_of(cell: cells.Cell, run: dict, trace: bool) -> dict:
    out = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        v = cell.readers[m["name"]].read(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def result(cell: cells.Cell, run: dict, trace: bool) -> dict:
    correct, checks = oracle.verdict(run["oracle"], cell.config["limits"])
    line = {"correct": correct, "attempted": run["attempted"],
            "failed": run["failed"], "metrics": metrics_of(cell, run, trace),
            "device": run["device"],
            "compiles_in_window": run["window_programs"]}
    if trace:
        line["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                             "idle_gaps": run["trace"]["idle_gaps"]}
    line["checks"] = checks
    return line


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None, root: Path = ROOT, t_start: float = T_START) -> int:
    args = parse(argv)
    cell = cells.load(args.workload, root)
    import repro.api  # noqa: F401  the system under test, before any work
    try:
        devs = require_devices(cell.chips)
    except NoChip as e:
        log(f"no result: {e}")
        return 3
    enable_compile_cache(root)
    clock = CompileClock()
    run = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs,
                   clock, t_start)
    line = result(cell, run, bool(args.trace))
    log(f"total_s={time.perf_counter() - t_start:.3f}")
    for name, c in line["checks"].items():
        log(f"check {name} = {c['value']} limit {c['limit']} "
            f"{'ok' if c['ok'] else 'FAIL'}")
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
