"""Whole runs of tiny cells on the CPU, with the harness's look for a chip
skipped inside these tests only.

The tiny cells, their configurations, traffic mixes and one more metric are
added to a temporary checkout as files and entries alone (``harness.py``):
that they run is the test that the harness is driven by data. The control
and each fault the cells can have must come out not correct.
"""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

from bench import control, oracle
from bench import run as bench_run
from bench.cell import load
from bench.tests import harness

SEED = 2 ** 32 + 12345          # larger than 32 signed bits hold


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return harness.checkout(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_chip_check(monkeypatch):
    monkeypatch.setattr(bench_run, "require_devices",
                        lambda chips: jax.devices()[:chips])


def _run(root, workload, seconds=3.0):
    cell = load(workload, root)
    run = bench_run.run_cell(cell, SEED, seconds, False, jax.devices()[:1],
                             bench_run.CompileClock(), 0.0)
    return cell, run


@pytest.mark.parametrize("workload", sorted(harness.TINY_CELLS))
def test_cells_added_as_files_run(root, workload, capsys):
    rc = bench_run.main(["--workload", workload, "--seed", str(SEED),
                         "--seconds", "3", "--trace", "0"], root=root)
    assert rc == 0
    line = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert line["correct"] is True
    assert list(line)[-1] == "checks"
    assert line["compiles_in_window"] == 0
    names = set(line["metrics"])
    assert {"setup_s", "recall_at_10", "pumps_per_s"} <= names
    assert ("visible_lag_p99_ms" in names) == (workload == "tiny.churn")
    assert line["device"]["count"] == 1
    assert line["attempted"] > 100 and line["failed"] == 0


@pytest.mark.parametrize("workload", sorted(harness.TINY_CELLS))
def test_control_is_not_correct(root, workload):
    cell, run = _run(root, workload)
    limits = cell.config["limits"]
    assert oracle.verdict(run["oracle"], limits)[0]
    numbers = control.control_numbers(run, cell.config)
    correct, checks = oracle.verdict(numbers, limits)
    assert not correct
    assert not checks["answer_dist_rel_err"]["ok"]


def _drain_leaves_state(monkeypatch):
    from repro.serving.update_queue import UpdateScheduler
    drain = UpdateScheduler.drain

    def unchanged(self, index, max_ops=None):
        return index, drain(self, index, max_ops)[1]
    monkeypatch.setattr(UpdateScheduler, "drain", unchanged)


def _wrap_search(monkeypatch, alter):
    from repro.serving.batcher import MicroBatcher
    search = MicroBatcher._default_search

    def wrapped(self, snapshot, Q):
        labels, dists = search(self, snapshot, Q)
        return alter(np.array(labels), np.array(dists))
    monkeypatch.setattr(MicroBatcher, "_default_search", wrapped)


def _half_batch(monkeypatch):
    def alter(labels, dists):
        half = (labels.shape[0] + 1) // 2
        labels[half:], dists[half:] = -1, np.inf
        return labels, dists
    _wrap_search(monkeypatch, alter)


def _answer_altered(monkeypatch):
    def alter(labels, dists):
        labels[:, 0] = np.where(labels[:, 0] >= 0, labels[:, 0] + 1, 0)
        return labels, dists
    _wrap_search(monkeypatch, alter)


def _repair_does_nothing(monkeypatch):
    """``repair_unreachable`` returns the index unchanged, in the engine's
    maintenance consult and in the facade. The tiny cells' windows orphan no
    point, so the orphan their build leaves is the one that stays."""
    import repro.api.facade as facade
    from repro.core import maintenance

    def unchanged(params, index):
        return index
    monkeypatch.setattr(maintenance, "repair_unreachable", unchanged)
    monkeypatch.setattr(facade, "_repair_unreachable", unchanged)


FAULTS = {"state_unchanged": _drain_leaves_state,
          "half_batch_left_out": _half_batch,
          "answer_altered": _answer_altered,
          "repair_does_nothing": _repair_does_nothing}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("workload", sorted(harness.TINY_CELLS))
def test_fault_is_not_correct(root, workload, fault, monkeypatch):
    FAULTS[fault](monkeypatch)
    cell, run = _run(root, workload)
    correct, checks = oracle.verdict(run["oracle"], cell.config["limits"])
    assert not correct
    if fault == "repair_does_nothing":
        assert not checks["unreachable_def1"]["ok"]


def test_split_metrics_share_one_reader(root):
    cell = load("msturing.churn", root)
    for name in ("search_ms.churn", "drain_ms.churn",
                 "device_idle_share.serve"):
        base = name.split(".")[0]
        assert cell.readers[name].__file__.endswith(f"metrics/{base}.py")


def test_answer_clock_stamps_on_the_benchmark_clock(root):
    cell, run = _run(root, "tiny.churn")
    lat = run["query_latency_ms"]
    assert len(lat) == run["attempted"] - len(run["mutation_lag_ms"])
    assert (lat >= 0).all()


def test_no_tpu_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(harness.REPO / "bench" / "run.py"),
                        "--workload", "msturing.churn", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_benchmark_files_alone_print_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files: the
    system under test is missing, so no result."""
    import shutil
    shutil.copy(harness.REPO / "BENCHMARK.json", tmp_path)
    shutil.copytree(harness.REPO / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "msturing.churn", "--seed", "1", "--seconds", "1",
                        "--trace", "0"], cwd=tmp_path, capture_output=True,
                       text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr
