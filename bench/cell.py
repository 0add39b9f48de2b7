"""A cell's files, found by the names in ``BENCHMARK.json``.

``configs/<config>.json`` (through the entry's ``file``),
``traffic/<mix>.json`` and ``metrics/<metric>.py`` for each metric the cell
reports: adding a cell or a metric adds files and entries and edits none.
A metric ``<base>.<part>`` with no file of its own is read by
``metrics/<base>.py``: one quantity split by the end-to-end metric it moves
in each cell (``drain_ms.churn``, ``drain_ms.ingest``) has one reader.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list          # metric entries of BENCHMARK.json
    per_layer: list
    readers: dict             # metric name -> reader module


def _reports(metric: dict, cell: str, e2e: set) -> bool:
    """A per-layer metric with a ``workloads`` list is read in those cells;
    one without, in every cell that reports the metric it ``moves``."""
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric["moves"] in e2e


def reader(name: str, root: Path = ROOT):
    path = root / "bench" / "metrics" / f"{name}.py"
    if not path.exists():
        path = path.with_name(name.split(".")[0] + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load(workload: str, root: Path = ROOT) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    traffic = json.loads(
        (root / "bench" / "traffic" / f"{w['traffic']}.json").read_text())
    e2e = [m for m in bench["end_to_end"]
           if "workloads" not in m or workload in m["workloads"]]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, workload, names)]
    readers = {m["name"]: reader(m["name"], root) for m in e2e + per_layer}
    return Cell(workload, int(w["chips"]), config, traffic, e2e, per_layer,
                readers)
