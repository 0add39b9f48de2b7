"""A temporary checkout for the benchmark's own tests: the benchmark's files,
the program's sources, and the tiny cells of ``fixtures/`` added as files
and entries only."""
from __future__ import annotations

import json
import shutil
from pathlib import Path

REPO = Path(__file__).resolve().parents[2]
FIXTURES = Path(__file__).resolve().parent / "fixtures"

TINY_CELLS = {
    "tiny.churn": ("tiny-stream", "tiny-churn"),
    "tiny.reembed": ("tiny-mnru", "tiny-reembed"),
}


def checkout(tmp: Path) -> Path:
    """A copy of the benchmark in ``tmp`` with the tiny cells, their
    configurations, traffic and one more metric added as files."""
    root = tmp / "checkout"
    shutil.copytree(REPO / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    (root / "src").symlink_to(REPO / "src")
    bench = json.loads((REPO / "BENCHMARK.json").read_text())
    for cfg in {c for c, _ in TINY_CELLS.values()}:
        shutil.copy(FIXTURES / f"{cfg}.json", root / "bench" / "configs")
        bench["configs"].append({
            "name": cfg, "source": "a CPU-sized stand-in for tests",
            "file": f"bench/configs/{cfg}.json", "reduced": [],
            "why": "tests"})
    for name, (cfg, mix) in TINY_CELLS.items():
        shutil.copy(FIXTURES / f"{mix}.json", root / "bench" / "traffic")
        bench["workloads"].append({"name": name, "config": cfg,
                                   "traffic": mix, "chips": 1,
                                   "why": "tests"})
    shutil.copy(FIXTURES / "pumps_per_s.py", root / "bench" / "metrics")
    names = list(TINY_CELLS)
    for m in bench["end_to_end"]:
        if m["name"] == "visible_lag_p99_ms":
            m["workloads"].append("tiny.churn")
    bench["end_to_end"].append({
        "name": "pumps_per_s", "unit": "1/s", "better": "higher",
        "bound": 0.1, "source": "host_clock", "workloads": names})
    for m in bench["per_layer"]:       # the tiny churn cell reads what
        if "workloads" in m:           # its full-size counterpart reads
            m["workloads"].append("tiny.churn")
    (root / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return root
