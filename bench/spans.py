"""The program's own spans in a profiler trace: the device-idle time inside
each one, and idle gaps named by the innermost span of either prefix.

``trace_reduce`` reads the benchmark's ``bench.*`` spans. The serving engine
opens ``repro.*`` spans of its own (``repro.core.common.span``: one per pump,
and one per step of it), on the same clock; this module reads both and
leaves every number of ``trace_reduce.reduce`` as it is.

    python bench/spans.py --out spans-7.json -- \\
        --workload msturing.churn --seed 7 --seconds 40 --trace 1

runs the cell exactly as ``bench/run.py`` does (its result line is the last
line of stdout) and writes the span reduction of its traced part to
``--out``. ``python bench/spans.py TRACE`` reduces a kept trace file or
profiler log directory.
"""
from __future__ import annotations

import argparse
import bisect
import dataclasses
import json
import sys
from pathlib import Path
from unittest import mock

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import trace_reduce  # noqa: E402

PREFIXES = (trace_reduce.SPAN_PREFIX, "repro.")
PUMP_SPAN = "bench.pump"
GC_SPAN = "repro.gc"
#: idle gaps inside a pump shorter than this need no name
NAMED_GAP_S = 1e-3


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    start: float              # seconds
    end: float
    meta: dict = dataclasses.field(default_factory=dict, compare=False)


def load(path: str) -> tuple[trace_reduce.Trace, list[Span]]:
    """The device programs (as ``trace_reduce.load`` reads them) and every
    host span of either prefix, with its metadata."""
    trace = trace_reduce.load(path)
    return trace, load_spans(path)


def load_spans(path: str) -> list[Span]:
    from jax.profiler import ProfileData
    path = Path(path)
    if path.is_dir():
        path = max(path.glob("**/*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    spans = []
    for plane in ProfileData.from_file(str(path)).planes:
        if plane.name.startswith("/host:"):
            spans += [Span(e.name, e.start_ns * 1e-9,
                           (e.start_ns + e.duration_ns) * 1e-9,
                           {k: v for k, v in e.stats})
                      for line in plane.lines for e in line.events
                      if e.name.startswith(PREFIXES)]
    return sorted(spans, key=lambda s: s.start)


def label(name: str) -> str:
    """A gap's name: benchmark spans lose their prefix (as in
    ``trace_reduce``), program spans keep theirs."""
    if name.startswith(trace_reduce.SPAN_PREFIX):
        return name[len(trace_reduce.SPAN_PREFIX):]
    return name


class Innermost:
    """The innermost open span at any time of ``[lo, hi]``: the one opened
    last among those open (spans nest on the thread that opens them)."""

    def __init__(self, spans, lo: float, hi: float):
        edges = sorted({lo, hi} | {t for s in spans for t in (s.start, s.end)
                                   if lo < t < hi})
        self.starts = edges[:-1]
        self.ends = edges[1:]
        self.span = []
        ordered = sorted(spans, key=lambda s: s.start)
        open_, i = [], 0
        for a, b in zip(self.starts, self.ends):
            while i < len(ordered) and ordered[i].start <= a:
                open_.append(ordered[i])
                i += 1
            open_ = [s for s in open_ if s.end > a]
            self.span.append(max(open_, key=lambda s: s.start)
                             if open_ else None)

    def at(self, t: float):
        i = bisect.bisect_right(self.starts, t) - 1
        return self.span[i] if 0 <= i < len(self.span) else None

    def split(self, a: float, b: float):
        """``(span, seconds)`` for each piece of ``[a, b]``."""
        i = max(0, bisect.bisect_right(self.starts, a) - 1)
        while i < len(self.starts) and self.starts[i] < b:
            s, t = max(a, self.starts[i]), min(b, self.ends[i])
            if t > s:
                yield self.span[i], t - s
            i += 1


class Busy:
    """Device-busy seconds inside any interval, from merged intervals."""

    def __init__(self, merged):
        self.starts = [s for s, _ in merged]
        self.ends = [t for _, t in merged]
        self.cum = [0.0]
        for s, t in merged:
            self.cum.append(self.cum[-1] + t - s)

    def _upto(self, x: float) -> float:
        i = bisect.bisect_right(self.starts, x)
        if i == 0:
            return 0.0
        return self.cum[i - 1] + min(x, self.ends[i - 1]) - self.starts[i - 1]

    def within(self, a: float, b: float) -> float:
        return self._upto(b) - self._upto(a)


def reduce(trace: trace_reduce.Trace, spans: list[Span],
           top: int = 10) -> dict:
    """``trace_reduce.reduce``'s keys with the same values, and:

    ``spans``: ``{name: {"s", "n", "idle_s"}}`` per span name inside the
    window: host seconds, count, and device-idle seconds inside it (mean
    over devices); ``named_gaps``: the ``top`` largest idle gaps, named by
    the innermost span of either prefix (``repro.recheck after
    _reduce_sum``); ``pump_idle``: device-idle seconds inside
    ``bench.pump``, the share of them inside a leaf program span (one in
    which no other span but ``repro.gc`` ever opens), and the gaps of
    ``NAMED_GAP_S`` or more there that no program span names;
    ``idle_spans``: the ``top`` leaf spans with the most device-idle
    seconds, with their metadata; ``spans_per_pump``: the most and the
    mean of the program spans a pump opened (``repro.gc`` aside)."""
    out = trace_reduce.reduce(trace, top)
    windows = [s for s in trace.spans if s.name == trace_reduce.WINDOW_SPAN]
    if windows:
        lo, hi = windows[0].start, windows[0].end
    else:
        lo = min(d[0].start for d in trace.devices if d)
        hi = max(max(e.end for e in d) for d in trace.devices if d)
    inside = [s for s in spans if s.end > lo and s.start < hi
              and s.name != trace_reduce.WINDOW_SPAN]
    inner = Innermost(inside, lo, hi)
    parents = set()
    for s in inside:
        p = None if s.name == GC_SPAN else _enclosing(s, inside)
        if p is not None:
            parents.add(p.name)
    program = {s.name for s in inside if s.name.startswith("repro.")}
    leaves = program - parents

    devices = [d for d in trace.devices if d]
    nd = len(devices)
    stats: dict[str, dict] = {}
    gaps, pump_idle, leaf_idle, unnamed = [], 0.0, 0.0, []
    idle_of = [0.0] * len(inside)
    pumps = [s for s in inside if s.name == PUMP_SPAN]
    for events in devices:
        merged = trace_reduce._union(events, lo, hi)
        busy = Busy(merged)
        for i, s in enumerate(inside):
            a, b = max(s.start, lo), min(s.end, hi)
            idle = ((b - a) - busy.within(a, b)) / nd
            st = stats.setdefault(s.name, {"s": 0.0, "n": 0, "idle_s": 0.0})
            st["idle_s"] += idle
            idle_of[i] += idle
        ends = {round(e.end, 9): e.name for e in events}
        prev, last = lo, "start"
        for s, t in merged + [(hi, hi)]:
            if s > prev:
                mid = (prev + s) / 2
                at = inner.at(mid)
                name = label(at.name) if at else "outside"
                gaps.append((f"{name} after {last}", s - prev))
                if s - prev >= NAMED_GAP_S and not name.startswith(
                        "repro.") and any(p.start <= mid < p.end
                                          for p in pumps):
                    unnamed.append([f"{name} after {last}", s - prev])
                for p in pumps:
                    a, b = max(prev, p.start), min(s, p.end)
                    if b <= a:
                        continue
                    pump_idle += (b - a) / nd
                    leaf_idle += sum(x for sp, x in inner.split(a, b)
                                     if sp is not None
                                     and sp.name in leaves) / nd
            prev = t
            last = ends.get(round(t, 9), last)
    for s in inside:
        stats[s.name]["s"] += min(s.end, hi) - max(s.start, lo)
        stats[s.name]["n"] += 1
    gaps.sort(key=lambda g: -g[1])
    out["spans"] = stats
    out["named_gaps"] = [[n, s] for n, s in gaps[:top]]
    out["pump_idle"] = {
        "idle_s": pump_idle,
        "leaf_share": leaf_idle / pump_idle if pump_idle > 0 else None,
        "unnamed_gaps": unnamed}
    per_pump: dict = {}
    for s in inside:
        if s.name in program and s.name != GC_SPAN and "pump" in s.meta:
            per_pump[s.meta["pump"]] = per_pump.get(s.meta["pump"], 0) + 1
    longest = sorted((i for i, s in enumerate(inside) if s.name in leaves),
                     key=lambda i: -idle_of[i])[:top]
    out["idle_spans"] = [[inside[i].name, idle_of[i], inside[i].meta]
                         for i in longest]
    out["spans_per_pump"] = {
        "max": max(per_pump.values()),
        "mean": sum(per_pump.values()) / len(per_pump)} if per_pump else None
    return out


def _enclosing(s: Span, spans) -> Span | None:
    """The innermost other span that holds ``s``."""
    best = None
    for p in spans:
        if p is not s and p.start <= s.start and s.end <= p.end and (
                best is None or p.start >= best.start):
            best = p
    return best


def run_keeping(argv: list[str], out: str) -> int:
    """``bench/run.py``'s ``main`` with its own arguments, and the span
    reduction of the trace it reads, written to ``out`` as JSON."""
    from bench import run
    kept = {}
    load_trace = trace_reduce.load

    def keep(path):
        kept["trace"], kept["spans"] = load_trace(path), load_spans(path)
        return kept["trace"]

    with mock.patch.object(trace_reduce, "load", keep):
        rc = run.main(argv)
    if kept:
        r = reduce(kept["trace"], kept["spans"])
        Path(out).parent.mkdir(parents=True, exist_ok=True)
        Path(out).write_text(json.dumps(
            {k: r[k] for k in ("window_s", "busy_s", "spans", "named_gaps",
                               "pump_idle", "idle_spans",
                               "spans_per_pump")}, indent=1))
        print(f"spans: written to {out}", file=sys.stderr)
    return rc


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    if "--" in argv:
        i = argv.index("--")
        ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
        ap.add_argument("--out", required=True)
        return run_keeping(argv[i + 1:], ap.parse_args(argv[:i]).out)
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace")
    r = reduce(*load(ap.parse_args(argv).trace))
    r.pop("programs")
    print(json.dumps(r, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
