"""The serving engine's host spans and wait counters, read back from the
profiler's own trace (``.xplane.pb``) of a tiny engine."""
import dataclasses
import gc
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro import api
from repro.core import MaintenancePolicy
from repro.core.batch_update import compiled_wave_programs
from repro.core.common import span_tags
from repro.data import clustered_vectors

# a width no other test file builds, so this file's wave programs are new
N, DIM, CAPACITY = 96, 6, 256
PUMPS = 3

#: child span -> the span it runs inside
PARENT = {"serve": "pump", "plan": "serve", "dispatch": "serve",
          "drain": "pump", "tape": "drain", "waves": "drain",
          "wave_compile": "waves", "backup": "pump", "maintain": "pump",
          "health": "maintain", "recheck": "maintain", "repair": "maintain",
          "consolidate": "maintain", "publish": "pump"}


@dataclasses.dataclass
class Span:
    name: str
    start: float
    end: float
    meta: dict
    line: str


def _index_with_orphan(seed: int = 5):
    """A tiny index whose last live point (no test deletes it) has no
    in-edge."""
    X = clustered_vectors(N + 64, DIM, seed=seed)
    vi = api.create(space="l2", dim=DIM, capacity=CAPACITY, M=4, M0=8,
                    ef_construction=32, ef_search=32)
    vi.add_items(X[:N], np.arange(N))
    ix = vi.index
    slot = next(s for s in range(CAPACITY - 1, -1, -1)
                if int(ix.levels[s]) >= 0 and s != int(ix.entry))
    vi._index = dataclasses.replace(
        ix, neighbors=jnp.where(ix.neighbors == slot, -1, ix.neighbors))
    return vi, X


def _engine(vi):
    return vi.serve(k=5, max_batch=8, max_ops_per_drain=64, tau=8,
                    backup_capacity=32, maintenance=MaintenancePolicy())


def _load_spans(trace_dir) -> list[Span]:
    path = sorted(glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True))
    out = []
    for plane in ProfileData.from_file(path[-1]).planes:
        if plane.name.startswith("/host:"):
            out += [Span(e.name[len("repro."):], e.start_ns * 1e-9,
                         (e.start_ns + e.duration_ns) * 1e-9,
                         dict(e.stats), line.name)
                    for line in plane.lines for e in line.events
                    if e.name.startswith("repro.")]
    return sorted(out, key=lambda s: (s.start, -s.end))


def _parent(s: Span, spans: list[Span]):
    """The innermost span on the same thread that encloses ``s``."""
    inside = [p for p in spans if p is not s and p.line == s.line
              and p.start <= s.start and s.end <= p.end and p.name != "gc"]
    return max(inside, key=lambda p: p.start, default=None)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Three pumps under the profiler: each serves 11 queries (two
    batches), the first drains 12 deletes and 10 inserts (and repairs the
    orphan), the second deletes enough to consolidate, the third only
    queries. Stamps around each submit and pump bound the wait counters."""
    vi, X = _index_with_orphan()
    eng = _engine(vi)
    waves0 = compiled_wave_programs()
    trace_dir = tmp_path_factory.mktemp("trace")
    stamps = []
    now = time.perf_counter

    def stamped(call, *args):
        a = now()
        call(*args)
        return a, now()

    jax.profiler.start_trace(str(trace_dir))
    try:
        for p in range(PUMPS):
            q = [stamped(eng.search, X[i]) for i in range(11)]
            u = []
            if p == 0:
                u += [stamped(eng.delete, label) for label in range(12)]
                u += [stamped(eng.insert, X[N + j], 1000 + j)
                      for j in range(10)]
            elif p == 1:
                u += [stamped(eng.delete, label) for label in range(12, 52)]
            p0 = now()
            eng.pump()
            stamps.append({"q": q, "u": u, "p0": p0, "p1": now()})
        with span_tags(pump=99):
            gc.collect()
    finally:
        jax.profiler.stop_trace()
    return {"engine": eng, "stamps": stamps,
            "counters": eng.metrics.to_dict()["counters"],
            "spans": _load_spans(trace_dir),
            "waves_compiled": compiled_wave_programs() - waves0}


def test_every_step_has_its_span_inside_its_pump(traced):
    spans, c = traced["spans"], traced["counters"]
    pumps = [s for s in spans if s.name == "pump"]
    assert [s.meta["pump"] for s in pumps] == list(range(1, PUMPS + 1))
    assert c["pumps"] == PUMPS
    steps = [s for s in spans if s.name not in ("pump", "gc")]
    for s in steps:
        pump = next(p for p in pumps if p.meta["pump"] == s.meta["pump"])
        assert pump.start <= s.start and s.end <= pump.end, s
        assert _parent(s, spans).name == PARENT[s.name], s
    ran = {name: sum(1 for s in steps if s.name == name) for name in PARENT}
    assert ran["serve"] == PUMPS and ran["publish"] == PUMPS
    assert ran["dispatch"] == c["batches_dispatched"] == 2 * PUMPS
    assert ran["plan"] == PUMPS          # a new epoch before every flush
    assert ran["drain"] == ran["tape"] == ran["waves"] == c["update_drains"]
    assert ran["backup"] == c["backup_rebuilds"] >= 1
    assert ran["repair"] == c["maintenance_repair_passes"] >= 1
    assert ran["consolidate"] == c["maintenance_consolidations"] >= 1
    assert ran["maintain"] == ran["health"] >= 2
    # one check after each pass, plus one sweep before the first pass after
    # a consolidation; before a first pass on the consult's own index the
    # health sweep's count is the check
    assert c["maintenance_recheck_sweeps"] == ran["consolidate"]
    assert c["maintenance_mask_reused"] >= 1
    assert ran["recheck"] == ran["repair"] + ran["consolidate"]
    assert ran["wave_compile"] >= 1 and traced["waves_compiled"] >= 1


def test_span_metadata(traced):
    spans = traced["spans"]
    for s in spans:
        if s.name == "dispatch":
            assert s.meta["rows"] in (8, 3) and s.meta["tier"] in (
                "graph", "exact")
            assert s.meta["bucket"] == (8 if s.meta["rows"] == 8 else 4)
        elif s.name == "serve":
            assert s.meta["queries"] == 11
        elif s.name == "drain":
            assert s.meta["ops"] in (22, 40)
        elif s.name == "tape":
            assert s.meta["waves"] >= 1 or s.meta["pump"] == 2
    gcs = [s for s in spans if s.name == "gc" and s.meta.get("pump") == 99]
    assert gcs and gcs[-1].meta["gen"] == 2


def test_wait_counters_agree_with_stamps(traced):
    """Each query waits from its submit to its dispatch, each op from its
    enqueue to its drain: both fall inside the pump that served them."""
    st, c = traced["stamps"], traced["counters"]
    for kind, served, wait in (("q", "queries_served", "query_wait_us"),
                               ("u", "updates_applied", "update_wait_us")):
        assert c[served] == sum(len(p[kind]) for p in st)
        lo = sum(p["p0"] - b for p in st for _, b in p[kind])
        hi = sum(p["p1"] - a for p in st for a, _ in p[kind])
        # the counter rounds once per batch or drain, to the microsecond
        assert lo * 1e6 - 2 * PUMPS <= c[wait] <= hi * 1e6 + 2 * PUMPS


def test_wave_programs_compiled_counts_new_widths_once():
    vi, X = _index_with_orphan(seed=9)
    eng = vi.serve(k=5, max_ops_per_drain=64)
    counter = eng.metrics.counter("wave_programs_compiled")
    # 5 inserts: a wave of a width this process has not compiled at DIM
    for j in range(5):
        eng.insert(X[N + j], 2000 + j)
    n0 = compiled_wave_programs()
    eng.pump()
    assert counter.value == compiled_wave_programs() - n0 >= 1
    first = counter.value
    for j in range(5, 10):
        eng.insert(X[N + j], 2000 + j)
    eng.pump()
    assert counter.value == first      # the same width: nothing compiled


def test_a_pump_opens_few_spans_without_a_session(monkeypatch):
    opened = []

    class Counting(jax.profiler.TraceAnnotation):
        def __init__(self, name, **meta):
            opened.append(name)
            super().__init__(name, **meta)

    vi, X = _index_with_orphan()
    eng = _engine(vi)
    for i in range(40):
        eng.search(X[i])
    for label in range(40):
        eng.delete(label)
    for j in range(20):
        eng.insert(X[N + j], 3000 + j)
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    eng.pump()
    own = [n for n in opened if n != "repro.gc"]
    assert "repro.pump" in own and "repro.maintain" in own
    assert len(own) <= 30
