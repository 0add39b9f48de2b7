"""The program's spans in a trace (``bench/spans.py``) and the readers of
the engine's own counters."""
import gzip
from pathlib import Path

import jax
import pytest

from bench import run as bench_run
from bench import spans as sp
from bench import trace_reduce as tr
from bench.cell import load, reader
from bench.readers import device_ms_per, idle_share
from bench.tests import harness

E, S = tr.Event, sp.Span
RECORDED = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb.gz"


def _trace(busy_second_device: bool = False):
    """Window [0, 10]; one pump from 0.5 to 8 whose Definition-1 re-check
    leaves the device idle from 5.1 to the next pump's search at 9.5."""
    dev0 = [E("batch_knn", 1.0, 2.0), E("_apply_wave", 2.3, 4.0),
            E("index_health", 4.6, 4.9), E("_reduce_sum", 5.0, 5.1),
            E("batch_knn", 9.5, 11.0)]
    devices = [dev0] + ([[E("other", 0.0, 10.0)]] if busy_second_device
                        else [])
    bench = [E("bench.window", 0.0, 10.0), E("bench.submit", 0.0, 0.5),
             E("bench.pump", 0.5, 8.0), E("bench.pump", 8.5, 12.0)]
    spans = [S(e.name, e.start, e.end) for e in bench]
    one = [("pump", 0.6, 7.9), ("serve", 0.62, 2.1), ("plan", 0.7, 0.95),
           ("dispatch", 1.0, 2.05), ("drain", 2.1, 4.5),
           ("tape", 2.12, 2.2), ("waves", 2.2, 4.4),
           ("maintain", 4.5, 7.8), ("health", 4.52, 5.0),
           ("recheck", 5.0, 7.5), ("gc", 6.0, 6.5), ("publish", 7.8, 7.9)]
    two = [("pump", 8.6, 11.9), ("serve", 9.4, 10.5),
           ("dispatch", 9.5, 10.4)]
    spans += [S("repro." + n, a, b, {"pump": p})
              for p, steps in ((1, one), (2, two)) for n, a, b in steps]
    return tr.Trace(devices, bench), sorted(spans, key=lambda s: s.start)


def test_gaps_named_by_the_innermost_span_of_either_prefix():
    trace, spans = _trace()
    r = sp.reduce(trace, spans)
    assert r["named_gaps"] == [
        ["repro.recheck after _reduce_sum", pytest.approx(4.4)],
        ["pump after start", pytest.approx(1.0)],
        ["repro.waves after _apply_wave", pytest.approx(0.6)],
        ["repro.tape after batch_knn", pytest.approx(0.3)],
        ["repro.health after index_health", pytest.approx(0.1)]]
    # the gaps' seconds are trace_reduce's, in the same order
    assert [s for _, s in r["idle_gaps"]] == [s for _, s in r["named_gaps"]]


def test_spans_hold_their_host_and_idle_seconds():
    trace, spans = _trace()
    r = sp.reduce(trace, spans)
    assert r["spans"]["repro.recheck"] == {
        "s": pytest.approx(2.5), "n": 1, "idle_s": pytest.approx(2.4)}
    # the second pump is clipped at the window's end
    assert r["spans"]["repro.pump"] == {
        "s": pytest.approx(7.3 + 1.4), "n": 2,
        "idle_s": pytest.approx(4.2 + 0.9)}
    assert r["spans"]["bench.pump"]["n"] == 2
    # idle seconds are a mean over devices
    r2 = sp.reduce(*_trace(busy_second_device=True))
    assert r2["spans"]["repro.recheck"]["idle_s"] == pytest.approx(1.2)


def test_idle_inside_pumps_falls_in_leaf_spans():
    r = sp.reduce(*_trace())
    idle = r["pump_idle"]
    # 4.4 s in the first pump, 1.0 s in the second before its search
    assert idle["idle_s"] == pytest.approx(5.4)
    # plan .25, dispatch .05, tape .08, waves .5, health .18, recheck 1.9,
    # gc .5, publish .1; the rest falls in pump, serve, drain or maintain
    assert idle["leaf_share"] == pytest.approx(3.56 / 5.4)
    assert idle["unnamed_gaps"] == [["pump after start", pytest.approx(1.0)]]
    assert r["spans_per_pump"] == {"max": 11, "mean": 7.0}
    assert r["idle_spans"][0] == ["repro.recheck", pytest.approx(2.4),
                                  {"pump": 1}]


def test_recorded_tpu_trace_reads_as_before(tmp_path):
    """On a trace recorded before the program had spans, every number of
    ``trace_reduce.reduce`` and every reader stays as it was."""
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    before = tr.reduce(tr.load(str(path)))
    after = sp.reduce(*sp.load(str(path)))
    for key, value in before.items():
        assert after[key] == value, key
    assert after["named_gaps"] == before["idle_gaps"]
    assert after["spans"]["bench.pump"]["n"] == 3
    run = {"counters": {"batches_dispatched": 3, "update_drains": 3}}
    for reduced in (before, after):
        assert idle_share({**run, "trace": reduced}) == \
            idle_share({**run, "trace": before})
        assert device_ms_per({**run, "trace": reduced}, ("batch_knn",),
                             "batches_dispatched") == pytest.approx(0.455,
                                                                    rel=1e-3)


COUNTERS = {"query_wait_us": 120_000, "queries_served": 40,
            "update_wait_us": 3_000_000, "updates_applied": 600,
            "plan_us": 9_000, "pumps": 3}


@pytest.mark.parametrize("name, value", [
    ("queue_wait_ms.churn", 3.0), ("update_wait_ms.churn", 5.0),
    ("plan_ms.churn", 3.0)])
def test_counter_readers(name, value):
    read = reader(name).read
    assert read({"counters": COUNTERS}) == pytest.approx(value)
    # a program without the counter, or a part with no events, reads none
    assert read({"counters": {"queries_served": 1, "updates_applied": 1,
                              "pumps": 1}}) is None
    assert read({"counters": {**COUNTERS, "queries_served": 0,
                              "updates_applied": 0, "pumps": 0}}) is None


def test_counter_readers_read_a_tiny_run(tmp_path, monkeypatch):
    monkeypatch.setattr(bench_run, "require_devices",
                        lambda chips: jax.devices()[:chips])
    root = harness.checkout(tmp_path)
    cell = load("tiny.churn", root)
    run = bench_run.run_cell(cell, 2 ** 32 + 7, 3.0, False,
                             jax.devices()[:1], bench_run.CompileClock(),
                             0.0)
    for name in ("queue_wait_ms.churn", "update_wait_ms.churn",
                 "plan_ms.churn"):
        assert cell.readers[name].read(run) > 0, name
