"""The trace reducer: busy union, idle share, time per program, and idle
gaps named by the benchmark's host spans."""
import gzip
from pathlib import Path

import pytest

from bench import trace_reduce as tr
from bench.readers import device_ms_per, idle_share

E = tr.Event
RECORDED = Path(__file__).resolve().parent / "fixtures" / "tiny.xplane.pb.gz"


def _trace():
    # window [0, 10]; programs overlap on device 0; device 1 runs less
    dev0 = [E("batch_knn", 1.0, 2.0), E("_apply_wave", 1.5, 4.0),
            E("index_health", 6.0, 7.0), E("batch_knn", 9.5, 11.0)]
    dev1 = [E("batch_knn", 1.0, 2.0)]
    spans = [E("bench.window", 0.0, 10.0), E("bench.submit", 0.0, 0.5),
             E("bench.pump", 0.5, 8.0), E("bench.pump", 8.5, 12.0)]
    return tr.Trace([dev0, dev1], spans)


def test_busy_union_and_programs():
    r = tr.reduce(_trace())
    assert r["window_s"] == pytest.approx(10.0)
    # device 0: [1, 4] + [6, 7] + [9.5, 10] = 4.5; device 1: 1.0
    assert r["busy_s"] == pytest.approx((4.5 + 1.0) / 2)
    assert r["programs"]["batch_knn"] == pytest.approx(1.0 + 0.5 + 1.0)
    assert r["programs"]["_apply_wave"] == pytest.approx(2.5)
    assert r["device_ops"][0] == ["batch_knn", pytest.approx(2.5)]


def test_gaps_named_by_host_spans():
    r = tr.reduce(_trace())
    # device 1 idles from 2 to 10, mostly inside the first pump; device 0
    # idles from 7 to 9.5 with its midpoint between the two pumps
    assert r["idle_gaps"] == [
        ["pump after batch_knn", pytest.approx(8.0)],
        ["outside after index_health", pytest.approx(2.5)],
        ["pump after _apply_wave", pytest.approx(2.0)],
        ["pump after start", pytest.approx(1.0)],
        ["pump after start", pytest.approx(1.0)]]


def test_readers_on_a_reduced_trace():
    run = {"trace": tr.reduce(_trace()),
           "counters": {"batches_dispatched": 2, "update_drains": 0}}
    assert idle_share(run) == pytest.approx(1 - 2.75 / 10)
    assert device_ms_per(run, ("batch_knn",), "batches_dispatched") == \
        pytest.approx(1250.0)
    assert device_ms_per(run, ("_apply_wave",), "update_drains") is None
    assert device_ms_per(run, ("rebuild_backup",), "batches_dispatched") \
        is None
    assert idle_share({"trace": None}) is None


def test_program_names():
    assert tr.program_name("jit_batch_knn(12)") == "batch_knn"
    assert tr.program_name("jit__apply_wave") == "_apply_wave"


def test_no_device_events_is_an_error():
    with pytest.raises(ValueError):
        tr.reduce(tr.Trace([], [E("bench.window", 0, 1)]))


def test_recorded_tpu_trace(tmp_path):
    """A trace recorded on one TPU v5e: three pumps of a 3,000-point engine,
    each serving 8 queries and draining one delete and one insert; the
    first pump compiles its wave program."""
    path = tmp_path / "tiny.xplane.pb"
    path.write_bytes(gzip.decompress(RECORDED.read_bytes()))
    t = tr.load(str(path))
    assert len(t.devices) == 1
    assert [s.name for s in t.spans].count("bench.pump") == 3
    r = tr.reduce(t)
    assert r["window_s"] == pytest.approx(5.567, abs=1e-3)
    assert 0 < r["busy_s"] < 0.01
    assert {"batch_knn", "_apply_wave", "_apply_deletes_jit"} <= \
        set(r["programs"])
    assert r["programs"]["batch_knn"] == pytest.approx(1.365e-3, rel=1e-3)
    # the longest gap is the first pump's compile, on the host
    assert r["idle_gaps"][0][0].startswith("pump after ")
    assert r["idle_gaps"][0][1] > 5.0
