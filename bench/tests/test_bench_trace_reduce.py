"""The trace reduction: pure functions over event lists, and the loader on a
small trace recorded on a TPU v5e."""

import gzip
import pathlib
import re

import pytest

from bench import harness, trace_reduce

DATA = pathlib.Path(__file__).resolve().parent / "data"


def _events():
    dev = [("fusion", 100, 200), ("jit_grouped_call_kernel", 250, 450),
           ("fusion", 400, 450), ("copy", 900, 1000), ("outside", 5000, 6000)]
    host = [("python", trace_reduce.WINDOW_SPAN, 0, 1000),
            ("python", "PjitFunction(grouped_call)", 200, 260),
            ("pjrt-tpu-tasks/1", "Transpose", 0, 1000),
            ("python", "client.send", 520, 880)]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_union_merges_overlaps():
    assert trace_reduce.union_ns([(0, 10), (5, 20), (30, 40)]) == 30
    assert trace_reduce.union_ns([]) == 0


def test_reduce_busy_window_kernel_and_gaps():
    r = trace_reduce.reduce(_events(), re.compile("grouped_call"), top=3)
    assert r["window_s"] == pytest.approx(1000e-9)
    # union of [100,200] [250,450] [900,1000] inside the window
    assert r["busy_s"] == pytest.approx(400e-9)
    assert r["kernel_s"] == pytest.approx(200e-9)
    assert r["device_ops"][0] == ["jit_grouped_call_kernel",
                                  pytest.approx(200e-9)]
    # longest gap first, named by the host span covering most of it
    assert r["idle_gaps"][0] == ["client.send", pytest.approx(450e-9)]
    assert r["idle_gaps"][1] == ["no host span", pytest.approx(100e-9)]


def test_reduce_finds_nothing_without_window_or_device():
    ev = _events()
    assert trace_reduce.reduce({"device": ev["device"], "host": []}) is None
    assert trace_reduce.reduce({"device": {}, "host": ev["host"]}) is None


def test_recorded_v5e_trace(tmp_path):
    # a quarter-second traced window of hadoop-1000.serve-bursty on one
    # TPU v5e: 102 flushes, each one launch of the grouped kernel
    prof = tmp_path / "plugins" / "profile" / "run"
    prof.mkdir(parents=True)
    (prof / "host.xplane.pb").write_bytes(
        gzip.decompress((DATA / "serve_v5e.xplane.pb.gz").read_bytes()))
    ev = trace_reduce.load(str(tmp_path))
    assert list(ev["device"]) == ["/device:TPU:0"]
    r = trace_reduce.reduce(ev, harness.KERNEL_EVENT)
    assert r["devices"] == 1
    assert r["window_s"] == pytest.approx(0.303190123, rel=1e-6)
    assert r["busy_s"] == pytest.approx(0.000336762, rel=1e-6)
    assert r["kernel_s"] == pytest.approx(0.000153302, rel=1e-6)
    assert 0 < r["kernel_s"] < r["busy_s"] < r["window_s"]
    names = [n for n, _ in r["device_ops"]]
    assert "%grouped_call.1" in names and all(" = " not in n for n in names)
    assert len(r["idle_gaps"]) == 10


def test_machine_pauses_name_the_gaps_they_cover():
    # the window span began at monotonic 50.0 s; a pause from 50.0000004 s
    # for 550 ns covers the gap [450, 900] better than client.send does
    ev = trace_reduce.with_pauses(_events(), [(50.0000004, 550e-9)], 50.0)
    r = trace_reduce.reduce(ev, top=3)
    assert r["idle_gaps"][0] == [trace_reduce.MACHINE_PAUSE,
                                 pytest.approx(450e-9)]
    assert trace_reduce.with_pauses(_events(), [], 50.0) == _events()
