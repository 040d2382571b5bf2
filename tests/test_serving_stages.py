"""The serving path's stage spans in a CPU profiler trace, and the tool that
splits a serve cell's time by them (``benchmarks/serving_stages.py``): a
broker flush on the numpy mirror and on the interpreted kernel is tiled by
its stage spans, in order, under one flush id, and ``broker.queued`` ends
where ``broker.pack`` starts; the tool reads span medians, wait medians and
a decision's parts from a trace and ``timing_stats()``."""

import asyncio
import glob
import json
import os
import time

import jax
import numpy as np
import pytest

from bench import harness, load, serve_cell, trace_reduce
from benchmarks import serving_stages
from repro.ml.models import ALL_MODELS
from repro.obs import spans
from repro.obs.metrics import WAIT_EDGES_S, percentile_from_hist
from repro.online.server import AsyncBroker
from repro.online.transport import connect

QUEUED_S = 0.05


def _model():
    rng = np.random.RandomState(0)
    X = rng.rand(300, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.rand(300) > 0.8).astype(np.float32)
    return ALL_MODELS["R.F."]().fit(X, y), X


def _traced(tmp_path, fn):
    """Run ``fn`` under the profiler; the program's spans from the trace as
    ``(start_ns, end_ns, name, flush)``, in start order."""
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    ev = trace_reduce.load(str(tmp_path))
    ours = [(s, e, n) for t, n, s, e in ev["host"]
            if t == "python" and n in spans.SPANS]
    # the flush tag is an event stat, which the loader leaves out
    from jax.profiler import ProfileData
    path = max(glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                         recursive=True), key=os.path.getmtime)
    tags = {(e.name, e.start_ns): dict(e.stats).get("flush")
            for p in ProfileData.from_file(path).planes for line in p.lines
            for e in line.events if e.name in spans.SPANS}
    return sorted((s, e, n, tags[(n, s)]) for s, e, n in ours)


def _one_barrier_flush(impl, X):
    """Two requests of one barrier round, the second ``QUEUED_S`` after the
    first: the round's flush finds the first request queued that long."""
    model, _ = _model()
    with AsyncBroker({"map": model}, impl=impl, policy="barrier") as server:
        addr = server.serve()

        async def round_of_two():
            a, b = await connect(addr), await connect(addr)
            await a.send({"op": "register", "n": 2})
            await a.send({"op": "predict", "id": 1, "kind": "map",
                          "X": X[:3]})
            await asyncio.sleep(QUEUED_S)
            await b.send({"op": "predict", "id": 2, "kind": "map",
                          "X": X[3:4]})
            replies = [await a.recv(), await b.recv()]
            await a.close(), await b.close()
            return replies

        replies = asyncio.run_coroutine_threadsafe(
            round_of_two(), server.loop).result(120)
        timing = server.timing_stats()
    want = np.asarray(model.predict_proba(X[:3]), np.float32)
    assert np.array_equal(replies[0]["probs"][0], want)
    return timing


@pytest.mark.parametrize("impl,stages", [
    ("numpy", ["broker.pack", "forest.numpy", "forest.unpack",
               "broker.reply"]),
    ("interpret", ["broker.pack", "forest.operands", "forest.launch",
                   "forest.fetch", "forest.unpack", "broker.reply"]),
])
def test_stage_spans_tile_one_flush_in_order(tmp_path, impl, stages):
    _, X = _model()
    out = {}
    got = _traced(tmp_path, lambda: out.update(t=_one_barrier_flush(impl, X)))
    assert [n for _, _, n, _ in got] == ["broker.queued"] + stages
    assert {f for *_, f in got} == {1}                 # one flush id
    # disjoint and in order, and no span around the whole flush
    for (_, e0, *_), (s1, *_) in zip(got, got[1:]):
        assert e0 <= s1
    # broker.queued ends where broker.pack starts, and lasts as long as the
    # first request sat in the queue
    (q0, q1, *_), (p0, *_) = got[0], got[1]
    assert p0 - q1 < 5e6
    assert QUEUED_S <= (q1 - q0) * 1e-9 < QUEUED_S + 1.0
    waits = np.asarray(out["t"]["queue_wait"])
    assert waits.sum() == 2
    first = percentile_from_hist(WAIT_EDGES_S, waits, 1.0)
    assert first == pytest.approx((q1 - q0) * 1e-9, rel=0.1)


# ---------------------------------------------------------------------------
# benchmarks/serving_stages.py
# ---------------------------------------------------------------------------

W = trace_reduce.WINDOW_SPAN


def _host():
    """Host events of a window [100, 1000] ns: program spans on the Python
    line, one ending past the window, one on another thread's line."""
    return [("python", W, 100, 1000),
            ("python", "broker.pack", 100, 130),
            ("python", "broker.pack", 300, 360),
            ("python", "broker.pack", 990, 1010),          # ends outside
            ("python", "forest.launch", 130, 230),
            ("python", "broker.reply", 230, 250),
            ("python", "broker.queued", 40, 100),          # starts outside
            ("pjrt-tpu-tasks/1", "broker.pack", 400, 420)]


def test_host_spans_keep_python_spans_inside_the_window():
    got = serving_stages.host_spans(_host(), 100, 1000)
    assert got == {"broker.pack": [pytest.approx(30e-9),
                                   pytest.approx(60e-9)],
                   "forest.launch": [pytest.approx(100e-9)],
                   "broker.reply": [pytest.approx(20e-9)]}
    assert serving_stages.host_spans([], 0, 10) == {}


@pytest.mark.parametrize("wait_s", [2e-5, 4e-3, 0.7])
def test_wait_median_reads_the_histogram(wait_s):
    counts = np.zeros(len(WAIT_EDGES_S) + 1, np.int64)
    counts[np.searchsorted(WAIT_EDGES_S, wait_s)] = 5
    got = serving_stages.wait_p50_ms(counts)
    assert 1e3 * wait_s <= got <= 1.05e3 * wait_s
    assert serving_stages.wait_p50_ms(np.zeros_like(counts)) is None


def _log(sched, sent, done):
    log = load.ClientLog(np.arange(len(sched)), np.asarray(sched))
    log.sent[:], log.done[:] = sent, done
    return log


def test_decompose_splits_a_decision_into_its_parts():
    def hist(wait_s, n):
        counts = np.zeros(len(WAIT_EDGES_S) + 1, np.int64)
        counts[np.searchsorted(WAIT_EDGES_S, wait_s)] = n
        return counts
    zero = hist(1.0, 0)
    timing0 = {k: zero for k in serving_stages.WAITS}
    timing1 = {"hop_in": hist(2e-3, 3), "queue_wait": hist(5e-4, 3),
               "hop_out": hist(3e-3, 3)}
    # sent 1 ms late, answered 10 ms after the scheduled send
    win = {"logs": [_log([1.0, 2.0, 3.0], [1.001, 2.001, 3.001],
                         [1.010, 2.010, 3.010])],
           "deadline": 100.0,
           "flushes": [(4, 1, 1, 2e-7), (4, 1, 1, 2e-7)]}
    events = {"device": {}, "host": _host()}
    got = serving_stages.decompose(win, events, timing0, timing1, serve_cell)
    assert got["stage_p50_ms"] == {"broker.pack": pytest.approx(45e-6),
                                   "forest.launch": pytest.approx(100e-6),
                                   "broker.reply": pytest.approx(20e-6)}
    assert got["stage_counts"] == {"broker.pack": 2, "forest.launch": 1,
                                   "broker.reply": 1}
    # pack + launch over the flush time the observer recorded
    assert got["scoring_over_flush"] == pytest.approx(190e-9 / 4e-7)
    assert got["wait_counts"] == {"hop_in": 3, "queue_wait": 3, "hop_out": 3}
    parts = got["decision_p50_parts_ms"]
    assert parts["send_lag"] == pytest.approx(1.0)
    assert parts["flush"] == pytest.approx(2e-4)
    assert 2.0 <= parts["hop_in"] <= 2.1 and 0.5 <= parts["queue_wait"] <= 0.53
    assert got["decision_p50_ms"] == pytest.approx(10.0)
    assert got["residual_ms"] == pytest.approx(10.0 - got["parts_sum_ms"])
    assert got["idle_gaps"] is None                     # no device events


def test_direct_share_reads_the_window_deltas():
    r0 = {"direct": 10, "task": 4}
    assert serving_stages.direct_share(
        r0, {"direct": 37, "task": 7}) == pytest.approx(27 / 30)
    assert serving_stages.direct_share(r0, dict(r0)) is None


def _smoke_root(tmp_path):
    """A checkout's benchmark files holding one small serve cell."""
    root = harness.ROOT
    cell = {"name": "emr-13-smoke.serve-trickle", "config": "emr-13-smoke",
            "traffic": "serve-trickle", "chips": 1, "why": "test"}
    spec = json.loads((root / "BENCHMARK.json").read_text())
    spec["workloads"] = [cell]
    (tmp_path / "bench" / "configs").mkdir(parents=True)
    (tmp_path / "bench" / "traffic").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(spec))
    config = json.loads((root / "bench/configs/emr-13.json").read_text())
    config.update(name="emr-13-smoke", workload="smoke")
    config["demand"]["sizes"] = {"map": {"1": 6, "13": 1}, "reduce": {"2": 3}}
    (tmp_path / "bench/configs/emr-13-smoke.json").write_text(
        json.dumps(config))
    (tmp_path / "bench/traffic/serve-trickle.json").write_text(json.dumps({
        "driver": "open_loop", "why": "test", "clients": 2,
        "arrivals": {"process": "poisson"}, "rate_rps": 200.0}))
    return tmp_path


def test_traced_smoke_cell_is_split_into_stages(tmp_path, monkeypatch):
    """A traced window of a small cell on the CPU (the numpy mirror): every
    request's waits are counted, the flush's stages are found, and they
    cover the flush time the broker's observer recorded."""
    def device_info(chips):
        d = jax.devices()[0]
        return {"platform": d.platform, "kind": d.device_kind,
                "count": len(jax.devices())}

    monkeypatch.setattr(harness, "device_info", device_info)
    t0 = time.perf_counter()
    out = serving_stages.run("emr-13-smoke.serve-trickle", 2**33 + 9, 0.5,
                             root=_smoke_root(tmp_path))
    assert out["correct"] and time.perf_counter() - t0 < 120
    n = out["wait_counts"]["queue_wait"]
    assert n > 0 and out["wait_counts"] == {"hop_in": n, "queue_wait": n,
                                            "hop_out": n}
    assert {"broker.pack", "forest.numpy", "forest.unpack",
            "broker.reply"} <= set(out["stage_p50_ms"])
    assert out["stage_counts"]["broker.pack"] == out["flushes"]
    assert out["scoring_over_flush"] >= 0.9
    assert all(v is not None and v >= 0
               for v in out["decision_p50_parts_ms"].values())
    assert out["queued_s"] > 0
    # every reply went into its channel inside the flush
    assert out["replies"] == {"direct": n, "task": 0}
    assert out["direct_reply_share"] == 1.0
