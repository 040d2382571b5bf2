"""The serving path's own instrumentation on the CPU: stage spans inside and
outside a flush, and the always-on wait histograms of
``AsyncBroker.timing_stats()`` (buffered, folded in bulk) beside an
unchanged ``stats()``.  The spans in a profiler trace are tested in
``test_serving_stages.py``."""

import json

import numpy as np
import pytest

from repro.ml.models import ALL_MODELS
from repro.obs import spans
from repro.obs.metrics import (WAIT_EDGES_S, MetricsRegistry, Pending,
                               percentile_from_hist)
from repro.online.server import H_HOP_IN, AsyncBroker, BrokerClient


def _model():
    rng = np.random.RandomState(0)
    X = rng.rand(300, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.rand(300) > 0.8).astype(np.float32)
    return ALL_MODELS["R.F."]().fit(X, y), X


def test_stages_make_spans_only_inside_a_flush():
    spans.stage("forest.numpy")                        # no open flush: no-op
    with pytest.raises(RuntimeError):
        with spans.Stages("broker.pack", flush=9) as st:
            first = st.open
            spans.stage("forest.numpy")
            assert st.open is not None and st.open is not first
            raise RuntimeError("scoring failed")
    assert st.open is None                             # closed on the way out
    spans.stage("forest.unpack")
    assert st.open is None


def test_timing_stats_count_every_hop_and_queue_wait():
    model, X = _model()
    sizes = [1 + i % 4 for i in range(24)]
    with AsyncBroker({"map": model}, policy="vt") as server:
        addrs = [server.serve(), server.serve()]        # two listeners
        clients = [BrokerClient(a, server.loop) for a in addrs]
        try:
            for i, n in enumerate(sizes):
                clients[i % 2].predict("map", X[i:i + n])
        finally:
            for c in clients:
                c.close()
        t = server.timing_stats()
        stats = server.stats()
    N = len(sizes)
    assert t["edges_s"] == WAIT_EDGES_S.tolist()
    for key in ("hop_in", "hop_out", "queue_wait"):
        assert len(t[key]) == len(WAIT_EDGES_S) + 1
        assert sum(t[key]) == N, key
    # one blocking client at a time: every request is a flush of its own,
    # and stats() holds the parent's keys, order and values byte for byte
    want = {"flushes": N, "dispatches": N, "rows": sum(sizes),
            "requests": N, "max_flush_rows": max(sizes), "policy": "vt"}
    assert json.dumps(stats) == json.dumps(want)


def test_tcp_hops_record_nothing():
    model, X = _model()
    with AsyncBroker({"map": model}, policy="vt") as server:
        addr = server.serve("tcp://127.0.0.1:0")
        client = BrokerClient(addr, server.loop)
        try:
            client.predict("map", X[:2])
        finally:
            client.close()
        t = server.timing_stats()
    assert sum(t["hop_in"]) == sum(t["hop_out"]) == 0
    assert sum(t["queue_wait"]) == 1


def test_wait_edges_read_a_median_within_five_percent():
    rng = np.random.default_rng(3)
    for scale in (2e-5, 3e-3, 0.4):
        v = rng.exponential(scale, 2001) + 1e-5
        counts = np.bincount(np.searchsorted(WAIT_EDGES_S, v),
                             minlength=len(WAIT_EDGES_S) + 1)
        got = percentile_from_hist(WAIT_EDGES_S, counts, 0.5)
        assert np.median(v) <= got <= 1.05 * np.median(v)


def test_pending_waits_fold_into_their_histogram():
    reg = MetricsRegistry(ring_capacity=1)
    h = reg.histogram("w", WAIT_EDGES_S)
    reg.freeze()
    ref = MetricsRegistry(ring_capacity=1)
    hr = ref.histogram("w", WAIT_EDGES_S)
    ref.freeze()
    waits = Pending(reg, h)
    vals = np.random.default_rng(5).exponential(3e-3, 500).tolist()
    waits.values.extend(vals)
    waits.fold()
    waits.fold()                                        # nothing left
    for v in vals:
        ref.observe(hr, v)
    assert waits.values == [] and reg.hist_counts[h] == ref.hist_counts[hr]


def test_hops_fold_while_serving(monkeypatch):
    """The channels fold every ``FOLD_AT`` waits, so the buffers stay small
    between reads, and nothing is lost or counted twice."""
    monkeypatch.setattr(Pending, "FOLD_AT", 5)
    model, X = _model()
    with AsyncBroker({"map": model}, policy="vt") as server:
        client = BrokerClient(server.serve(), server.loop)
        try:
            for i in range(23):
                client.predict("map", X[i:i + 1])
        finally:
            client.close()
        assert len(server._hop_in.values) < 5
        assert len(server._queue_wait.values) < 5
        assert sum(server.timing.hist_counts[H_HOP_IN]) >= 20
        t = server.timing_stats()
    assert sum(t["hop_in"]) == sum(t["hop_out"]) == sum(t["queue_wait"]) == 23


def test_pending_fold_from_other_threads_loses_nothing():
    """Appends on one thread (the event loop's) while others fold, as
    ``timing_stats()`` does from the caller's thread: every value is
    counted once."""
    import sys
    import threading

    reg = MetricsRegistry(ring_capacity=1)
    h = reg.histogram("w", WAIT_EDGES_S)
    reg.freeze()
    waits = Pending(reg, h)
    n, done = 60000, threading.Event()

    def append():
        for i in range(n):
            waits.values.append(1e-4 * (1 + i % 50))
        done.set()

    def fold():
        while not done.is_set():
            waits.fold()

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-6)
    try:
        threads = [threading.Thread(target=append)] + [
            threading.Thread(target=fold) for _ in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(old)
    waits.fold()
    assert sum(reg.hist_counts[h]) == n
