"""The broker's reply path: a flush puts its replies on the in-process
channels before it returns, with no asyncio Task per reply, and falls back
to a Task where a comm cannot send without suspending (no
``send_nowait``, a fault-wrapped comm, a full channel), keeping each comm's
replies in order.  Counts and order only: no wall-clock thresholds."""

import asyncio
import time

import numpy as np
import pytest

from repro.ml.models import ALL_MODELS
from repro.online.faults import FaultPlan
from repro.online.server import AsyncBroker, BrokerClient, _Req
from repro.online.transport import InProcComm, _Channel, connect


def _model():
    rng = np.random.RandomState(0)
    X = rng.rand(300, 6).astype(np.float32)
    y = (X[:, 0] + 0.3 * rng.rand(300) > 0.8).astype(np.float32)
    return ALL_MODELS["R.F."]().fit(X, y), X


def _on_loop(server, coro, timeout=60):
    return asyncio.run_coroutine_threadsafe(coro, server.loop).result(timeout)


def _pair(capacity=8):
    """A connected inproc pair ``(broker side, client side)``."""
    up, down = _Channel(8), _Channel(capacity)
    return (InProcComm(up, down, "inproc://broker", "inproc://client"),
            InProcComm(down, up, "inproc://client", "inproc://broker"))


async def _burst(addr, clients, per_client, X, comms):
    """Each client sends ``per_client`` requests without reading (inproc
    sends do not suspend below capacity), then reads every reply."""
    comms.extend([await connect(addr) for _ in range(clients)])
    for c, comm in enumerate(comms):
        for i in range(per_client):
            lo = 2 * (c * per_client + i)
            await comm.send({"op": "predict", "id": i, "kind": "map",
                             "X": X[lo:lo + 2]})
    replies = [[await comm.recv() for _ in range(per_client)]
               for comm in comms]
    for comm in comms:
        await comm.close()
    return replies


def _check_replies(model, X, replies, per_client):
    for c, got in enumerate(replies):
        assert [r["id"] for r in got] == list(range(per_client))
        for i, r in enumerate(got):
            lo = 2 * (c * per_client + i)
            want = np.asarray(model.predict_proba(X[lo:lo + 2]), np.float32)
            assert np.array_equal(r["probs"][0], want)


def test_a_flush_leaves_its_replies_in_the_client_channels():
    """When ``_flush`` returns, every reply it made is already in its
    client's channel, and the flush made no asyncio Task for them."""
    model, X = _model()
    clients, per_client = 3, 4
    comms: list = []
    seen = []
    with AsyncBroker({"map": model}, policy="vt") as server:
        addr = server.serve()
        flush = server._flush

        def traced_flush(cause):
            n = len(server._queue)
            held = sum(len(c._rx.q) for c in comms)
            tasks = asyncio.all_tasks()
            flush(cause)
            seen.append((n, sum(len(c._rx.q) for c in comms) - held,
                         len(asyncio.all_tasks() - tasks)))

        server._flush = traced_flush
        replies = _on_loop(server, _burst(addr, clients, per_client, X,
                                          comms))
        hop_out = sum(server.timing_stats()["hop_out"])
    _check_replies(model, X, replies, per_client)
    assert sum(n for n, _, _ in seen) == clients * per_client
    assert all(queued == n and new_tasks == 0 for n, queued, new_tasks in seen)
    assert server.n_direct_replies == clients * per_client == hop_out
    assert server.n_task_replies == 0


def test_hop_out_counts_direct_replies():
    """A blocking ``BrokerClient`` (the fleet's async executor's client):
    every reply goes in directly, and ``hop_out`` times each one."""
    model, X = _model()
    with AsyncBroker({"map": model}, policy="vt") as server:
        client = BrokerClient(server.serve(), server.loop)
        try:
            for i in range(9):
                client.predict("map", X[i:i + 1])
        finally:
            client.close()
        t = server.timing_stats()
    assert server.n_direct_replies == sum(t["hop_out"]) == 9
    assert server.n_task_replies == 0


def test_a_comm_without_send_nowait_gets_its_replies_by_task():
    model, X = _model()

    class FakeComm:
        closed = False

        def __init__(self):
            self.sent = []

        async def send(self, msg):
            self.sent.append(msg)

    comm = FakeComm()
    server = AsyncBroker(policy="vt").start()
    try:
        def park_and_flush():
            for i in range(5):
                server._queue.append(_Req(comm, i, [(model, X[i:i + 1])], 1,
                                          i + 1, None))
            server._queued_rows = 5
            server._flush("idle")

        server.loop.call_soon_threadsafe(park_and_flush)
        deadline = time.time() + 10
        while len(comm.sent) < 5 and time.time() < deadline:
            time.sleep(0.01)
    finally:
        server.stop()
    assert [m["id"] for m in comm.sent] == list(range(5))
    assert server.n_task_replies == 5 and server.n_direct_replies == 0


def test_fault_wrapped_comms_get_their_replies_by_task_in_order():
    """A ``FaultyComm`` has no non-suspending send: a plan that injects
    nothing still sends every reply by Task, in order."""
    model, X = _model()
    clients, per_client = 2, 5
    with AsyncBroker({"map": model}, policy="vt") as server:
        addr = server.serve(fault_plan=FaultPlan(seed=3))
        replies = _on_loop(server, _burst(addr, clients, per_client, X, []))
        t = server.timing_stats()
        injected = server.fault_stats()["injected"]
    _check_replies(model, X, replies, per_client)
    assert server.n_task_replies == clients * per_client == sum(t["hop_out"])
    assert server.n_direct_replies == 0 and injected["events"] == 0


def test_a_full_channel_falls_back_to_a_task_and_keeps_order():
    """Capacity 1: the first reply goes in directly, the second finds the
    channel holding it unread and takes a Task.  The client then reads the
    first, which makes room, but the third still takes a Task: a Task reply
    to that comm is in flight and must not be overtaken."""
    server = AsyncBroker(policy="vt").start()

    async def scenario():
        broker_side, client = _pair(capacity=1)
        reqs = [_Req(broker_side, i, [], 0, i + 1, None) for i in range(3)]
        server._reply(reqs[0], {"id": 0})
        server._reply(reqs[1], {"id": 1})
        got = [await client.recv()]
        server._reply(reqs[2], {"id": 2})
        got += [await client.recv(), await client.recv()]
        for _ in range(10):                 # let the Tasks' callbacks run
            await asyncio.sleep(0)
        return [m["id"] for m in got], dict(server._sending)

    try:
        order, sending = _on_loop(server, scenario())
    finally:
        server.stop()
    assert order == [0, 1, 2]
    assert server.n_direct_replies == 1 and server.n_task_replies == 2
    assert sending == {}


@pytest.mark.parametrize("closed_by", ["broker", "client"])
def test_a_reply_to_a_closed_comm_is_cached_and_dropped(closed_by):
    """The replay slot holds the reply for a retry; the send itself is
    dropped without raising, whether the broker's side is closed or the
    client closed its end of the channels."""
    server = AsyncBroker(policy="vt").start()

    async def scenario():
        broker_side, client = _pair()
        await (broker_side if closed_by == "broker" else client).close()
        msg = {"id": 7, "probs": [np.zeros(1, np.float32)]}
        server._reply(_Req(broker_side, 7, [], 0, 1, None, client="c1"), msg)
        return msg

    try:
        msg = _on_loop(server, scenario())
    finally:
        server.stop()
    assert server._replay["c1"] == (7, "done", msg)
    assert server.n_direct_replies == server.n_task_replies == 0
