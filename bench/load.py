"""The client layer: the decision stream, arrival schedules and open-loop
clients that drive a serving ``AsyncBroker`` through the transport.

The arrival processes and the open-loop client follow
``repro.online.bench`` (``_arrival_schedule``, ``_open_loop_client``),
copied here so that the yardstick stays with the benchmark: a schedule is
bounded by the measured window rather than by a request count, and every
request is recorded (scheduled send, actual send, reply, answer) for the
metrics and the correctness check.  Latency runs from each request's
*scheduled* arrival to its reply, so a stalled broker keeps paying for the
requests it should already have served (no coordinated omission)."""

from __future__ import annotations

import asyncio
import time

import numpy as np
from jax.profiler import TraceAnnotation


def rng_for(seed: int, *tags) -> np.random.Generator:
    """An independent generator for one use of ``--seed``: any whole number
    (larger than 32 bits too), split by integer tags."""
    return np.random.default_rng(np.random.SeedSequence([int(seed), *tags]))


def size_mix(config: dict) -> list[tuple[str, int, int]]:
    """``(kind, rows, count)`` of the configuration's request sizes: the
    histogram ``{kind: {rows: count}}`` of its ATLAS deployment's scoring
    calls, recorded by ``bench/demand.py``."""
    hist = config["demand"]["sizes"]
    return [(kind, int(n), int(c)) for kind in sorted(hist)
            for n, c in hist[kind].items() if int(c) > 0]


def cut_requests(map_rows: np.ndarray, reduce_rows: np.ndarray,
                 mix: list[tuple[str, int, int]],
                 rng: np.random.Generator) -> list[tuple[str, np.ndarray]]:
    """The decision stream: one request per count of the mix, so every seed
    replays the same multiset of kinds and sizes, in an order drawn from
    ``rng``.  A request takes the next ``rows`` launch rows of its kind from
    the trace, cyclically (a kind the trace never ran takes the other
    kind's rows)."""
    pools = {"map": np.asarray(map_rows), "reduce": np.asarray(reduce_rows)}
    if not any(len(x) for x in pools.values()):
        raise ValueError("the trace has no launch rows to replay")
    for kind, other in (("map", "reduce"), ("reduce", "map")):
        if not len(pools[kind]):
            pools[kind] = pools[other]
    shapes = [(kind, n) for kind, n, c in mix for _ in range(c)]
    order = rng.permutation(len(shapes))
    nxt = {"map": 0, "reduce": 0}
    requests = []
    for i in order:
        kind, n = shapes[i]
        pool = pools[kind]
        idx = (nxt[kind] + np.arange(n)) % len(pool)
        nxt[kind] = int(idx[-1]) + 1
        requests.append((kind, pool[idx]))
    return requests


def arrival_schedule(seconds: float, rate_rps: float, arrivals: dict,
                     rng: np.random.Generator) -> np.ndarray:
    """Scheduled send offsets in ``[0, seconds)`` for one client.

    ``{"process": "poisson"}`` draws exponential gaps at ``rate_rps``;
    ``{"process": "mmpp", "fast": 4.0, "slow": 0.4, "flip": 0.05}`` is the
    two-state MMPP: bursts at ``fast`` times the base rate and calm
    stretches at ``slow`` times it, flipping with probability ``flip`` after
    each arrival, starting in a burst."""
    rate_rps = float(rate_rps)
    if rate_rps <= 0:
        raise ValueError(f"rate must be positive, got {rate_rps}")
    proc = arrivals["process"]
    out, t = [], 0.0
    if proc == "poisson":
        while True:
            t += rng.exponential(1.0 / rate_rps)
            if t >= seconds:
                break
            out.append(t)
    elif proc == "mmpp":
        fast = True
        hi, lo, flip = (float(arrivals["fast"]), float(arrivals["slow"]),
                        float(arrivals["flip"]))
        while True:
            t += rng.exponential(1.0 / (rate_rps * (hi if fast else lo)))
            if t >= seconds:
                break
            out.append(t)
            if rng.random() < flip:
                fast = not fast
    else:
        raise ValueError(f"unknown arrival process {proc!r}")
    return np.asarray(out, np.float64)


class ClientLog:
    """What one client saw: per request its stream index, scheduled and
    actual send times, reply time and answer (``None`` until answered)."""

    def __init__(self, idxs: np.ndarray, sched: np.ndarray):
        self.idxs = idxs
        self.sched = sched                       # absolute perf_counter times
        self.sent = np.full(len(idxs), np.nan)
        self.done = np.full(len(idxs), np.nan)
        self.probs: list = [None] * len(idxs)
        self.errors: list = [None] * len(idxs)


async def open_loop_client(address: str, requests, log: ClientLog,
                           slo_ms: float | None, reply_deadline: float):
    """Fire requests at their scheduled times without waiting for replies; a
    reader task matches replies by id.  Replies still missing at
    ``reply_deadline`` (a perf_counter time) stay unanswered."""
    from repro.online.transport import connect
    comm = await connect(address)
    n = len(log.idxs)

    async def reader():
        for _ in range(n):
            reply = await comm.recv()
            t_done = time.perf_counter()
            j = reply["id"]
            log.done[j] = t_done
            if reply.get("error") is not None:
                log.errors[j] = str(reply["error"])
            else:
                log.probs[j] = reply["probs"][0]

    rtask = asyncio.ensure_future(reader())
    try:
        for j in range(n):
            delay = log.sched[j] - time.perf_counter()
            if delay > 0:
                await asyncio.sleep(delay)
            kind, X = requests[log.idxs[j]]
            msg = {"op": "predict", "id": j, "kind": kind, "X": X}
            if slo_ms:
                msg["budget_ms"] = slo_ms
            log.sent[j] = time.perf_counter()
            with TraceAnnotation("client.send"):
                await comm.send(msg)
        try:
            await asyncio.wait_for(
                rtask, max(reply_deadline - time.perf_counter(), 0.001))
        except asyncio.TimeoutError:
            pass
    finally:
        rtask.cancel()
        await asyncio.gather(rtask, return_exceptions=True)
        await comm.close()


def plan_clients(n_requests: int, traffic: dict, seconds: float, seed: int,
                 tag: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """Per client ``(stream indices, scheduled offsets)`` for one window.
    Client ``c`` replays every ``clients``-th request of the stream from a
    start drawn from the seed; ``tag`` separates the warm-up from the
    measured window."""
    clients = int(traffic["clients"])
    per_client = float(traffic["rate_rps"]) / clients
    start = int(rng_for(seed, tag, 0).integers(n_requests))
    plans = []
    for c in range(clients):
        offs = arrival_schedule(seconds, per_client, traffic["arrivals"],
                                rng_for(seed, tag, 1 + c))
        idxs = (start + c + clients * np.arange(len(offs))) % n_requests
        plans.append((idxs.astype(np.int64), offs))
    return plans
