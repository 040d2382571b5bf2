"""Host cost of the serving path's instrumentation with no profiler running:
the spans of one broker flush (``broker.queued`` and the six stage spans of
a kernel flush) and its wait counters (the flush's ``queue_wait``
observations, and per request the stamp and observation of each of its two
inproc hops), in µs per flush at a given number of requests per flush.

    PYTHONPATH=src python -m benchmarks.span_overhead [requests_per_flush ...]

Prints ``name,us_per_flush,derived`` CSV lines (host CPU timings)."""

from __future__ import annotations

import collections
import sys
import time
import timeit
from time import perf_counter

from repro.obs import spans
from repro.obs.metrics import Pending
from repro.online.server import _TIMING, H_HOP_IN, H_QUEUE_WAIT

STAGES = ("forest.operands", "forest.launch", "forest.fetch",
          "forest.unpack", "broker.reply")


def _best_us(fn, number: int) -> float:
    return min(timeit.repeat(fn, number=number, repeat=7)) / number * 1e6


def span_us() -> float:
    """The spans of one flush on the kernel path, as ``AsyncBroker`` makes
    them, with no profiler recording."""
    def flush():
        q = spans.span("broker.queued", flush=1)
        q.__enter__()
        q.__exit__(None, None, None)
        with spans.Stages("broker.pack", flush=1) as st:
            for name in STAGES[:-1]:
                spans.stage(name)
            st.to(STAGES[-1])
    return _best_us(flush, 20000)


def queue_wait_us(n: int) -> float:
    """One flush's ``queue_wait`` observations of ``n`` requests, folds
    included."""
    waits = Pending(_TIMING.clone(), H_QUEUE_WAIT)
    t0 = time.perf_counter()
    admitted = [t0 - 1e-3 * (1 + i % 7) for i in range(n)]

    def flush():
        t = time.perf_counter()
        waited = waits.values
        waited.extend([t - a for a in admitted])
        if len(waited) >= Pending.FOLD_AT:
            waits.fold()
    return _best_us(flush, 5000)


def hop_us() -> float:
    """One message's stamp and wait observation, folds included, beyond
    the plain deque append and pop of an inproc channel."""
    waits = Pending(_TIMING.clone(), H_HOP_IN)
    q: collections.deque = collections.deque()
    msg = {"op": "predict"}

    waited, fold_at = waits.values, waits.FOLD_AT

    def stamped():                          # transport._Channel put + get
        q.append((msg, perf_counter()))
        m, t_put = q.popleft()
        waited.append(perf_counter() - t_put)
        if len(waited) >= fold_at:
            waits.fold()

    def plain():
        q.append(msg)
        q.popleft()
    return _best_us(stamped, 100000) - _best_us(plain, 100000)


def run(sizes=(37, 52)) -> None:
    s, h = span_us(), hop_us()
    print(f"spans_per_flush,{s:.3f},7 spans")
    print(f"hop_counter_per_message,{h:.3f},stamp + observe")
    for n in sizes:
        w = queue_wait_us(n)
        total = s + w + 2 * n * h
        print(f"queue_wait_per_flush_n{n},{w:.3f},{n} requests")
        print(f"all_per_flush_n{n},{total:.3f},spans + queue_wait + "
              f"{2 * n} hops")


if __name__ == "__main__":
    run(tuple(int(a) for a in sys.argv[1:]) or (37, 52))
