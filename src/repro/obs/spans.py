"""Named spans of the serving path, on the profiler's clock.

A span is a ``jax.profiler.TraceAnnotation`` (the profiler's TraceMe).  With
no profiler running it records nothing and costs under a microsecond; in a
profiler trace it lands on the host line of the thread that opened it, on
the same clock as the device's events, with its keyword metadata as event
stats.  ``SPANS`` says what each name covers.  Spans are made whether or not
a profiler records: ``TraceAnnotation.is_enabled()`` reads False on a TPU
host while a trace records them, so it cannot gate them.

A broker flush is tiled by consecutive stage spans, all tagged
``flush=<n>``, with no span around the whole flush: ``Stages`` holds the
open one, and the code a flush runs through moves it on with ``stage``,
which does nothing outside a flush (a predictor scoring on its own thread,
a test calling the kernel)."""

from __future__ import annotations

import threading

from jax.profiler import TraceAnnotation

SPANS = {
    "broker.queued": "from a request entering the broker's empty queue to "
                     "the start of the flush that takes it",
    "broker.pack": "flush start: the queue taken, each request's queue "
                   "wait counted, rows coalesced per model and assembled "
                   "into one block, the models' packed blocks looked up",
    "forest.operands": "the grouped kernel's tile layout, padded rows and "
                       "model blocks, built on the host",
    "forest.launch": "the grouped kernel's jitted call: dispatch and the "
                     "host-to-device transfers of its operands",
    "forest.fetch": "the result brought to the host: the device run, its "
                    "layout copies and the device-to-host transfer",
    "forest.numpy": "the numpy mirror's pass, in place of the three "
                    "kernel stages",
    "forest.unpack": "the host divide, the clip and the per-request slices",
    "broker.reply": "each request's reply handed to its transport",
}


class _Local(threading.local):
    stages = None                   # the flush open on this thread, if any


_local = _Local()


def span(name: str, **meta) -> TraceAnnotation:
    """A span named ``name`` (a key of ``SPANS``) carrying ``meta``."""
    return TraceAnnotation(name, **meta)


class Stages:
    """Consecutive spans that tile one flush on the calling thread:
    ``with Stages("broker.pack", flush=n) as st:`` opens the first, each
    ``st.to(name)`` (or ``stage(name)`` further down the call) closes the
    open one and opens the next, and the end of the block closes the last."""

    def __init__(self, first: str, **meta):
        self.first, self.meta, self.open = first, meta, None

    def to(self, name: str):
        if self.open is not None:
            self.open.__exit__(None, None, None)
        self.open = span(name, **self.meta)
        self.open.__enter__()

    def __enter__(self) -> "Stages":
        _local.stages = self
        self.to(self.first)
        return self

    def __exit__(self, *exc):
        _local.stages = None
        self.open.__exit__(None, None, None)
        self.open = None
        return False


def stage(name: str):
    """Move this thread's flush on to stage ``name``; outside a flush,
    nothing."""
    st = _local.stages
    if st is not None:
        st.to(name)
