"""Rows the broker scored per flush over the window: the deltas of
``AsyncBroker.stats()`` ``rows`` over ``flushes`` (program counters)."""


def read(ctx):
    c = ctx["window"]["counters"]
    return c["rows"] / c["flushes"] if c["flushes"] else None
