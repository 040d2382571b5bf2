"""Median host time of one broker flush in the window: packing the rows,
the device pass and its transfers, the host divide, as the broker's own
flush observer times it (``AsyncBroker.obs.record_flush``).  The event loop
serves nothing else meanwhile."""

import numpy as np


def read(ctx):
    dts = [f[3] for f in ctx["window"]["flushes"]]
    return 1e3 * float(np.median(dts)) if dts else None
