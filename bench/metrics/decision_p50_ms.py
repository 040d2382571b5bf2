"""Median decision latency of all requests in the window, from each
request's scheduled send to its reply as the client saw it (host clock)."""

import numpy as np


def read(ctx):
    lat = ctx["driver"].latencies_ms(ctx["window"])
    return float(np.percentile(lat, 50)) if lat.size else None
