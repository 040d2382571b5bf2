"""99th percentile decision latency of all requests in the window, timed as
``decision_p50_ms``; a request that failed or was never answered counts at
the reply deadline, past any limit (host clock).  A pause of the whole
machine (``bench.witness``; the result's ``machine_pauses``) lands on every
request in flight and sets this tail whatever the broker does, which is why
it is a per-layer metric and not held to a bound."""

import numpy as np


def read(ctx):
    lat = ctx["driver"].latencies_ms(ctx["window"])
    return float(np.percentile(lat, 99)) if lat.size else None
