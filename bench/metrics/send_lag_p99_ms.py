"""How late the load generator ran: the 99th percentile of each request's
actual send time minus its scheduled send time, from the generator's own
clock.  A large lag means the clients, which share the broker's event loop,
were starved and the latencies under-state the load."""

import numpy as np


def read(ctx):
    lag = ctx["driver"].send_lag_ms(ctx["window"])
    return float(np.percentile(lag, 99)) if lag.size else None
