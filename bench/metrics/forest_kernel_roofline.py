"""The grouped forest kernel's share of its roofline over the window.

Numerator: the least time the chip could take for the window's flushes,
from the algorithm's work (``bench/roofline.py``: every flush's real rows
and the models it read, not the padded tiles or the one-hot products) over
the published peaks of the device kind (``bench/peaks.json``), whichever of
compute and memory bounds it.  Denominator: the kernel's summed device time
in the profiler trace.  The kernel has no stable name of its own yet; its
events are matched by ``harness.KERNEL_EVENT`` (the jitted launcher's name,
``grouped_call``, as it appears in a TPU trace).  Nothing to read (no trace,
no kernel event, no flush) gives no value, never 0."""

from bench import roofline


def read(ctx):
    tr = ctx["trace"]
    flushes = ctx["window"]["flushes"]
    if tr is None or not tr.get("kernel_s") or not flushes:
        return None
    f = ctx["config"]["forest"]
    ops = nbytes = 0
    for rows, _, segments, _ in flushes:
        o, b = roofline.flush_work(rows, segments, n_trees=f["n_trees"],
                                   depth=f["depth"],
                                   n_features=f["n_features"])
        ops += o
        nbytes += b
    pct, bound = roofline.roofline_pct(ops, nbytes, tr["kernel_s"],
                                       roofline.peaks(ctx["device"]["kind"]))
    ctx["notes"].append(f"forest_kernel_roofline: {bound}-bound, {ops} ops, "
                        f"{nbytes} bytes, kernel {tr['kernel_s']} s")
    return pct
