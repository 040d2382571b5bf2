"""Profiler trace -> the numbers the device-side metrics read.

A traced run writes JAX's profiler trace (``.xplane.pb``) for the measured
window.  ``load`` reads it with ``jax.profiler.ProfileData`` into plain
event lists; everything after that is pure functions of those lists, so
the reduction is tested on a small recorded trace without a chip.

- Device events are those on the ``XLA Ops`` line of each ``/device:TPU:n``
  plane: one event per operation the chip ran.
- The window is the benchmark's own host span ``WINDOW_SPAN`` (a
  ``TraceAnnotation`` around the measured window); device and host events
  of one trace share its clock.
- Busy time is the union of the device events' intervals inside the
  window, averaged over the devices; idle is the rest of the window."""

from __future__ import annotations

import glob
import os
import re

WINDOW_SPAN = "bench.window"
# what names an idle gap that a pause of the whole machine covers
MACHINE_PAUSE = "machine pause (every process)"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


def load(trace_dir: str) -> dict:
    """``{"device": {plane: [(name, start_ns, end_ns)]},
    "host": [(thread, name, start_ns, end_ns)]}`` from the newest
    ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(paths[-1])
    device: dict = {}
    host: list = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            evs = device.setdefault(plane.name, [])
            for line in plane.lines:
                if line.name == OPS_LINE:
                    evs.extend((e.name, e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((line.name, e.name, e.start_ns,
                             e.start_ns + e.duration_ns) for e in line.events)
    return {"device": device, "host": host}


def window_bounds(events: dict) -> tuple[float, float] | None:
    spans = [(s, e) for _, name, s, e in events["host"] if name == WINDOW_SPAN]
    if not spans:
        return None
    return min(s for s, _ in spans), max(e for _, e in spans)


def with_pauses(events: dict, pauses, span_start: float) -> dict:
    """``events`` with the machine's pauses (``(time.monotonic start,
    seconds)``, from ``bench.witness``) as host spans ``MACHINE_PAUSE`` on
    the trace's clock, placed by the window span, which began at the
    monotonic time ``span_start``."""
    bounds = window_bounds(events)
    if bounds is None or not pauses:
        return events
    off = bounds[0] - 1e9 * span_start
    extra = [("python", MACHINE_PAUSE, off + 1e9 * t, off + 1e9 * (t + d))
             for t, d in pauses]
    return {**events, "host": events["host"] + extra}


def op_name(name: str) -> str:
    """An XLA op event's instruction name (``%copy.1``), without the HLO
    text that follows it in a TPU trace."""
    return name.split(" = ", 1)[0]


def _clip(evs, lo, hi):
    return [(n, max(s, lo), min(e, hi)) for n, s, e in evs if e > lo and s < hi]


def union_ns(intervals) -> float:
    """Total length covered by ``(start, end)`` intervals."""
    total, cur_s, cur_e = 0.0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def reduce(events: dict, kernel: re.Pattern | None = None,
           top: int = 10) -> dict | None:
    """Busy and window seconds, kernel seconds and the breakdown.  ``None``
    when the trace holds no window span or no device event in it."""
    bounds = window_bounds(events)
    if bounds is None:
        return None
    lo, hi = bounds
    per_dev = {p: _clip(evs, lo, hi) for p, evs in events["device"].items()}
    per_dev = {p: evs for p, evs in per_dev.items() if evs}
    if not per_dev:
        return None
    busy = sum(union_ns((s, e) for _, s, e in evs)
               for evs in per_dev.values()) / len(per_dev)
    by_name: dict = {}
    for evs in per_dev.values():
        for n, s, e in evs:
            n = op_name(n)
            by_name[n] = by_name.get(n, 0.0) + (e - s)
    kernel_ns = None
    if kernel is not None:
        hits = [v for n, v in by_name.items() if kernel.search(n)]
        kernel_ns = sum(hits) / len(per_dev) if hits else None
    ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
    return {"window_s": (hi - lo) * 1e-9, "busy_s": busy * 1e-9,
            "devices": len(per_dev),
            "kernel_s": None if kernel_ns is None else kernel_ns * 1e-9,
            "device_ops": [[n, v * 1e-9 / len(per_dev)] for n, v in ops],
            "idle_gaps": idle_gaps(per_dev, events["host"], lo, hi, top)}


def idle_gaps(per_dev: dict, host: list, lo: float, hi: float,
              top: int) -> list:
    """The longest gaps between device operations on the first device, each
    named by the span of a Python thread that covers most of it (``"no host
    span"`` when none does: Python code that opens no span, or waiting)."""
    evs = sorted((s, e) for _, s, e in per_dev[sorted(per_dev)[0]])
    gaps, cur = [], lo
    for s, e in evs:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        gaps.append((cur, hi))
    gaps = sorted(gaps, key=lambda g: g[0] - g[1])[:top]
    spans = [(n, s, e) for t, n, s, e in host
             if t == "python" and n != WINDOW_SPAN]
    out = []
    for g0, g1 in gaps:
        best, cover = "no host span", 0.0
        for n, s, e in spans:
            c = min(e, g1) - max(s, g0)
            if c > cover:
                best, cover = n, c
        out.append([best, (g1 - g0) * 1e-9])
    return out
