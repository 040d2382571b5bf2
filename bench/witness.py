"""A witness of pauses of the whole machine.

The chip's host pauses now and then: every process on it stops at the same
moment, for about 0.11 s and now and then for seconds (PERF.md, section 5).
A pause that falls in a window sets the tail of its latencies, whatever the
system under test does.  The witness is a separate Python process that
touches neither JAX nor the chip: it sleeps a millisecond at a time and
reports every sleep that overran by ``MIN_PAUSE_S`` or more.  A gap it sees
is a pause of the machine, not of the benchmark's process.  Times are
``time.monotonic``, which every process on the host shares."""

from __future__ import annotations

import subprocess
import sys
import threading

MIN_PAUSE_S = 0.05

_SLEEPER = f"""
import time
while True:
    t = time.monotonic()
    time.sleep(0.001)
    dt = time.monotonic() - t
    if dt >= {MIN_PAUSE_S}:
        print(t, dt, flush=True)
"""


class MachineWitness:
    """``with MachineWitness() as w: ...``; ``w.pauses`` holds ``(start,
    seconds)`` of every pause the witness has reported so far."""

    def __init__(self):
        self.pauses: list[tuple[float, float]] = []
        self._proc = self._reader = None

    def __enter__(self):
        self._proc = subprocess.Popen([sys.executable, "-c", _SLEEPER],
                                      stdout=subprocess.PIPE, text=True)
        self._reader = threading.Thread(target=self._read, daemon=True)
        self._reader.start()
        return self

    def _read(self):
        for line in self._proc.stdout:
            a, b = line.split()
            self.pauses.append((float(a), float(b)))

    def __exit__(self, *exc):
        self._proc.terminate()
        self._proc.wait(timeout=30)
        self._reader.join(timeout=30)
        self._proc.stdout.close()
        return False

    def within(self, t0: float, t1: float) -> list[tuple[float, float]]:
        """The pauses that overlap ``[t0, t1]``."""
        return [(s, d) for s, d in self.pauses if s < t1 and s + d > t0]
