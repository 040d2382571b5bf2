"""The machine witness: a separate process that reports overrun sleeps."""

import os
import signal
import time

from bench import witness


def test_witness_sees_its_own_process_stopped_and_ends_cleanly():
    with witness.MachineWitness() as w:
        time.sleep(0.3)
        t0 = time.monotonic()
        os.kill(w._proc.pid, signal.SIGSTOP)     # the machine "pauses"
        time.sleep(0.2)
        os.kill(w._proc.pid, signal.SIGCONT)
        deadline = time.monotonic() + 5
        while not w.pauses and time.monotonic() < deadline:
            time.sleep(0.01)
    assert w._proc.poll() is not None
    (start, seconds), *_ = w.within(t0 - 0.01, time.monotonic())
    assert 0.15 < seconds < 5 and start < t0 + 0.05
    assert w.within(0.0, t0 - 1.0) == []
