"""What the machine did to a window, seen from this process: the longest
time it was kept from running. A one-chip machine shares its host's
cores, and a silence in the answers is the program's doing only if this
process did not stand still meanwhile. Printed on standard error; no
metric reads it. (/proc/stat reads all zeros on the chip's machine, so
the cores' steal and iowait cannot be had from there.)"""

from __future__ import annotations

import threading
import time

TICK_S = 0.05


class HostWatch:
    """A thread that asks to sleep TICK_S at a time and records by how much
    it once overslept."""

    def __init__(self):
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True)
        self.standstill_s = 0.0
        self._thread.start()

    def _run(self) -> None:
        last = time.monotonic()
        while not self._stop.wait(TICK_S):
            now = time.monotonic()
            self.standstill_s = max(self.standstill_s, now - last - TICK_S)
            last = now

    def report(self) -> str:
        self._stop.set()
        self._thread.join()
        return f"longest standstill of this process {self.standstill_s:.3f}s"
