"""The measured window: whole units of work over the time that passed.

A window opens at a clock reading, counts the units (steps, ticks) that are
dispatched while less than ``seconds`` have passed, and closes when the last
of them is finished.  The divisor of a rate is the time that really passed
to that moment, never ``seconds``.
"""

from __future__ import annotations

import time
from typing import Callable, Optional


class Window:
    """Counts whole units against an injectable clock."""

    def __init__(self, seconds: float,
                 clock: Callable[[], float] = time.perf_counter):
        self.seconds = float(seconds)
        self.clock = clock
        self.opened: Optional[float] = None
        self.closed: Optional[float] = None
        self.units = 0
        self.work = 0.0

    def open(self) -> float:
        self.opened = self.clock()
        return self.opened

    def admits(self) -> bool:
        """May another unit start?  True until ``seconds`` have passed: the
        unit that is running when they pass is the window's last."""
        return self.clock() - self.opened < self.seconds

    def count(self, work: float) -> None:
        self.units += 1
        self.work += work

    def close(self) -> float:
        """Call when the last counted unit has finished."""
        self.closed = self.clock()
        return self.closed

    @property
    def elapsed(self) -> float:
        return self.closed - self.opened

    def rate(self) -> float:
        """All the work of the window over all the time that passed."""
        return self.work / self.elapsed
