"""What held the server process back in a run's window.

A run on the host's clock is held back when the machine stands still, or
when the server process stops itself to collect garbage.  ``Watch``, over
the window, times the server's collections and runs a ticker that wakes
every 50 ms and keeps how late it woke, so that a stall of the load
generator (its schedule thread's lateness, ``loadgen.child``) can be told
from one of the server.  It only observes.
"""

from __future__ import annotations

import gc
import threading
import time


class Watch:
    """``with Watch() as w:`` around the window; ``w.summary()`` after."""

    TICK_S = 0.05

    def __init__(self):
        self.gc_pauses = []          # (generation, seconds)
        self.tick_late_max_ms = 0.0
        self._gc_t0 = None
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._tick, daemon=True,
                                        name="chipbench-watch")

    def __enter__(self) -> "Watch":
        gc.callbacks.append(self._on_gc)
        self._thread.start()
        return self

    def __exit__(self, *exc) -> None:
        self._stop.set()
        self._thread.join()
        gc.callbacks.remove(self._on_gc)

    def _on_gc(self, phase, info) -> None:
        if phase == "start":
            self._gc_t0 = time.perf_counter()
        elif self._gc_t0 is not None:
            self.gc_pauses.append((info.get("generation"),
                                   time.perf_counter() - self._gc_t0))
            self._gc_t0 = None

    def _tick(self) -> None:
        due = time.perf_counter() + self.TICK_S
        while not self._stop.wait(max(0.0, due - time.perf_counter())):
            self.tick_late_max_ms = max(self.tick_late_max_ms,
                                        (time.perf_counter() - due) * 1e3)
            due += self.TICK_S

    def summary(self) -> dict:
        ms = [d * 1e3 for _, d in self.gc_pauses]
        return {"server_tick_late_max_ms": self.tick_late_max_ms,
                "server_gc_n": len(ms),
                "server_gc_gen2_n": sum(1 for g, _ in self.gc_pauses
                                        if g == 2),
                "server_gc_ms": sum(ms),
                "server_gc_max_ms": max(ms, default=0.0)}
