"""What the host did to a window: the garbage collector's pauses and the
CPU time the hypervisor took away (steal), for the run's log."""

from __future__ import annotations

import gc
import os
import time


def _steal_s() -> float | None:
    """Seconds of CPU time stolen so far, summed over the host's CPUs."""
    try:
        with open("/proc/stat") as f:
            fields = f.readline().split()
        return int(fields[8]) / os.sysconf("SC_CLK_TCK")
    except (OSError, IndexError, ValueError):
        return None


class HostWatch:
    """Between :meth:`start` and :meth:`stop`, times every collection of
    the garbage collector and reads the host's steal counter."""

    def __init__(self):
        self.pauses: list[tuple[int, float]] = []   # (generation, seconds)
        self._t = 0.0

    def _on_gc(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t))

    def start(self) -> None:
        self._steal0 = _steal_s()
        self._cpu0 = time.process_time()
        self._wall0 = time.perf_counter()
        gc.callbacks.append(self._on_gc)

    def stop(self) -> None:
        gc.callbacks.remove(self._on_gc)
        steal1 = _steal_s()
        self.steal_s = (None if steal1 is None or self._steal0 is None
                        else steal1 - self._steal0)
        self.cpu_s = time.process_time() - self._cpu0
        self.wall_s = time.perf_counter() - self._wall0

    def describe(self) -> str:
        full = [s for g, s in self.pauses if g == 2]
        steal = ("n/a" if self.steal_s is None
                 else f"{self.steal_s:.2f} s over {os.cpu_count()} CPUs")
        return (f"host: {len(self.pauses)} collections "
                f"({sum(s for _, s in self.pauses):.3f} s), {len(full)} full "
                f"(longest {max(full, default=0.0):.3f} s), process CPU "
                f"{self.cpu_s:.1f} s in {self.wall_s:.1f} s, steal {steal}")
