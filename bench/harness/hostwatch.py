"""What the host did while the window ran, to tell a stall's cause.

Records each full (generation 2) collection of Python's collector with its
length, and the process's CPU time, context switches and page faults
(`getrusage`) against the window's wall time.  A stall with a long
collection in it is the collector's; one in which the process used no CPU
was spent waiting (on the device, a lock, or a host that did not run it).
Reading these costs nothing measurable: one callback a collection.
"""

from __future__ import annotations

import gc
import resource
import time


class HostWatch:
    def __init__(self):
        self.full = []      # (start, seconds) of each full collection
        self.collections = 0
        self.collect_s = 0.0
        self._t = None

    def _on_gc(self, phase, info):
        if phase == "start":
            self._t = time.monotonic()
            return
        if self._t is None:
            return
        dt = time.monotonic() - self._t
        self._t = None
        self.collections += 1
        self.collect_s += dt
        if info.get("generation") == 2:
            self.full.append((time.monotonic() - dt, dt))

    def __enter__(self):
        self._r0 = resource.getrusage(resource.RUSAGE_SELF)
        self._w0, self._c0 = time.monotonic(), time.process_time()
        gc.callbacks.append(self._on_gc)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self._on_gc)
        self.wall_s = time.monotonic() - self._w0
        self.cpu_s = time.process_time() - self._c0
        r1 = resource.getrusage(resource.RUSAGE_SELF)
        self.rusage = {k: getattr(r1, k) - getattr(self._r0, k)
                       for k in ("ru_nivcsw", "ru_nvcsw", "ru_majflt",
                                 "ru_minflt")}
        return False

    def summary(self) -> str:
        longest = max((d for _, d in self.full), default=0.0)
        r = self.rusage
        return (f"wall {self.wall_s!r} s, process CPU {self.cpu_s!r} s; "
                f"{self.collections} collections, {self.collect_s!r} s, "
                f"{len(self.full)} full, longest full {longest!r} s; "
                f"context switches {r['ru_nvcsw']} voluntary, "
                f"{r['ru_nivcsw']} involuntary; page faults "
                f"{r['ru_majflt']} major, {r['ru_minflt']} minor")
