"""What one run recorded, as the metric readers see it.

A reader is ``read(run) -> float | None``: ``None`` where the run holds
nothing for it to read, and the harness then leaves the metric out.
"""

from __future__ import annotations

import dataclasses
from typing import List, Optional, Tuple


@dataclasses.dataclass
class Run:
    cfg: dict
    t_start: float                      # process start, monotonic clock
    window: Tuple[float, float]         # (t0, t1), monotonic clock
    requests: List[dict]                # client.new_record, filled
    recorder: object = None             # serve.Recorder
    trace: Optional[dict] = None        # trace_reduce.reduce, traced runs
    memory: Optional[dict] = None       # device.memory_stats() after
    peak: Optional[dict] = None         # peaks.json entry of the device
    host_end: Optional[float] = None    # profiler start, traced runs

    def inside(self, t: float) -> bool:
        return self.window[0] <= t < self.window[1]

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def host_window(self) -> Tuple[float, float]:
        """The part of the window that host-clock readers take: all of it,
        or in a traced run the part before the profiler started, since the
        profiler slows what they time."""
        if self.host_end is None:
            return self.window
        return self.window[0], max(self.window[0],
                                   min(self.window[1], self.host_end))

    def in_host_window(self, t: float) -> bool:
        return self.host_window[0] <= t < self.host_window[1]

    def tokens_in_window(self) -> int:
        return sum(1 for r in self.requests for t in r["t"]
                   if self.inside(t))

    def host_ticks(self):
        """Decode ticks that end in the host window."""
        return [t for t in self.recorder.ticks if self.in_host_window(t[1])]

    def host_prefills(self):
        """Prefills that end in the host window."""
        return [p for p in self.recorder.prefills
                if self.in_host_window(p[1])]
