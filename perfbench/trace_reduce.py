"""From a profiler trace to the numbers the per-layer metrics read.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes into
plain lists: the traced window's length, the device's operations and
program executions, and the benchmark's host annotations around the
scheduler's prefill and decode calls.  ``reduce`` turns those lists into
the device's busy time (the union of its operations' intervals, averaged
over the chips), the idle share, program launches per annotated decode
tick, the programs that took most device time (by name, so that the
many launches of one eager operation add up), and the longest idle
gaps, each named by what the scheduler thread was doing in it.  Kept
apart from any chip so that a recorded trace (``tests/fixtures``) checks
the arithmetic.
"""

from __future__ import annotations

import bisect
import re
from collections import defaultdict
from typing import Dict, List, Tuple

from perfbench.serve import DECODE, PREFILL

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PREFIX = "/device:TPU:"
GAP_NAMES = {PREFILL: "prefill", DECODE: "decode_tick"}


def extract(path: str) -> dict:
    """Plain lists from one trace file; times in ns from the trace's start."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    window_ns = None
    ops: List[list] = []
    modules: List[list] = []
    host: List[list] = []
    chips = []
    for plane in pd.planes:
        if plane.name == "Task Environment":
            stats = dict(plane.stats)
            if "profile_start_time" in stats and "profile_stop_time" in stats:
                window_ns = float(stats["profile_stop_time"]
                                  - stats["profile_start_time"])
        elif plane.name.startswith(DEVICE_PREFIX) and \
                plane.name[len(DEVICE_PREFIX):].isdigit():
            chip = len(chips)
            chips.append(plane.name)
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is None:
                    continue
                for e in line.events:
                    dest.append([chip, e.name, e.start_ns, e.end_ns])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in GAP_NAMES:
                        host.append([e.name, e.start_ns, e.end_ns])
    if window_ns is None:
        window_ns = max([e[3] for e in ops + modules]
                        + [e[2] for e in host] + [0.0])
    return {"window_ns": window_ns, "chips": chips, "ops": ops,
            "modules": modules, "host": host}


def union(intervals: List[Tuple[float, float]], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """Merged, clipped to [lo, hi]."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def op_family(name: str) -> str:
    """An operation's name without the numbers XLA appends to tell
    instances apart (``fusion.12`` and ``fusion.7`` are one family)."""
    return re.sub(r"(\.\d+)+$", "", re.sub(r"\(\d+\)$", "", name))


def _covering(host, t: float) -> str:
    for name, s, e in host:
        if s <= t < e:
            return GAP_NAMES[name]
    return "outside"


def reduce(ev: dict, top: int = 10) -> dict:
    window = float(ev["window_ns"])
    n_chips = max(1, len(ev["chips"]))
    busy_src = ev["ops"] or ev["modules"]
    per_chip = defaultdict(list)
    for chip, _, s, e in busy_src:
        per_chip[chip].append((s, e))
    busy_ns = [sum(e - s for s, e in union(per_chip.get(c, []), 0.0, window))
               for c in range(n_chips)]
    out: Dict[str, object] = {
        "window_s": window / 1e9,
        "busy_s": sum(busy_ns) / n_chips / 1e9,
    }
    out["idle_share"] = (1.0 - out["busy_s"] / out["window_s"]
                         if window > 0 else None)

    totals: Dict[str, float] = defaultdict(float)
    for _, name, s, e in ev["modules"] or ev["ops"]:
        totals[op_family(name)] += (e - s) / 1e9
    out["device_ops"] = sorted(([k, v] for k, v in totals.items()),
                               key=lambda kv: -kv[1])[:top]

    host = sorted(ev["host"], key=lambda h: h[1])
    gaps = []
    busy0 = union(per_chip.get(0, []), 0.0, window)
    edge = 0.0
    for s, e in busy0 + [(window, window)]:
        if s > edge:
            gaps.append((s - edge, _covering(host, (edge + s) / 2)))
        edge = max(edge, e)
    gaps.sort(key=lambda g: -g[0])
    out["idle_gaps"] = [[name, d / 1e9] for d, name in gaps[:top]]
    idle_by = defaultdict(float)
    for d, name in gaps:
        idle_by[name] += d / 1e9
    out["idle_by_host_state"] = dict(idle_by)

    ticks = [(s, e) for name, s, e in host
             if name == DECODE and s >= 0.0 and e <= window]
    if ticks and ev["modules"]:
        starts = sorted(s for chip, _, s, _ in ev["modules"] if chip == 0)
        n = sum(bisect.bisect_right(starts, e) - bisect.bisect_left(starts, s)
                for s, e in ticks)
        out["launches_per_tick"] = n / len(ticks)
        out["ticks_traced"] = len(ticks)
    else:
        out["launches_per_tick"] = None
        out["ticks_traced"] = len(ticks)
    return out
