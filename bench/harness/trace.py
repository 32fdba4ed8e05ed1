"""From a profiler trace to device busy time and the breakdown.

`Capture` traces a window with the Python tracer off (host events are the
runtime's own, such as each jitted dispatch).  `events` reads the
``.xplane.pb`` that JAX writes into a flat list of
``[plane, line, name, start_ns, duration_ns]``; `reduce` works on such a
list alone, so a small recorded list checks it without a chip.

* busy: the union of the intervals of the operations on each device plane
  (line ``XLA Ops``), averaged over the devices;
* ``device_ops``: the programs (line ``XLA Modules``) with the most device
  time, summed by name;
* ``idle_gaps``: the longest gaps between busy intervals on the first
  device, each named by the host event that overlaps it most.
"""
from __future__ import annotations

import glob
import os
import time
from typing import Dict, List, Optional, Sequence, Tuple

DEVICE_PREFIX = "/device:"
OPS_LINE = "XLA Ops"
PROGRAMS_LINE = "XLA Modules"
TOP = 10


class Capture:
    """``start()`` then ``stop()``: the traced window's host seconds and the
    path of its ``.xplane.pb``."""

    def __init__(self, log_dir: str):
        self.log_dir = log_dir
        self.t_start = self.seconds = None

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.log_dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self) -> str:
        import jax
        self.seconds = time.perf_counter() - self.t_start
        jax.profiler.stop_trace()
        paths = sorted(glob.glob(os.path.join(
            self.log_dir, "**", "*.xplane.pb"), recursive=True),
            key=os.path.getmtime)
        if not paths:
            raise RuntimeError(f"the profiler wrote no trace under "
                               f"{self.log_dir}")
        return paths[-1]


def events(path: str) -> List[list]:
    """Device events of every line, and host events of every line but the
    Python tracer's, as ``[plane, line, name, start_ns, duration_ns]``."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            if not device and line.name == "python":
                continue
            for ev in line.events:
                out.append([plane.name, line.name, ev.name,
                            float(ev.start_ns), float(ev.duration_ns)])
    return out


def union(intervals: Sequence[Tuple[float, float]]) -> List[List[float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def device_planes(evs: List[list]) -> List[str]:
    return sorted({p for p, line, *_ in evs
                   if p.startswith(DEVICE_PREFIX) and line == OPS_LINE})


def busy_ns(evs: List[list], plane: str) -> List[List[float]]:
    return union([(s, s + d) for p, line, _, s, d in evs
                  if p == plane and line == OPS_LINE])


def _label(evs: List[list], s: float, e: float) -> str:
    best, name = 0.0, "no host event"
    for p, _, n, hs, hd in evs:
        if p.startswith(DEVICE_PREFIX):
            continue
        ov = min(e, hs + hd) - max(s, hs)
        if ov > best:
            best, name = ov, n
    return name


def reduce(evs: List[list], window_s: float, n_devices: Optional[int] = None
           ) -> Dict:
    """``busy_s`` (mean over the device planes, or over ``n_devices`` when
    given), ``window_s`` and the breakdown."""
    planes = device_planes(evs)
    if not planes:
        return {"busy_s": 0.0, "window_s": window_s, "device_ops": [],
                "idle_gaps": []}
    busy = {p: busy_ns(evs, p) for p in planes}
    total = sum(e - s for p in planes for s, e in busy[p])
    busy_s = total / 1e9 / (n_devices or len(planes))
    progs: Dict[str, float] = {}
    for p, line, name, _, d in evs:
        if p == planes[0] and line == PROGRAMS_LINE:
            progs[name] = progs.get(name, 0.0) + d / 1e9
    device_ops = sorted(progs.items(), key=lambda kv: -kv[1])[:TOP]
    b = busy[planes[0]]
    gaps = sorted(((s2 - e1, e1, s2) for (_, e1), (s2, _) in zip(b, b[1:])),
                  reverse=True)[:TOP]
    idle = [[_label(evs, s, e), g / 1e9] for g, s, e in gaps]
    return {"busy_s": busy_s, "window_s": window_s,
            "device_ops": [[n, s] for n, s in device_ops],
            "idle_gaps": idle}
