"""From a profiler trace to the numbers the per-layer metrics read.

Two steps, kept apart so that the second can be tested on a small
recorded trace (``tests/recorded_trace.json``):

1. :func:`read_xplane` reads the ``.xplane.pb`` the JAX profiler wrote
   and keeps what the benchmark uses: each device's operations (the
   ``XLA Ops`` line of every ``/device:TPU:<n>`` plane) and the
   benchmark's own host spans (``window``, ``next_batch``, ``stage``,
   ``dispatch``), all in nanoseconds on the profiler's clock.
2. :func:`reduce` clips those to the ``window`` span and derives busy
   time (the union of each device's operation intervals), time per
   operation name, and the idle gaps, each named after the host span
   that overlaps it most.
"""
from __future__ import annotations

import glob
import re
from typing import Dict, List, Optional

HOST_SPANS = ("window", "next_batch", "stage", "dispatch")
# a device operation's event name is its HLO instruction's text:
# ``%fusion.5 = pred[131072]{...} fusion(...)`` -> ``fusion.5``
OP_NAME = re.compile(r"^%?([^\s=]+)")
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def read_xplane(log_dir: str) -> Dict:
    """The trace under ``log_dir`` as a plain dict:
    ``{"devices": {id: [[name, start_ns, dur_ns], ...]},
    "host": [[name, start_ns, dur_ns], ...]}``."""
    import jax
    paths = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one xplane file under {log_dir}, "
                           f"found {len(paths)}")
    data = jax.profiler.ProfileData.from_file(paths[0])
    devices: Dict[str, List] = {}
    host: List = []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name == OPS_LINE:
                devices.setdefault(m.group(1), []).extend(
                    [op_name(e.name), e.start_ns, e.duration_ns]
                    for e in line.events)
            elif plane.name.startswith("/host:"):
                host.extend([e.name, e.start_ns, e.duration_ns]
                            for e in line.events if e.name in HOST_SPANS)
    return {"devices": devices, "host": host}


def op_name(event_name: str) -> str:
    m = OP_NAME.match(event_name)
    return m.group(1) if m else event_name


def union(intervals: List) -> List:
    """Sorted, merged ``[start, end]`` intervals."""
    out: List = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def reduce(trace: Dict, top: int = 10) -> Optional[Dict]:
    """Window, busy time, time per operation and idle gaps; None when the
    trace holds no window span or no device operation in it."""
    windows = [h for h in trace["host"] if h[0] == "window"]
    if len(windows) != 1:
        return None
    w0 = windows[0][1]
    w1 = w0 + windows[0][2]
    spans = [(n, s, s + d) for n, s, d in trace["host"] if n != "window"]
    busy, per_op, gaps = [], {}, {}
    for dev, ops in sorted(trace["devices"].items()):
        iv = []
        for name, s, d in ops:
            s, e = max(s, w0), min(s + d, w1)
            if e > s:
                iv.append([s, e])
                per_op[name] = per_op.get(name, 0) + (e - s)
        merged = union(iv)
        busy.append(sum(e - s for s, e in merged))
        edges = [w0] + [x for se in merged for x in se] + [w1]
        for g0, g1 in zip(edges[::2], edges[1::2]):
            if g1 <= g0:
                continue
            best, label = 0, "none"
            for n, s, e in spans:
                ov = min(e, g1) - max(s, g0)
                if ov > best:
                    best, label = ov, n
            gaps[label] = gaps.get(label, 0) + (g1 - g0)
    if not busy or not any(busy):
        return None
    n_dev = len(busy)
    return {
        "window_ns": w1 - w0,
        "busy_ns": sum(busy) / n_dev,
        "n_devices": n_dev,
        "op_ns": {k: v / n_dev for k, v in per_op.items()},
        "device_ops": sorted(([k, v / n_dev / 1e9]
                              for k, v in per_op.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v / n_dev / 1e9]
                             for k, v in gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def kernel_ns(red: Dict, prefix: str) -> float:
    """Device time per chip of the operations whose name starts with
    ``prefix`` (a Pallas kernel's ``name=``)."""
    return sum(v for k, v in red["op_ns"].items() if k.startswith(prefix))
