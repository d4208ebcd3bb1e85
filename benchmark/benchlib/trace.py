"""Reduce a JAX profiler trace (``.xplane.pb``) to device busy time, idle
share, time per device operation and the host activity in each idle gap.

* Device planes are the planes named ``/device:TPU:<k>``; their operations
  are the events of the line ``XLA Ops``.  Busy time is the length of the
  union of those intervals inside the traced window, so overlapping or
  nested events count once.
* The traced window is the host event named ``window_name`` (the harness
  opens one around its measured loop).  Without it, the window runs from
  the first to the last device operation.
* An idle gap is a stretch of the window in which no operation runs on the
  device.  Each moment of a gap is named by the host event, open at that
  moment on any host thread, that began last: what the host had most
  recently begun while the device sat idle.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
NO_HOST_EVENT = "(no host event)"


def union_length(intervals, lo=None, hi=None) -> float:
    """Length of the union of ``(start, end)`` intervals, clipped to
    ``[lo, hi]`` where given."""
    total = 0.0
    cur_s = cur_e = None
    for s, e in sorted(intervals):
        if lo is not None:
            s = max(s, lo)
        if hi is not None:
            e = min(e, hi)
        if e <= s:
            continue
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        else:
            cur_e = max(cur_e, e)
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def idle_gaps(intervals, lo: float, hi: float):
    """The ``(start, end)`` stretches of ``[lo, hi]`` that no interval
    covers."""
    gaps = []
    t = lo
    for s, e in sorted(intervals):
        if e <= t:
            continue
        if s > t:
            gaps.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        gaps.append((t, hi))
    return [(s, e) for s, e in gaps if e > s]


def host_timeline(host):
    """Piecewise-constant ``[(start, end, name)]``: at each moment the host
    event, among those open on any thread, that began last."""
    import heapq

    bounds = sorted({t for s, e, _ in host for t in (s, e)})
    host = sorted(host)
    active = []                          # max-heap on start, lazy deletion
    out = []
    k = 0
    for t0, t1 in zip(bounds, bounds[1:]):
        while k < len(host) and host[k][0] <= t0:
            s, e, name = host[k]
            heapq.heappush(active, (-s, e, name))
            k += 1
        # an ended event is dropped once it reaches the top; one below an
        # open event stays hidden until then
        while active and active[0][1] <= t0:
            heapq.heappop(active)
        if active:
            out.append((t0, t1, active[0][2]))
    return out


def _label_gaps(gaps, host):
    """Split each idle gap by the host timeline: ``[(start, end, name)]``."""
    timeline = host_timeline(host)
    out = []
    j = 0
    for s, e in gaps:
        t = s
        while j < len(timeline) and timeline[j][1] <= s:
            j += 1
        i = j
        while t < e:
            if i < len(timeline) and timeline[i][0] < e:
                a, b, name = timeline[i]
                if a > t:
                    out.append((t, a, NO_HOST_EVENT))
                    t = a
                end = min(b, e)
                if end > t:
                    out.append((t, end, name))
                    t = end
                i += 1
            else:
                out.append((t, e, NO_HOST_EVENT))
                t = e
    return out


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def _events(plane):
    for line in plane.lines:
        for ev in line.events:
            yield line.name, ev


def reduce_trace(path: str, window_name: str = "bench.window",
                 top: int = 10) -> dict:
    """Reduce the trace at ``path`` (a file or a profiler log directory).

    Returns ``busy_s`` (mean over device planes), ``window_s``,
    ``idle_share`` (0..1), ``device_ops`` (the ``top`` operations by summed
    device time, ``[name, seconds]``, over all device planes), ``idle_gaps``
    (the ``top`` host-event names by summed idle seconds), ``devices``."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    pd = ProfileData.from_file(path)
    dev_intervals = []                 # one list per device plane
    host = []                          # (start, end, name) of host events
    window = None
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            iv = []
            for line_name, ev in _events(plane):
                if line_name == OPS_LINE and ev.duration_ns > 0:
                    iv.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                               ev.name))
            dev_intervals.append(iv)
        elif plane.name.startswith("/host:"):
            for _, ev in _events(plane):
                if ev.duration_ns <= 0:
                    continue
                if ev.name == window_name:
                    window = (ev.start_ns, ev.start_ns + ev.duration_ns)
                else:
                    host.append((ev.start_ns, ev.start_ns + ev.duration_ns,
                                 ev.name))
    if not dev_intervals or not any(dev_intervals):
        raise ValueError(f"{path}: no device operations in the trace")
    if window is None:
        flat = [iv for ivs in dev_intervals for iv in ivs]
        window = (min(s for s, _, _ in flat), max(e for _, e, _ in flat))
    lo, hi = window
    busy = []
    per_op = defaultdict(float)
    for ivs in dev_intervals:
        busy.append(union_length([(s, e) for s, e, _ in ivs], lo, hi))
        for s, e, name in ivs:
            d = min(e, hi) - max(s, lo)
            if d > 0:
                per_op[name] += d
    window_ns = hi - lo
    last = max(min(e, hi) for s, e, _ in dev_intervals[0] if s < hi)
    if hi - last > max(0.1 * window_ns, 0.25e9):
        # the profiler's device buffer filled and dropped the rest
        raise ValueError(f"{path}: device operations stop "
                         f"{(hi - last) / 1e9:.3f} s before the traced "
                         f"window ends; the trace is incomplete")
    busy_ns = sum(busy) / len(busy)
    # gaps of the first device plane, named by the host
    gaps = idle_gaps([(s, e) for s, e, _ in dev_intervals[0]], lo, hi)
    by_name = defaultdict(float)
    for s, e, name in _label_gaps(gaps, host):
        by_name[name] += e - s

    def top_list(d):
        return [[k, v / 1e9] for k, v in
                sorted(d.items(), key=lambda kv: -kv[1])[:top]]

    return {"busy_s": busy_ns / 1e9, "window_s": window_ns / 1e9,
            "idle_share": 1.0 - busy_ns / window_ns,
            "devices": len(dev_intervals),
            "device_ops": top_list(per_op), "idle_gaps": top_list(by_name)}
