"""Reduction of a profiler trace to device busy time, idle share and the
breakdown, on the host clock of the benchmark's own spans.

The benchmark writes its spans into the profiler's trace with
``jax.profiler.TraceAnnotation`` under names that start with ``bench/``
(``bench/window`` around the measured window, ``bench/partition``,
``bench/init_labels``, ... around each call into the system).
The device's operations are the events of the ``XLA Ops`` line of each
``/device:`` plane.  Busy time is the union of those intervals; a device is
idle wherever no operation runs on it.  Every time below is in seconds, and
every device quantity is averaged over the devices traced.
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Callable, Dict, List, Tuple

Interval = Tuple[float, float]
SPAN_PREFIX = "bench/"
WINDOW = "bench/window"


def tpu_ops(plane_name: str, line_name: str) -> bool:
    """The device operations of a TPU trace."""
    return plane_name.startswith("/device:") and line_name == "XLA Ops"


def cpu_ops(plane_name: str, line_name: str) -> bool:
    """XLA's CPU client threads, which stand for the device in a trace
    recorded on the CPU (the reduction's own tests)."""
    return plane_name == "/host:CPU" and line_name.startswith("tf_XLA")


def load(trace_dir: str):
    """The profile of the newest ``.xplane.pb`` under ``trace_dir``."""
    import jax
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return jax.profiler.ProfileData.from_file(max(paths,
                                                  key=os.path.getmtime))


def op_name(event_name: str) -> str:
    """``fusion.101`` of an HLO event name ``%fusion.101 = f32[...] ...``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(profile, keep: Callable[[str, str], bool]) -> Dict[str, list]:
    """{device: [(op name, start_s, end_s)]} of the events with a duration
    on the lines ``keep`` selects."""
    out: Dict[str, list] = collections.defaultdict(list)
    for plane in profile.planes:
        for line in plane.lines:
            if not keep(plane.name, line.name):
                continue
            dev = plane.name if plane.name.startswith("/device:") \
                else "cpu"
            for e in line.events:
                if e.duration_ns > 0:
                    s = e.start_ns * 1e-9
                    out[dev].append((op_name(e.name), s,
                                     s + e.duration_ns * 1e-9))
    return dict(out)


def host_spans(profile) -> List[tuple]:
    """The benchmark's spans ``(name, start_s, end_s)`` from host planes."""
    spans = []
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(SPAN_PREFIX):
                    s = e.start_ns * 1e-9
                    spans.append((e.name, s, s + e.duration_ns * 1e-9))
    return sorted(spans, key=lambda x: x[1])


def leaves(events: List[tuple]) -> List[tuple]:
    """The events that contain no other event: a ``while`` op spans the
    ops of its body, and only those are the work."""
    events = sorted(events, key=lambda x: (x[1], -x[2]))
    out = []
    for i, ev in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[1] >= ev[2] or nxt[2] > ev[2]:
            out.append(ev)
    return out


def union(intervals) -> List[Interval]:
    """Sorted, disjoint union of ``(start, end)`` intervals."""
    merged: List[list] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def covered(merged: List[Interval], a: float, b: float) -> float:
    """Length of ``[a, b]`` that the disjoint ``merged`` intervals cover."""
    i = bisect.bisect_right(merged, (a, float("inf"))) - 1
    total = 0.0
    for s, e in merged[max(i, 0):]:
        if s >= b:
            break
        total += max(0.0, min(e, b) - max(s, a))
    return total


def reduce(profile, keep: Callable[[str, str], bool] = tpu_ops,
           top: int = 10) -> dict:
    """Busy time, idle share and breakdown over the ``bench/window`` span.

    Returns ``{}`` when the trace holds no window span or no device
    operation, so that every metric read from it is left out.  Otherwise:

    ``window_s``       the window's length;
    ``busy_s``         device busy time in the window (union of its
                       operations), averaged over devices;
    ``busy_in``        {span name: busy time inside those spans};
    ``span_s``         {span name: summed span durations};
    ``span_n``         {span name: number of spans};
    ``device_ops``     the ``top`` operations by summed device time, of
                       the operations that contain no other;
    ``idle_gaps``      idle device time by the benchmark span the host
                       was in (``host`` outside every span), the ``top``
                       largest; the spans inside the window follow one
                       another and do not nest.
    """
    spans = host_spans(profile)
    windows = [s for s in spans if s[0] == WINDOW]
    by_dev = _events(profile, keep)
    if not windows or not by_dev:
        return {}
    _, w0, w1 = windows[-1]
    inner = [s for s in spans if s[0] != WINDOW and s[2] > w0 and s[1] < w1]
    ndev = len(by_dev)
    busy = 0.0
    busy_in: Dict[str, float] = collections.defaultdict(float)
    op_time: Dict[str, float] = collections.defaultdict(float)
    idle: Dict[str, float] = collections.defaultdict(float)
    for evs in by_dev.values():
        merged = union((s, e) for _, s, e in evs)
        busy += covered(merged, w0, w1) / ndev
        for name, s, e in inner:
            busy_in[name[len(SPAN_PREFIX):]] += covered(merged, s, e) / ndev
        for name, s, e in leaves(evs):
            op_time[name] += max(0.0, min(e, w1) - max(s, w0)) / ndev
        spent = 0.0
        for name, s, e in inner:
            s, e = max(s, w0), min(e, w1)
            gap = (e - s) - covered(merged, s, e)
            idle[name[len(SPAN_PREFIX):]] += gap / ndev
            spent += gap
        idle["host"] += ((w1 - w0) - covered(merged, w0, w1) - spent) / ndev
    span_s: Dict[str, float] = collections.defaultdict(float)
    span_n: Dict[str, int] = collections.defaultdict(int)
    for name, s, e in inner:
        span_s[name[len(SPAN_PREFIX):]] += e - s
        span_n[name[len(SPAN_PREFIX):]] += 1
    rank = lambda d: [[k, v] for k, v in sorted(d.items(),
                                                key=lambda kv: -kv[1])[:top]
                      if v > 0]
    return {"window_s": w1 - w0, "busy_s": busy, "busy_in": dict(busy_in),
            "span_s": dict(span_s), "span_n": dict(span_n),
            "device_ops": rank(op_time), "idle_gaps": rank(idle)}
