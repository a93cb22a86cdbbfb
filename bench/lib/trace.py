"""Reduction of a JAX profiler trace to device busy time, idle gaps and
time per device operation.

The traced window runs from the host mark ``bench.window_start`` to
``bench.window_end`` (``jax.profiler.TraceAnnotation`` marks the harness
makes when it starts and stops the trace).  Device operations are the events of the ``XLA Ops`` line of
each ``/device:TPU:<i>`` plane, clipped to the window.  Busy time is the
union of those intervals, averaged over the chips used; an idle gap is a
stretch of the window with no operation on the first chip, named after
the harness's host span (``bench.<what>``) that covers most of it.
"""

from __future__ import annotations

import bisect
import glob
from pathlib import Path

import numpy as np

WINDOW_START = "bench.window_start"
WINDOW_END = "bench.window_end"
HOST_PREFIX = "bench."


def _overlap(a0, a1, b0, b1) -> float:
    return max(0.0, min(a1, b1) - max(a0, b0))


SMALL_GAP_S = 20e-6     # gaps shorter than this lie between operations


def short_name(name: str) -> str:
    """``%fusion.83 = f32[...] fusion(...)`` -> ``fusion.83``."""
    return name.split(" = ", 1)[0].lstrip("%")


def _device_union(events, w0: float, w1: float, ops: dict):
    """Busy intervals of one chip from (name, start, end) events, clipped
    to [w0, w1]; adds seconds per short name to ``ops``."""
    starts, ends = [], []
    for name, s, e in events:
        s, e = max(s, w0), min(e, w1)
        if e <= s:
            continue
        key = short_name(name)
        ops[key] = ops.get(key, 0.0) + (e - s)
        starts.append(s)
        ends.append(e)
    if not starts:
        return []
    order = np.argsort(np.asarray(starts), kind="stable")
    st = np.asarray(starts)[order]
    en = np.maximum.accumulate(np.asarray(ends)[order])
    new = np.empty(len(st), bool)
    new[0] = True
    new[1:] = st[1:] > en[:-1]
    first = np.flatnonzero(new)
    last = np.append(first[1:] - 1, len(st) - 1)
    return list(zip(st[first].tolist(), en[last].tolist()))


def reduce_events(device_ops, host_spans: list[tuple[str, float, float]],
                  window: tuple[float, float]) -> dict:
    """Reduce events (name, start, end), times in seconds on one clock.

    ``device_ops`` holds one event iterable per chip.
    Returns ``busy_s`` (mean over chips), ``window_s``, ``ops`` (seconds
    per operation, all chips), ``gaps`` (start, end, host label) on the
    first chip, longest first, for gaps of at least ``SMALL_GAP_S``, and
    ``idle_by_host`` (idle seconds per host label; the short gaps between
    operations under ``between-ops``)."""
    w0, w1 = window
    ops: dict[str, float] = {}
    busy = [_device_union(evs, w0, w1, ops) for evs in device_ops]
    busy_s = (sum(sum(e - s for s, e in u) for u in busy) / len(busy)
              if busy else 0.0)

    spans = sorted((s, e, n[len(HOST_PREFIX):]) for n, s, e in host_spans
                   if n.startswith(HOST_PREFIX)
                   and n not in (WINDOW_START, WINDOW_END))
    starts = [s for s, _, _ in spans]
    longest = max((e - s for s, e, _ in spans), default=0.0)
    labelled = []
    idle_by_host: dict[str, float] = {}
    t = w0
    for s, e in (busy[0] if busy else []) + [(w1, w1)]:
        g0, g1 = t, s
        t = max(t, e)
        if g1 <= g0:
            continue
        if g1 - g0 < SMALL_GAP_S:
            idle_by_host["between-ops"] = \
                idle_by_host.get("between-ops", 0.0) + (g1 - g0)
            continue
        shares: dict[str, float] = {}
        lo = bisect.bisect_left(starts, g0 - longest)
        hi = bisect.bisect_left(starts, g1)
        for hs, he, label in spans[lo:hi]:
            ov = _overlap(g0, g1, hs, he)
            if ov > 0:
                shares[label] = shares.get(label, 0.0) + ov
        label = max(shares, key=shares.get) if shares else "none"
        labelled.append((g0, g1, label))
        idle_by_host[label] = idle_by_host.get(label, 0.0) + (g1 - g0)
    labelled.sort(key=lambda g: g[0] - g[1])
    return {"busy_s": busy_s, "window_s": w1 - w0, "ops": ops,
            "gaps": labelled, "idle_by_host": idle_by_host}


def _events(line):
    for ev in line.events:
        yield ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9


def read_trace(trace_dir: Path, chips: int) -> dict | None:
    """Reduce the newest ``.xplane.pb`` under ``trace_dir``; None when no
    trace, no window span or no device operation is found."""
    import jax
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not files:
        return None
    pd = jax.profiler.ProfileData.from_file(files[-1])
    host_spans: list[tuple[str, float, float]] = []
    lines = {}
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(HOST_PREFIX):
                        host_spans.append((ev.name, ev.start_ns * 1e-9,
                                           ev.end_ns * 1e-9))
        elif plane.name.startswith("/device:TPU:"):
            tail = plane.name[len("/device:TPU:"):]
            if not tail.isdigit() or int(tail) >= chips:
                continue
            for line in plane.lines:
                if line.name == "XLA Ops":
                    lines[int(tail)] = line
    starts = [s for n, s, _ in host_spans if n == WINDOW_START]
    ends = [s for n, s, _ in host_spans if n == WINDOW_END]
    if not starts or not ends or not lines:
        return None
    red = reduce_events([_events(lines[k]) for k in sorted(lines)],
                        host_spans, (min(starts), max(ends)))
    return red if red["busy_s"] > 0 else None


def breakdown(red: dict, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time and the longest idle gaps, named by the host span."""
    ops = sorted(red["ops"].items(), key=lambda kv: -kv[1])[:top]
    gaps = [[label, g1 - g0] for g0, g1, label in red["gaps"][:top]]
    return {"device_ops": [[n, s] for n, s in ops], "idle_gaps": gaps}
