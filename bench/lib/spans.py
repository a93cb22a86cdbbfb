"""Reduction of the program's own spans and named scopes in a profiler
trace, on the clock of the device operations.

The program marks each layer boundary of its serving path with a host
span named ``respect.<what>`` (``jax.profiler.TraceAnnotation``) and names
its device stages with ``jax.named_scope`` (``encode``, ``decode``,
``rho_dp``, ``repair``).  This module reads both from the same
``.xplane.pb`` that :mod:`bench.lib.trace` reduces:

* ``idle_by_span``: every idle second of the first chip in the traced
  window, keyed by the innermost ``respect.*`` span open on the serving
  worker's thread at that moment.  The worker's thread is the host line
  that carries ``respect.flush``; spans on any other thread never take a
  gap.  Idle time with the worker in no program span is
  ``unattributed``; before the worker's first recorded span and after
  its last it is ``window-edge`` (the profiler drops a span that opened
  before the trace started or closed after it stopped); gaps shorter
  than ``SMALL_GAP_S`` stay ``between-ops`` as in
  :mod:`bench.lib.trace`.  The values sum to the idle time of the first
  chip.
* ``device_by_scope``: seconds of the first chip per named scope, the
  union of the intervals of the operations whose ``op_name`` lies under
  the scope (a loop and the operations of its body count once); the rest
  is ``unscoped``.  The whole-decode kernel is the jitted
  ``decode_batch``, under ``decode``.
* ``submit_s``: the durations of the ``respect.submit`` spans, which run
  on the callers' threads (validation, the first content hash, dedup,
  enqueue), in time order.  They take no gap, since the worker's line
  alone does; a traced run logs their count, total and p95 beside the
  two dicts, which sizes the host work a request costs before it reaches
  the worker.

A trace of a program without these spans or scopes gives empty dicts, and
the readers of the metrics built on them read nothing.
"""

from __future__ import annotations

import functools
import glob
import json
import re
from pathlib import Path

import numpy as np

from bench.lib.cell import log
from bench.lib.spec import BENCH
from bench.lib.trace import (SMALL_GAP_S, WINDOW_END, WINDOW_START,
                             _device_union)

PROGRAM_PREFIX = "respect."
WORKER_SPAN = "respect.flush"
SUBMIT_SPAN = "respect.submit"
UNATTRIBUTED = "unattributed"
WINDOW_EDGE = "window-edge"
BETWEEN_OPS = "between-ops"
UNSCOPED = "unscoped"
# op_name path component -> named scope; the whole-decode kernel is the
# jitted ``decode_batch`` and carries no scope of its own
SCOPES = {"encode": "encode", "decode": "decode", "decode_batch": "decode",
          "rho_dp": "rho_dp", "repair": "repair"}
DEVICE_PLANE = "/device:TPU:0"
OP_NAME_STAT = "tf_op"      # event-metadata stat that holds the op_name

_WRAPPED = re.compile(r"^(?:[\w.-]+\()*([\w.-]+)\)*$")


def scope_of(op_name: str) -> str:
    """Innermost named scope of an ``op_name`` path such as
    ``jit(batched)/vmap(repair)/while/body/gather`` -> ``repair``."""
    found = UNSCOPED
    for part in op_name.split("/"):
        m = _WRAPPED.match(part)
        if m and m.group(1) in SCOPES:
            found = SCOPES[m.group(1)]
    return found


def op_scopes(ops) -> list[str]:
    """Scope of each operation (name, start, end, op_name).  An operation
    whose metadata names no scope, as a ``while`` loop's does not, takes
    the one scope of the operations that run inside its interval."""
    cache: dict[str, str] = {}
    scopes = []
    for _, _, _, op_name in ops:
        if op_name not in cache:
            cache[op_name] = scope_of(op_name)
        scopes.append(cache[op_name])
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    for at, i in enumerate(order):
        if scopes[i] != UNSCOPED:
            continue
        end, inner = ops[i][2], set()
        for j in range(at + 1, len(order)):
            k = order[j]
            if ops[k][1] >= end:
                break
            if ops[k][2] <= end and scopes[k] != UNSCOPED:
                inner.add(scopes[k])
        if len(inner) == 1:
            scopes[i] = inner.pop()
    return scopes


def innermost(spans: list[tuple[str, float, float]]):
    """Flatten the nested spans (name, start, end) of one thread to
    (start, end, name) pieces of the innermost open span, in time order.
    A child that outlasts its parent by clock rounding is clipped."""
    pieces: list[tuple[float, float, str]] = []
    stack: list[tuple[float, str]] = []     # (end, name), outermost first
    t = 0.0

    def pop_until(when: float) -> None:
        nonlocal t
        while stack and stack[-1][0] <= when:
            end, name = stack.pop()
            if end > t:
                pieces.append((t, end, name))
                t = end

    for name, s, e in sorted(spans, key=lambda x: (x[1], -x[2])):
        pop_until(s)
        if stack:
            if s > t:
                pieces.append((t, s, stack[-1][1]))
            e = min(e, stack[-1][0])
        stack.append((e, name))
        t = s
    pop_until(float("inf"))
    return pieces


def worker_line(lines: dict):
    """Key of the host line that spends most time in ``respect.flush``."""
    best, most = None, 0.0
    for key, spans in lines.items():
        t = sum(e - s for n, s, e in spans if n == WORKER_SPAN)
        if t > most:
            best, most = key, t
    return best


def reduce_program(device_ops, lines: dict, window: tuple[float, float]):
    """``device_ops``: the first chip's events (name, start, end, op_name);
    ``lines``: host line key -> its ``respect.*`` spans (name, start, end);
    ``window``: (start, end); all in seconds on one clock.

    Returns ``idle_by_span`` and ``device_by_scope`` (see the module doc)
    with the ``window_s`` and ``busy_s`` (of the first chip) they are
    shares of, or None when no device operation falls in the window."""
    w0, w1 = window
    ops = list(device_ops)
    busy = _device_union(((n, s, e) for n, s, e, _ in ops), w0, w1, {})
    if not busy:
        return None
    groups: dict[str, list] = {}
    for (n, s, e, _), scope in zip(ops, op_scopes(ops)):
        groups.setdefault(scope, []).append((n, s, e))
    by_scope = {scope: sum(e - s for s, e in
                           _device_union(evs, w0, w1, {}))
                for scope, evs in groups.items()}

    worker = worker_line(lines)
    pieces = innermost(lines[worker]) if worker is not None else []
    # the profiler keeps a span only if it opens and closes inside the
    # trace: before the worker's first kept span and after its last, it
    # was in spans cut by the window's edges
    first, last = (pieces[0][0], pieces[-1][1]) if pieces else (w1, w1)
    edge = WINDOW_EDGE if pieces else UNATTRIBUTED
    idle: dict[str, float] = {}

    def add(label: str, dt: float) -> None:
        if dt > 0:
            idle[label] = idle.get(label, 0.0) + dt

    def add_none(a: float, b: float) -> None:
        add(edge, min(b, first) - a)
        add(UNATTRIBUTED, min(b, last) - max(a, first))
        add(edge, b - max(a, last))

    j = 0
    t = w0
    for s, e in busy + [(w1, w1)]:
        g0, g1 = t, s
        t = max(t, e)
        if g1 <= g0:
            continue
        if g1 - g0 < SMALL_GAP_S:
            add(BETWEEN_OPS, g1 - g0)
            continue
        while j < len(pieces) and pieces[j][1] <= g0:
            j += 1
        cur, k = g0, j
        while k < len(pieces) and pieces[k][0] < g1:
            p0, p1, name = pieces[k]
            add_none(cur, min(p0, g1))
            hi = min(p1, g1)
            add(name, hi - max(p0, cur))
            cur = max(cur, hi)
            k += 1
        add_none(cur, g1)
    submits = sorted((s, e - s) for spans in lines.values()
                     for n, s, e in spans if n == SUBMIT_SPAN)
    return {"window_s": w1 - w0, "busy_s": sum(e - s for s, e in busy),
            "idle_by_span": idle, "device_by_scope": by_scope,
            "submit_s": [d for _, d in submits]}


def submit_summary(durations) -> dict | None:
    """Count, total seconds and p95 microseconds of the ``respect.submit``
    spans; None where there are none."""
    if not durations:
        return None
    return {"n": len(durations), "total_s": float(sum(durations)),
            "p95_us": float(np.percentile(durations, 95) * 1e6)}


def _varint(buf, i: int) -> tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf):
    """(field number, value) of each field of a serialized protobuf
    message; a length-delimited value is a memoryview of its bytes."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield key >> 3, v


def op_names(path: str, plane: str = DEVICE_PLANE) -> dict[str, str]:
    """Event name -> op_name path, from the event metadata of the device
    plane of an ``.xplane.pb`` (``jax.profiler.ProfileData`` gives the
    events but not their metadata).  The event name is the instruction's
    HLO text: short names such as ``while.38`` repeat across programs.
    Reads the fields of the XSpace schema
    (``tsl/profiler/protobuf/xplane.proto``) that lead there:
    XSpace.planes = 1; XPlane.name = 2, .event_metadata = 4 and
    .stat_metadata = 5 (maps: key = 1, value = 2); XEventMetadata.name = 2,
    .stats = 5; XStatMetadata.name = 2; XStat.metadata_id = 1,
    .str_value = 5, .ref_value = 7."""
    data = memoryview(Path(path).read_bytes())
    for f, xplane in _fields(data):
        if f != 1:
            continue
        name, events, stat_names = None, [], {}
        for pf, v in _fields(xplane):
            if pf == 2:
                name = bytes(v).decode()
                if name != plane:
                    break
            elif pf == 4:
                events.append(v)
            elif pf == 5:
                entry = dict(_fields(v))
                meta = dict(_fields(entry.get(2, b"")))
                stat_names[entry.get(1, 0)] = bytes(meta.get(2, b"")).decode()
        if name != plane:
            continue
        stat_id = {n: k for k, n in stat_names.items()}.get(OP_NAME_STAT)
        out = {}
        for entry in events:
            meta = _fields(dict(_fields(entry)).get(2, b""))
            event, op_name = "", ""
            for mf, v in meta:
                if mf == 2:
                    event = bytes(v).decode()
                elif mf == 5:
                    stat = dict(_fields(v))
                    if stat.get(1) == stat_id:
                        op_name = (bytes(stat[5]).decode() if 5 in stat
                                   else stat_names.get(stat.get(7), ""))
            if event and op_name:
                out[event] = op_name
        return out
    return {}


def newest_trace(trace_dir: Path) -> str | None:
    """The newest ``.xplane.pb`` under ``trace_dir``, if any."""
    files = sorted(glob.glob(str(Path(trace_dir) / "**" / "*.xplane.pb"),
                             recursive=True))
    return files[-1] if files else None


def read_program_trace(path: str) -> dict | None:
    """:func:`reduce_program` over the ``.xplane.pb`` file ``path``; None
    when it has no window marks or no operation on the first chip."""
    import jax
    pd = jax.profiler.ProfileData.from_file(path)
    marks: dict[str, list[float]] = {WINDOW_START: [], WINDOW_END: []}
    lines: dict = {}
    device = None
    for p, plane in enumerate(pd.planes):
        if plane.name.startswith("/host:"):
            for i, line in enumerate(plane.lines):
                for ev in line.events:
                    if ev.name in marks:
                        marks[ev.name].append(ev.start_ns * 1e-9)
                    elif ev.name.startswith(PROGRAM_PREFIX):
                        lines.setdefault((p, i), []).append(
                            (ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9))
        elif plane.name == DEVICE_PLANE:
            for line in plane.lines:
                if line.name == "XLA Ops":
                    device = [(ev.name, ev.start_ns * 1e-9, ev.end_ns * 1e-9)
                              for ev in line.events]
    if not marks[WINDOW_START] or not marks[WINDOW_END] or not device:
        return None
    names = op_names(path)
    device = [(n, s, e, names.get(n, "")) for n, s, e in device]
    return reduce_program(device, lines, (min(marks[WINDOW_START]),
                                          max(marks[WINDOW_END])))


@functools.lru_cache(maxsize=1)
def _reduce_logged(path: str, mtime_ns: int) -> dict | None:
    red = read_program_trace(path)
    if red is not None:
        log("idle by program span: " + json.dumps(red["idle_by_span"]))
        log("device by scope: " + json.dumps(red["device_by_scope"]))
        submit = submit_summary(red["submit_s"])
        if submit is not None:
            log("respect.submit on the callers' threads: "
                + json.dumps(submit))
    return red


def program_record(rec: dict) -> dict | None:
    """:func:`read_program_trace` of the trace a traced run (``rec`` is
    its per-layer record) left in the harness's trace directory, read
    once and logged on standard error; None for an untraced run."""
    path = newest_trace(BENCH / ".cache" / "trace")
    if rec.get("trace") is None or path is None:
        return None
    return _reduce_logged(path, Path(path).stat().st_mtime_ns)


def idle_share(rec: dict, names) -> float | None:
    """Idle seconds under the spans ``names`` (a name ending in ``*`` is a
    prefix) over the traced window, in %; None where the worker's line
    carries no program span."""
    red = program_record(rec)
    if red is None or not set(red["idle_by_span"]) - {
            UNATTRIBUTED, WINDOW_EDGE, BETWEEN_OPS}:
        return None
    t = sum(v for k, v in red["idle_by_span"].items()
            if any(k.startswith(n[:-1]) if n.endswith("*") else k == n
                   for n in names))
    return 100.0 * t / red["window_s"]


def device_share(rec: dict, scopes) -> float | None:
    """Device seconds under the named ``scopes`` over the device's busy
    time, in %; None where no operation carries one of them."""
    red = program_record(rec)
    if red is None or not any(s in red["device_by_scope"] for s in scopes):
        return None
    return 100.0 * sum(red["device_by_scope"].get(s, 0.0)
                       for s in scopes) / red["busy_s"]
