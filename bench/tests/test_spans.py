"""The reduction of the program's spans and named scopes on small traces."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.lib import spans as S  # noqa: E402
from bench.lib import trace as T  # noqa: E402

WINDOW = (0.0, 10.0)


def test_gap_goes_to_the_workers_innermost_span():
    # chip busy [0, 1] and [6, 10]: one gap [1, 6].  The worker packs in
    # [1, 4] (closure inside it in [2, 3]), dispatches in [4, 6]; the
    # generator's thread sleeps in bench.wait over the whole gap and
    # another thread is in a program span too.
    ops = [("fusion.1", 0.0, 1.0, "jit(f)/vmap(rho_dp)/add"),
           ("while.2", 6.0, 10.0, "jit(f)/vmap(repair)/while")]
    lines = {
        "worker": [("respect.flush", 0.5, 9.0), ("respect.pack", 1.0, 4.0),
                   ("respect.pack.closure", 2.0, 3.0),
                   ("respect.run", 4.0, 8.0), ("respect.dispatch", 4.0, 6.0)],
        "caller": [("respect.submit", 1.0, 6.0)],
    }
    red = S.reduce_program(ops, lines, WINDOW)
    assert red["idle_by_span"] == pytest.approx({
        "respect.pack": 2.0, "respect.pack.closure": 1.0,
        "respect.dispatch": 2.0})
    # the caller's submit takes no gap but is kept for the log
    assert red["submit_s"] == pytest.approx([5.0])
    # the harness's own reduction gives the same gap to the sleeping thread
    host = [("bench.wait", 0.9, 6.1)]
    old = T.reduce_events([[(n, s, e) for n, s, e, _ in ops]], host, WINDOW)
    assert old["idle_by_host"] == pytest.approx({"wait": 5.0})


@pytest.mark.parametrize("spans, want", [
    # the child wins over its parent, the parent keeps the rest
    ([("respect.flush", 1.0, 9.0), ("respect.lookup", 2.0, 3.0)],
     {"respect.flush": 7.0, "respect.lookup": 1.0, "window-edge": 2.0}),
    # a child that outlasts its parent by clock rounding is clipped
    ([("respect.flush", 1.0, 5.0), ("respect.resolve", 4.0, 5.000001)],
     {"respect.flush": 3.0, "respect.resolve": 1.0, "window-edge": 6.0}),
    # siblings, a grandchild, time in no span at all between spans, and
    # before the first and after the last span (cut by the trace's edges)
    ([("respect.flush", 1.0, 6.0), ("respect.pack", 1.0, 3.0),
      ("respect.pack.embed", 1.5, 2.5), ("respect.run", 3.0, 6.0),
      ("respect.wait_request", 7.0, 8.0)],
     {"respect.pack": 1.0, "respect.pack.embed": 1.0, "respect.run": 3.0,
      "respect.wait_request": 1.0, "unattributed": 1.0,
      "window-edge": 3.0}),
], ids=["innermost", "clipped", "siblings"])
def test_innermost_span_and_idle_total(spans, want):
    # the chip is idle over the whole window but for one short gap's
    # worth of work at the end
    ops = [("fusion.1", 10.0 - 1e-6, 10.0, "")]
    red = S.reduce_program(ops, {"w": spans}, WINDOW)
    got = red["idle_by_span"]
    assert got == pytest.approx(want, abs=1e-5)
    assert sum(got.values()) == pytest.approx(
        red["window_s"] - red["busy_s"])


def test_submit_spans_of_every_caller_line_are_summarised():
    ops = [("a", 0.0, 10.0, "")]
    lines = {"worker": [("respect.flush", 0.0, 10.0)],
             "c1": [("respect.submit", 1.0, 1.001),
                    ("respect.submit", 3.0, 3.004)],
             "c2": [("respect.submit", 2.0, 2.002)]}
    red = S.reduce_program(ops, lines, WINDOW)
    assert red["submit_s"] == pytest.approx([0.001, 0.002, 0.004])
    got = S.submit_summary(red["submit_s"])
    assert got["n"] == 3
    assert got["total_s"] == pytest.approx(0.007)
    assert got["p95_us"] == pytest.approx(3800.0)


def test_short_gaps_stay_between_ops_and_other_lines_never_count():
    ops = [("a", 0.0, 1.0, ""), ("b", 1.0 + 1e-6, 10.0, "")]
    lines = {"w": [("respect.flush", 0.0, 10.0)],
             "x": [("respect.flush", 0.0, 1.0), ("respect.pack", 0.0, 1.0)]}
    red = S.reduce_program(ops, lines, WINDOW)
    assert red["idle_by_span"] == pytest.approx({"between-ops": 1e-6})
    assert S.worker_line(lines) == "w"


def test_no_program_spans_leave_every_gap_unattributed():
    ops = [("a", 2.0, 3.0, "")]
    red = S.reduce_program(ops, {}, WINDOW)
    assert red["idle_by_span"] == pytest.approx({"unattributed": 9.0})
    assert red["submit_s"] == [] and S.submit_summary([]) is None
    assert S.reduce_program([("a", 11.0, 12.0, "")], {}, WINDOW) is None


def test_device_by_scope_sums_ops_by_scope_counting_nested_once():
    ops = [
        # a loop carries no op_name of its own: it takes the one scope of
        # the ops inside it, and overlapping ops count once
        ("while.38", 1.0, 3.0, ""),
        ("fusion.4", 1.5, 2.0, "jit(b)/vmap(repair)/while/body/gather"),
        ("fusion.5", 3.0, 3.5, "jit(b)/vmap(rho_dp)/reduce_min"),
        ("while.53", 4.0, 5.0, "jit(b)/jit(decode_pack)/vmap(encode)/while"),
        ("decode_batch.1", 5.0, 7.0,
         "jit(b)/jit(decode_pack)/jit(decode_batch)/pallas_call:"),
        ("fusion.9", 7.0, 7.25, "jit(b)/vmap(decode)/while/body/tanh"),
        ("copy.1", 8.0, 8.5, "jit(b)/copy"),
        # a loop over ops of two scopes keeps none
        ("while.60", 9.0, 9.5, ""),
        ("fusion.10", 9.0, 9.1, "jit(b)/vmap(encode)/add"),
        ("fusion.11", 9.2, 9.3, "jit(b)/vmap(decode)/add"),
    ]
    assert S.op_scopes(ops) == [
        "repair", "repair", "rho_dp", "encode", "decode", "decode",
        "unscoped", "unscoped", "encode", "decode"]
    red = S.reduce_program(ops, {}, WINDOW)
    assert red["device_by_scope"] == pytest.approx({
        "repair": 2.0, "rho_dp": 0.5, "encode": 1.1, "decode": 2.35,
        "unscoped": 1.0})
    assert red["busy_s"] == pytest.approx(6.75)


@pytest.mark.parametrize("op_name, scope", [
    ("jit(batched)/vmap(repair)/while/body/while/body/closed_call/gather",
     "repair"),
    ("jit(batched)/vmap(rho_dp)/jit(cumsum)", "rho_dp"),
    ("jit(batched)/vmap(decode)/while", "decode"),
    ("transpose(jvp(encode))/dot_general", "encode"),
    ("jit(batched)/vmap(one)/repairs", "unscoped"),
    ("", "unscoped"),
])
def test_scope_of(op_name, scope):
    assert S.scope_of(op_name) == scope


# the metric readers on hand-made reductions
def _rec(monkeypatch, red):
    monkeypatch.setattr(S, "program_record", lambda rec: red)
    return {"trace": {}}


IDLE = {"respect.pack": 0.1, "respect.pack.closure": 0.2,
        "respect.pack.h2d": 0.05, "respect.lookup": 0.03,
        "respect.results": 0.02, "respect.collect": 0.01,
        "respect.resolve": 0.04, "respect.flush": 0.05,
        "respect.run": 0.1, "respect.wait_request": 0.2,
        "between-ops": 0.01, "unattributed": 0.01}
RED = {"window_s": 2.0, "busy_s": 0.5, "idle_by_span": IDLE,
       "device_by_scope": {"repair": 0.3, "rho_dp": 0.1, "decode": 0.1}}


@pytest.mark.parametrize("cell", ["open", "closed"])
@pytest.mark.parametrize("metric, want", [
    ("idle_in_pack_share", 17.5), ("idle_in_facade_share", 2.5),
    ("idle_in_frontend_share", 5.0), ("assign_device_share", 80.0)])
def test_metric_readers(monkeypatch, metric, want, cell):
    from bench.lib.spec import load_module
    reader = load_module("metrics", f"{metric}.{cell}")
    assert reader.read(_rec(monkeypatch, RED)) == pytest.approx(want)
    # a program without the spans and scopes reads nothing
    bare = {"window_s": 2.0, "busy_s": 0.5,
            "idle_by_span": {"unattributed": 1.3, "window-edge": 0.1,
                             "between-ops": 0.1},
            "device_by_scope": {"unscoped": 0.4, "decode": 0.1}}
    assert reader.read(_rec(monkeypatch, bare)) is None
    assert reader.read(_rec(monkeypatch, None)) is None


def test_untraced_run_reads_nothing():
    assert S.program_record({"trace": None}) is None


def test_op_names_from_event_metadata(tmp_path):
    """The op_name of each device operation is read from the event metadata
    of the device plane; a hand-made XSpace holds one with a string stat,
    one by reference, and a host plane that is skipped."""
    def varint(x):
        out = bytearray()
        while True:
            b, x = x & 0x7F, x >> 7
            out.append(b | (0x80 if x else 0))
            if not x:
                return bytes(out)

    def field(num, value):
        if isinstance(value, int):
            return varint(num << 3) + varint(value)
        value = value.encode() if isinstance(value, str) else value
        return varint(num << 3 | 2) + varint(len(value)) + value

    def entry(key, value):
        return field(1, key) + field(2, value)

    stat_meta = field(5, entry(7, field(1, 7) + field(2, "tf_op"))) + \
        field(5, entry(9, field(1, 9) + field(2, "jit(f)/vmap(repair)/x")))
    ev = field(4, entry(1, field(1, 1) + field(2, "%while.3 = ...") +
                        field(4, "while.3") +
                        field(5, field(1, 7) + field(7, 9)))) + \
        field(4, entry(2, field(1, 2) + field(2, "%fusion.1 = ...") +
                       field(4, "fusion.1") +
                       field(5, field(1, 7) + field(5, "jit(f)/rho_dp/y"))))
    device = field(1, 5) + field(2, S.DEVICE_PLANE) + \
        field(3, field(2, "XLA Ops")) + ev + stat_meta
    host = field(2, "/host:CPU") + ev
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(field(1, host) + field(1, device) + field(3, "w"))
    assert S.op_names(str(path)) == {"%while.3 = ...": "jit(f)/vmap(repair)/x",
                                     "%fusion.1 = ...": "jit(f)/rho_dp/y"}
    assert S.op_names(str(path), plane="/device:TPU:1") == {}


def test_cpu_trace_reads_nothing(tmp_path):
    """A served request under the profiler on the CPU: program spans but
    no chip, so the reduction reads nothing."""
    import jax
    import numpy as np

    from repro.core import RespectScheduler, sample_dag
    from repro.serving import SchedulerService
    sched = RespectScheduler.init(seed=0, hidden=16)
    g = sample_dag(np.random.default_rng(0), n=10)
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW_START):
            pass
        with SchedulerService(sched, max_batch=2, max_wait_ms=1) as svc:
            svc.submit(g, 4).result(timeout=60)
        with jax.profiler.TraceAnnotation(T.WINDOW_END):
            pass
    assert S.read_program_trace(S.newest_trace(tmp_path)) is None
    assert S.newest_trace(tmp_path / "empty") is None
