"""The trace reduction on small recorded traces."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.lib import trace as T  # noqa: E402


def test_busy_union_gaps_and_labels():
    # one chip: ops at [1, 3], [2, 4] (overlap) and [6, 7] in window [0, 10]
    ops = [[("fusion.1", 1.0, 3.0), ("decode_kernel", 2.0, 4.0),
            ("fusion.1", 6.0, 7.0), ("outside", 11.0, 12.0)]]
    host = [("bench.window_start", 0.0, 0.0), ("bench.flush", 0.5, 4.5),
            ("bench.wait", 4.5, 9.5), ("other", 0.0, 10.0)]
    red = T.reduce_events(ops, host, (0.0, 10.0))
    assert red["busy_s"] == pytest.approx(4.0)          # [1, 4] + [6, 7]
    assert red["window_s"] == pytest.approx(10.0)
    assert red["ops"] == pytest.approx({"fusion.1": 3.0, "decode_kernel": 2.0})
    # gaps longest first: [7, 10] wait, [4, 6] wait, [0, 1] flush
    assert [(round(a, 6), round(b, 6), lab) for a, b, lab in red["gaps"]] == [
        (7.0, 10.0, "wait"), (4.0, 6.0, "wait"), (0.0, 1.0, "flush")]
    assert red["idle_by_host"] == pytest.approx({"wait": 5.0, "flush": 1.0})
    bd = T.breakdown(red, top=2)
    assert bd["device_ops"] == [["fusion.1", 3.0], ["decode_kernel", 2.0]]
    assert bd["idle_gaps"] == [["wait", 3.0], ["wait", 2.0]]


def test_busy_is_mean_over_chips_and_clipped_to_window():
    ops = [[("a", -1.0, 2.0)], [("a", 1.0, 5.0), ("b", 9.0, 12.0)]]
    red = T.reduce_events(ops, [], (0.0, 10.0))
    assert red["busy_s"] == pytest.approx((2.0 + 5.0) / 2)
    assert red["gaps"][0][2] == "none"


def test_trace_without_chip_reads_nothing(tmp_path):
    import jax
    import jax.numpy as jnp
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((64, 64))
    f(x).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        with jax.profiler.TraceAnnotation(T.WINDOW_START):
            pass
        f(x).block_until_ready()
        with jax.profiler.TraceAnnotation(T.WINDOW_END):
            pass
    assert T.read_trace(tmp_path, 1) is None
    assert T.read_trace(tmp_path / "empty", 1) is None
