"""The host watch times the collector's pauses between start and stop."""

import gc
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT)]

from bench.lib.hostwatch import HostWatch  # noqa: E402


def test_records_a_full_collection_and_detaches():
    w = HostWatch()
    w.start()
    gc.collect()
    w.stop()
    assert any(g == 2 and s >= 0.0 for g, s in w.pauses)
    assert w._on_gc not in gc.callbacks
    assert f"{sum(g == 2 for g, _ in w.pauses)} full" in w.describe()
    n = len(w.pauses)
    gc.collect()
    assert len(w.pauses) == n
