"""Traffic files name their arrival process and pool kind, which are
found by name; a key that no part reads is refused."""

import json
import sys
import time
from pathlib import Path

import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.arrivals import gamma, poisson  # noqa: E402
from bench.lib.spec import SpecError, load_module  # noqa: E402
from bench.pools import zipf  # noqa: E402

TRAFFIC = sorted((ROOT / "bench" / "traffic").glob("*.json"))


def _traffic(name: str) -> dict:
    return json.loads((ROOT / "bench" / "traffic" / f"{name}.json").read_text())


@pytest.mark.parametrize("path", TRAFFIC, ids=[p.stem for p in TRAFFIC])
def test_every_traffic_file_resolves(path):
    traffic = json.loads(path.read_text())
    runner = load_module("runners", traffic["runner"])
    parts = runner.resolve(traffic, path.name)
    assert parts["arrivals"].LOOP in ("open", "closed")


@pytest.mark.parametrize("change", [
    {"popularity": "zipf"},        # a key nothing reads
    {"pool": "hot"},               # a pool kind with no module
    {"arrivals": "bursty"},        # an arrival process with no module
    {"pool": "zipf"},              # zipf without its keys
])
def test_unread_or_unknown_traffic_is_refused(change):
    traffic = dict(_traffic("table1-open80"), **change)
    runner = load_module("runners", traffic["runner"])
    with pytest.raises(SpecError):
        runner.resolve(traffic, "test")


def test_poisson_offers_the_same_load_every_second():
    t = {"rate_per_s": 36.0, "block_s": 1.0}
    runs = [poisson.requests(t, seed, 50.0) for seed in (1, 2**31 + 7)]
    for n, dues in runs:
        assert n == 1800 and len(dues) == n
        assert np.allclose(dues[::36], np.arange(50.0))   # 36 a second
        assert dues[-1] < 50.0
    a, b = (np.diff(d) for _, d in runs)
    assert not np.array_equal(a, b)
    for blk in range(0, 1764, 36):                    # the same gaps
        assert np.allclose(np.sort(a[blk:blk + 36]), np.sort(b[blk:blk + 36]))


def test_gamma_arrivals_are_burstier_than_poisson():
    t = {"rate_per_s": 36.0, "block_s": 10.0}
    _, p = poisson.requests(t, 3, 50.0)
    n, g = gamma.requests(dict(t, shape=0.25), 3, 50.0)
    cv = [np.std(np.diff(d)) / np.mean(np.diff(d)) for d in (p, g)]
    assert n == len(p) and cv[1] > 1.5 * cv[0]
    assert abs(g[-1] - p[-1]) < 1.0


def test_zipf_picks_a_static_hot_set_from_the_seed():
    t = {"pool_size": 4096, "zipf_theta": 0.99}
    size, a = zipf.plan(t, 11, 100_000)
    _, a2 = zipf.plan(t, 11, 100_000)
    _, b = zipf.plan(t, 12, 100_000)
    assert size == 4096 and np.array_equal(a, a2)
    assert a.min() >= 0 and a.max() < size
    counts = np.sort(np.bincount(a, minlength=size))[::-1]
    assert 0.75 < counts[:1024].sum() / len(a) < 0.9
    assert np.argmax(np.bincount(a)) != np.argmax(np.bincount(b))


def test_zipf_cell_is_served_from_the_cache_and_correct():
    from bench.lib import device
    from bench.lib.spec import load_cell
    parts = load_cell("synth30-k4.closed64")
    traffic = dict(_traffic("synth30-zipf-closed64"), pool_size=256,
                   max_per_s=4000, assign_sample=64, check_sample=2000)
    parts.update(parts["runner"].resolve(traffic, "test"), traffic=traffic)
    parts["config"]["service"]["max_batch"] = 4
    run = parts["runner"].Run(parts, 2**32 + 5, 1.0, False,
                              time.perf_counter(), log=lambda m: None)
    counter = device.CompileCounter()
    run.setup(counter)
    run.window(counter, None)
    out = run.outcomes()
    assert out["failed"] == 0 and len(out["ok"]) == len(out["asked"])
    assert run.stats.cache_hits + run.stats.dedup_hits > 0
    run.release_program()
    numbers, _ = run.check(out)
    limits = parts["config"]["correct"]
    assert all(v <= limits[k] for k, v in numbers.items()), numbers


class _G:
    def __init__(self, n, widest):
        self.n = n
        self.children = [[0] * widest]


class _Sched:
    def __init__(self):
        self.calls = []

    def schedule_many(self, graphs, *args, **kw):
        self.calls.append(graphs)


def test_warm_runs_each_program_once_as_the_pool_grows():
    from bench.lib.warm import Warmer, program_calls
    pool = [_G(30, 3), _G(30, 5), _G(32, 2)]
    keys = set(program_calls(pool, 4))
    assert keys == {(32, b, w, d) for b in (1, 2, 4)
                    for w, d in ((4, False), (8, False), (4, True))}
    sched = _Sched()
    warmer = Warmer(sched, 4, 4, None)
    assert warmer.warm(pool[:1]) == 3
    assert warmer.warm(pool) == 6
    assert warmer.warm(pool) == 0 and len(sched.calls) == 9
