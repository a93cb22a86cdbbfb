"""Mixed-size, multi-device training engine on the unified padded batch.

The acceptance contract of the refactor:

* training and serving share ONE representation (`PaddedGraphBatch`);
* a mixed-size padded train/eval step matches the per-size unpadded path
  bit-for-bit on rewards, labels and exact-match (CPU);
* the data-parallel step reproduces the single-device params trajectory;
* trainer state (params, baseline, opt state, step, best baseline reward)
  round-trips through the checkpoint manager;
* the sampler's mixed-size bucketed stream is deterministic and its label
  cache keys distinguish solver/budget/system.
"""

import json
import os
import subprocess
import sys
import textwrap
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import DagSampler, PipelineSystem, prefetch, sample_dag
from repro.core.exact import exact_dp
from repro.core.rl import (RLTrainer, _label_cache_key, _policy_rewards,
                           label_graphs, make_eval_fn, make_rollout_fn,
                           pack_graphs)

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture(scope="module")
def sys4():
    return PipelineSystem(n_stages=4)


@pytest.fixture(scope="module")
def mixed_graphs():
    rng = np.random.default_rng(0)
    return [sample_dag(rng, n=int(rng.integers(10, 51)),
                       deg=int(rng.integers(2, 7))) for _ in range(10)]


# --------------------------------------------------------------------- #
# parity: padded mixed-size == per-size unpadded, bit for bit
# --------------------------------------------------------------------- #
def test_mixed_size_padded_matches_unpadded_bitwise(sys4, mixed_graphs):
    """Greedy rollout of ONE mixed-size padded batch vs each graph through
    an unpadded (bucket_n == n) pack: rewards, stage assignments and
    exact-match flags are bit-identical."""
    batch = pack_graphs(mixed_graphs, 4, sys4, label_method="dp")
    params = RLTrainer(n_stages=4, system=sys4, hidden=32, seed=0).params
    roll = make_rollout_fn(4, sys4)
    r_pad, _, _, _, a_pad = roll(params, batch, jax.random.PRNGKey(1))
    la_pad = np.asarray(batch.label_assign)
    for i, g in enumerate(mixed_graphs):
        single = pack_graphs([g], 4, sys4, label_method="dp", pad=False)
        assert single.bucket_n == g.n          # genuinely unpadded
        r1, _, _, _, a1 = roll(params, single, jax.random.PRNGKey(1))
        assert float(r_pad[i]) == float(r1[0]), g.model_name     # bitwise
        assert np.array_equal(np.asarray(a_pad)[i, : g.n],
                              np.asarray(a1)[0]), g.model_name
        assert np.array_equal(la_pad[i, : g.n],
                              np.asarray(single.label_assign)[0])
        # exact-match flag agrees too
        m_pad = bool((np.asarray(a_pad)[i, : g.n] == la_pad[i, : g.n]).all())
        m_one = bool((np.asarray(a1)[0] ==
                      np.asarray(single.label_assign)[0]).all())
        assert m_pad == m_one


def test_sampled_rollout_padded_matches_unpadded(sys4, mixed_graphs):
    """Stochastic decode parity: with the SAME per-graph key, the sampled
    order/reward of a graph is identical padded or unpadded."""
    batch = pack_graphs(mixed_graphs[:4], 4, sys4, label_method="dp")
    params = RLTrainer(n_stages=4, system=sys4, hidden=32, seed=1).params
    keys = jax.random.split(jax.random.PRNGKey(7), 4)
    r_pad, lp_pad, _, o_pad, _ = _policy_rewards(
        params, batch, keys, 4, sys4, True, sample=True)
    for i, g in enumerate(mixed_graphs[:4]):
        single = pack_graphs([g], 4, sys4, label_method="dp", pad=False)
        r1, lp1, _, o1, _ = _policy_rewards(
            params, single, keys[i][None], 4, sys4, True, sample=True)
        assert np.array_equal(np.asarray(o_pad)[i, : g.n], np.asarray(o1)[0])
        assert float(r_pad[i]) == float(r1[0])


def test_eval_ignores_inert_batch_padding_rows(sys4, mixed_graphs):
    """Batch-dim padding (n_valid == 0 rows) must not move eval metrics."""
    batch = pack_graphs(mixed_graphs, 4, sys4, label_method="dp")
    params = RLTrainer(n_stages=4, system=sys4, hidden=32, seed=0).params
    ev = make_eval_fn(4, sys4)
    m1 = ev(params, batch)
    m2 = ev(params, batch.pad_batch(16))
    assert float(m1["reward_greedy"]) == float(m2["reward_greedy"])
    assert float(m1["exact_match"]) == float(m2["exact_match"])


def test_train_step_on_mixed_bucketed_stream(sys4):
    """The one jitted train step consumes packs of different (bucket_n, B)
    shapes from the curriculum stream and the reward stays finite."""
    sam = DagSampler(seed=3, n=(10, 50))
    tr = RLTrainer(n_stages=4, system=sys4, hidden=32, lr=3e-3, seed=0)
    key = jax.random.PRNGKey(0)
    shapes = set()
    n_packs = 0
    for pack in prefetch(sam.packed_stream(
            12, 4, system=sys4, batches_per_epoch=3, epochs=1,
            curriculum=True), depth=2):
        key, k = jax.random.split(key)
        m = tr.train_step(pack, k)
        shapes.add((pack.bucket_n, pack.batch))
        n_packs += 1
        assert np.isfinite(list(m.values())).all()
    assert len(shapes) > 1          # genuinely mixed shapes, one step fn
    assert tr.step_count == n_packs  # one optimizer step per pack


# --------------------------------------------------------------------- #
# labels: pad-aware bucketed DP labeler + cache keying
# --------------------------------------------------------------------- #
def test_mixed_size_labels_match_exact_dp(sys4, mixed_graphs):
    """One bucketed vmapped solve labels mixed sizes identically to the
    per-graph host exact_dp."""
    la, lo = label_graphs(mixed_graphs, 4, sys4, label_method="dp")
    for g, a in zip(mixed_graphs, la):
        a_ref, _ = exact_dp(g, 4, sys4)
        assert np.array_equal(np.asarray(a), np.asarray(a_ref)), g.model_name


def test_label_cache_key_distinguishes_solver_and_system(sys4):
    g = sample_dag(np.random.default_rng(5), n=20, deg=3)
    base = _label_cache_key(g, 4, sys4, "dp", 6, 0.25)
    # dp keys ignore the bb time budget ...
    assert base == _label_cache_key(g, 4, sys4, "dp", 6, 99.0)
    # ... bb keys depend on it
    bb1 = _label_cache_key(g, 4, sys4, "bb", 6, 0.25)
    bb2 = _label_cache_key(g, 4, sys4, "bb", 6, 0.50)
    assert bb1 != bb2 and bb1 != base
    # stages and system parameters separate keys
    assert base != _label_cache_key(g, 5, sys4.with_stages(5), "dp", 6, 0.25)
    slower = PipelineSystem(n_stages=4, link_bw=sys4.link_bw * 0.5)
    assert base != _label_cache_key(g, 4, slower, "dp", 6, 0.25)


def test_label_cache_bb_and_dp_do_not_collide(tmp_path, sys4):
    """bb and dp labels for the same graph live under different cache keys,
    so switching solvers never serves stale labels."""
    graphs = [sample_dag(np.random.default_rng(6), n=12, deg=2)]
    label_graphs(graphs, 4, sys4, label_method="dp", cache_dir=tmp_path)
    n_dp = len(list(tmp_path.glob("*.npz")))
    label_graphs(graphs, 4, sys4, label_method="bb", bb_budget_s=0.05,
                 cache_dir=tmp_path)
    assert len(list(tmp_path.glob("*.npz"))) == n_dp + 1


# --------------------------------------------------------------------- #
# sampler determinism
# --------------------------------------------------------------------- #
def test_dag_sampler_epoch_determinism():
    """Two samplers with one seed emit identical mixed-size epochs; the
    (seed, counter) state restores mid-stream."""
    a = DagSampler(seed=11, n=(10, 50))
    b = DagSampler(seed=11, n=(10, 50))
    packs_a = list(a.packed_stream(8, 4, batches_per_epoch=2, epochs=1))
    packs_b = list(b.packed_stream(8, 4, batches_per_epoch=2, epochs=1))
    assert len(packs_a) == len(packs_b)
    for pa, pb in zip(packs_a, packs_b):
        for la, lb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            assert np.array_equal(np.asarray(la), np.asarray(lb))
    # restore() resumes the exact stream position
    state = a.state()
    next_a = a.next_batch(4)
    c = DagSampler(seed=0, n=(10, 50))
    c.restore(state)
    next_c = c.next_batch(4)
    assert [g.content_hash() for g in next_a] == \
           [g.content_hash() for g in next_c]


def test_packed_stream_respects_batch_divisor(sys4):
    """batch_divisor pads every pack's batch dim to a multiple — the
    shard_map divisibility contract holds for ANY bucket mix."""
    sam = DagSampler(seed=4, n=(10, 50))
    packs = list(sam.packed_stream(10, 4, system=sys4, batches_per_epoch=2,
                                   epochs=1, batch_divisor=8))
    assert packs
    for p in packs:
        assert p.batch % 8 == 0
    # and the single-group (fixed-size) case as well
    fixed = DagSampler(seed=4, n=20)
    for p in fixed.packed_stream(10, 4, system=sys4, batches_per_epoch=1,
                                 epochs=1, batch_divisor=8):
        assert p.batch % 8 == 0


def test_curriculum_stream_resumes_mid_stream():
    """The curriculum ramp is a function of (seed, counter): a sampler
    restored mid-epoch continues the exact stream, ramp included."""
    a = DagSampler(seed=13, n=(10, 50))
    packs_a = list(a.packed_stream(6, 4, batches_per_epoch=4, epochs=1,
                                   curriculum=True, bucket=False))
    assert len(packs_a) == 4        # bucket=False: one pack per draw
    b = DagSampler(seed=13, n=(10, 50))
    b.restore({"seed": 13, "count": 2})
    packs_b = list(b.packed_stream(6, 4, batches_per_epoch=4, epochs=1,
                                   curriculum=True, bucket=False))
    assert len(packs_b) == 4        # draws 2..5; the first two overlap A
    for pa, pb in zip(packs_a[2:], packs_b[:2]):
        for la, lb in zip(jax.tree.leaves(pa), jax.tree.leaves(pb)):
            assert np.array_equal(np.asarray(la), np.asarray(lb))


def test_prefetch_preserves_order_and_propagates_errors():
    it = prefetch(iter(range(5)), depth=2)
    assert list(it) == [0, 1, 2, 3, 4]

    def boom():
        yield 1
        raise RuntimeError("label solver died")

    it = prefetch(boom(), depth=2)
    assert next(it) == 1
    with pytest.raises(RuntimeError, match="label solver died"):
        next(it)


# --------------------------------------------------------------------- #
# trainer checkpoint round-trip
# --------------------------------------------------------------------- #
def test_trainer_state_roundtrips_through_manager(tmp_path, sys4):
    sam = DagSampler(seed=2, n=(10, 30))
    batch = sam.next_packed_batch(8, 4, system=sys4)
    tr = RLTrainer(n_stages=4, system=sys4, hidden=32, lr=3e-3, seed=0)
    key = jax.random.PRNGKey(0)
    for _ in range(3):
        key, k = jax.random.split(key)
        tr.train_step(batch, k)
    tr.maybe_update_baseline(batch)
    tr.save(tmp_path)

    tr2 = RLTrainer(n_stages=4, system=sys4, hidden=32, lr=3e-3, seed=42)
    assert tr2.restore(tmp_path) == tr.step_count
    for a, b in zip(jax.tree.leaves(tr.state), jax.tree.leaves(tr2.state)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(tr2.state.best_baseline_reward) == \
        float(tr.state.best_baseline_reward)
    # restored trainer continues training bit-identically to the original
    key2 = jax.random.PRNGKey(9)
    m1 = tr.train_step(batch, key2)
    m2 = tr2.train_step(batch, key2)
    assert m1 == m2


def test_restore_on_empty_dir_returns_none(tmp_path, sys4):
    tr = RLTrainer(n_stages=4, system=sys4, hidden=32, seed=0)
    assert tr.restore(tmp_path) is None


# --------------------------------------------------------------------- #
# dataset batches are the unified representation too
# --------------------------------------------------------------------- #
def test_labeled_dataset_batch_is_padded(tmp_path, sys4):
    from repro.core.batching import PaddedGraphBatch
    from repro.data import LabeledDagDataset
    ds = LabeledDagDataset(count=8, n=20, n_stages=4, seed=0,
                           label_method="dp", system=sys4,
                           cache_dir=tmp_path)
    batch = ds.batch(0, 4)
    assert isinstance(batch, PaddedGraphBatch)
    assert batch.bucket_n == 32 and batch.has_labels
    assert np.asarray(batch.n_valid).tolist() == [20] * 4
    tr = RLTrainer(n_stages=4, system=sys4, hidden=32, seed=0)
    m = tr.train_step(batch, jax.random.PRNGKey(0))
    assert np.isfinite(list(m.values())).all()


# --------------------------------------------------------------------- #
# data-parallel training (subprocess: needs forced host devices)
# --------------------------------------------------------------------- #
def test_sharded_training_matches_single_device():
    """With 4 forced host devices, the shard_map data-parallel step tracks
    the single-device params trajectory at equal global batch."""
    code = """
        import json, jax, jax.numpy as jnp, numpy as np
        from repro.core import PipelineSystem, sample_dag
        from repro.core.rl import RLTrainer, pack_graphs
        sys4 = PipelineSystem(n_stages=4)
        rng = np.random.default_rng(0)
        graphs = [sample_dag(rng, n=int(rng.integers(10, 25)), deg=3)
                  for _ in range(8)]
        batch = pack_graphs(graphs, 4, sys4, label_method="dp")
        tr1 = RLTrainer(n_stages=4, system=sys4, hidden=16, lr=3e-3, seed=0)
        tr4 = RLTrainer(n_stages=4, system=sys4, hidden=16, lr=3e-3, seed=0,
                        n_devices=4)
        key = jax.random.PRNGKey(0)
        for _ in range(3):
            key, k = jax.random.split(key)
            m1 = tr1.train_step(batch, k)
            m4 = tr4.train_step(batch, k)
        diffs = [float(jnp.max(jnp.abs(a - b))) for a, b in
                 zip(jax.tree.leaves(tr1.params), jax.tree.leaves(tr4.params))]
        print(json.dumps({
            "n_dev": jax.device_count(), "max_diff": max(diffs),
            "r1": m1["reward_sample"], "r4": m4["reward_sample"]}))
    """
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = str(REPO / "src")
    env["JAX_PLATFORMS"] = "cpu"       # a child never reaches for the chip
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True, text=True, timeout=420, env=env)
    assert proc.returncode == 0, proc.stderr[-4000:]
    out = json.loads(proc.stdout.strip().splitlines()[-1])
    assert out["n_dev"] == 4
    assert out["max_diff"] < 1e-5           # psum reordering noise only
    assert out["r1"] == pytest.approx(out["r4"], abs=1e-6)
