"""Interpret-mode tests for the persistent whole-decode kernel (PR 8).

Fast-tier coverage for :mod:`repro.kernels.ptr.decode` and the shape
validation in :mod:`repro.kernels.ptr.ops`:

* masking at ``n_valid`` boundaries (real prefix is a permutation, pads
  drain after it, log-prob/entropy are exactly zero past the boundary),
* tie-break equality with the banded lex rule ``segment.py``/``repair``
  apply downstream (uniform-cost graphs, fused kernel vs scan vs host),
* bf16-path order agreement on the golden Table-I DNN graphs,
* sampled-path determinism from a fixed key,
* ``decode_kernel_supported`` and the raise on a forced kernel or an
  untileable pointer step (no quiet fallback to the scan).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import CompGraph, ptrnet, repair, rho, sample_dag
from repro.core.batching import BucketedDecoder, bucket_for
from repro.core.costmodel import PipelineSystem
from repro.core.dnn_graphs import all_model_graphs
from repro.core.embedding import embed_dim, embed_graph
from repro.kernels.ptr import decode as ptr_decode
from repro.kernels.ptr import ops as ptr_ops

MAX_DEG = 6
N_STAGES = 4
_PARAMS = ptrnet.init_params(jax.random.PRNGKey(0), embed_dim(MAX_DEG), 32)


def _padded_inputs(g: CompGraph, pad_n: int):
    feats = np.asarray(embed_graph(g, MAX_DEG))
    pmat = np.asarray(g.parent_matrix(MAX_DEG))
    pf = np.zeros((pad_n, feats.shape[1]), feats.dtype)
    pf[: g.n] = feats
    pp = np.full((pad_n, MAX_DEG), -1, np.int32)
    pp[: g.n] = pmat
    return jnp.asarray(pf), jnp.asarray(pp)


def _pack(graphs, pad_n):
    fs, ps = zip(*(_padded_inputs(g, pad_n) for g in graphs))
    return (jnp.stack(fs), jnp.stack(ps),
            jnp.asarray([g.n for g in graphs], jnp.int32))


def test_masking_respects_n_valid_boundary():
    """Real nodes come out as a topo-valid permutation of [0, n), pads
    drain strictly after them in ascending index order, and logp/entropy
    are exactly zero on every drained step."""
    graphs = [sample_dag(np.random.default_rng(s), n=n, deg=3)
              for s, n in ((0, 9), (1, 14), (2, 16))]
    pad_n = 16
    feats, pmat, n_valid = _pack(graphs, pad_n)
    order, logp, ent = ptr_decode.decode_pack(
        _PARAMS, feats, pmat, n_valid, interpret=True)
    order = np.asarray(order)
    for i, g in enumerate(graphs):
        real, pads = order[i, : g.n], order[i, g.n:]
        assert sorted(real.tolist()) == list(range(g.n))
        # drain region: remaining pad indices, first-occurrence argmax
        # over a constant mask -> ascending
        assert pads.tolist() == sorted(range(g.n, pad_n))
        for j, v in enumerate(real):
            parents = [p for p in np.asarray(g.parent_matrix(MAX_DEG))[v]
                       if p >= 0]
            assert all(p in real[:j] for p in parents), \
                "kernel emitted a node before one of its parents"
        assert float(np.abs(np.asarray(logp)[i, g.n:]).sum()) == 0.0
        assert float(np.abs(np.asarray(ent)[i, g.n:]).sum()) == 0.0


def _uniform(g: CompGraph) -> CompGraph:
    n = g.n
    return dataclasses.replace(
        g, flops=np.full(n, 1.0e9), param_bytes=np.full(n, 1.0e6),
        out_bytes=np.full(n, 1.0e5))


def test_tie_break_matches_banded_lex_rule():
    """Uniform-cost graphs make both the pointer logits and the DP cost
    surface tie-heavy.  The kernel must pick the same (lowest-index)
    winners as the scan so the downstream banded lex rho/repair rule in
    ``segment.py`` sees identical inputs — end to end, the fused kernel
    schedule equals the fused scan schedule equals host rho+repair."""
    graphs = [_uniform(sample_dag(np.random.default_rng(s), n=12, deg=2))
              for s in range(4)]
    system = PipelineSystem(N_STAGES)
    scan = BucketedDecoder(decode_impl="scan")
    kern = BucketedDecoder(decode_impl="kernel-interpret")
    f_scan = scan.fused_schedules(_PARAMS, graphs, N_STAGES, system)
    f_kern = kern.fused_schedules(_PARAMS, graphs, N_STAGES, system)
    for g, (o_s, a_s), (o_k, a_k) in zip(graphs, f_scan, f_kern):
        assert np.array_equal(o_s, o_k)
        assert np.array_equal(a_s, a_k)
        host = repair(g, rho(g, np.asarray(o_k), N_STAGES), N_STAGES)
        assert np.array_equal(np.asarray(host), a_k)


def test_bf16_order_agreement_on_golden_dnns():
    """The bf16 storage path must still produce the f32 orders on the
    golden DNN graphs (smallest two keep this in the fast tier)."""
    models = all_model_graphs()
    graphs = [models["Xception"], models["ResNet50"]]
    pad_n = bucket_for(max(g.n for g in graphs))
    feats, pmat, n_valid = _pack(graphs, pad_n)
    o32, _, _ = ptr_decode.decode_pack(
        _PARAMS, feats, pmat, n_valid, interpret=True)
    o16, _, _ = ptr_decode.decode_pack(
        _PARAMS, feats, pmat, n_valid, interpret=True, bf16=True)
    assert np.array_equal(np.asarray(o32), np.asarray(o16))
    # and bf16 agrees with the scan decode too
    for i, g in enumerate(graphs):
        f, p = _padded_inputs(g, pad_n)
        o_scan, _, _ = ptrnet.greedy_order(_PARAMS, f, p, True, g.n)
        assert np.array_equal(np.asarray(o_scan), np.asarray(o16)[i])


def test_sampled_path_deterministic_from_fixed_key():
    graphs = [sample_dag(np.random.default_rng(s), n=13, deg=3)
              for s in range(3)]
    pad_n = 16
    feats, pmat, n_valid = _pack(graphs, pad_n)
    keys = jax.random.split(jax.random.PRNGKey(42), len(graphs))

    def draw(ks):
        return ptr_decode.decode_pack(
            _PARAMS, feats, pmat, n_valid, sample_keys=ks, sampled=True,
            interpret=True)

    o1, lp1, _ = draw(keys)
    o2, lp2, _ = draw(keys)
    assert np.array_equal(np.asarray(o1), np.asarray(o2))
    assert np.array_equal(np.asarray(lp1), np.asarray(lp2))
    # same key -> the scan's sampled decode, bitwise on orders
    for i, g in enumerate(graphs):
        f, p = _padded_inputs(g, pad_n)
        o_scan, _, _ = ptrnet.sample_order(
            _PARAMS, f, p, keys[i], True, n_valid=g.n)
        assert np.array_equal(np.asarray(o_scan), np.asarray(o1)[i])
    # a different key must be able to change the decode
    o3, _, _ = draw(jax.random.split(jax.random.PRNGKey(7), len(graphs)))
    assert not np.array_equal(np.asarray(o1), np.asarray(o3))


def test_decode_kernel_supported_shape_gate():
    """Satellite 4: block shapes are validated against the 8x128 TPU
    tile, and over-VMEM buckets are rejected instead of assumed."""
    assert ptr_ops.pointer_shapes_ok(32, 128)
    assert ptr_ops.decode_kernel_supported(32, 128)
    assert ptr_ops.decode_kernel_supported(1024, 128)
    assert not ptr_ops.pointer_shapes_ok(12, 128)   # sublane misaligned
    assert not ptr_ops.pointer_shapes_ok(32, 100)   # lane misaligned
    assert not ptr_ops.decode_kernel_supported(12, 128)
    assert not ptr_ops.decode_kernel_supported(32, 100)
    # a bucket whose VMEM-resident operands blow the budget is rejected
    # even though it tiles cleanly
    assert not ptr_ops.decode_kernel_supported(
        4096, 128, vmem_limit_bytes=1 << 20)


def test_forced_kernel_on_cpu_falls_back_once_to_scan():
    """decode_impl="kernel" means the compiled TPU kernel; on CPU it
    raises instead of falling back to the scan, every time it is asked,
    while the scan and the interpreted kernel still agree."""
    graphs = [sample_dag(np.random.default_rng(3), n=10, deg=3)]
    forced = BucketedDecoder(decode_impl="kernel")
    for _ in range(2):
        with pytest.raises(ValueError, match="kernel-interpret"):
            forced.greedy_orders(_PARAMS, graphs)
    o_scan = BucketedDecoder(decode_impl="scan").greedy_orders(
        _PARAMS, graphs)
    o_interp = BucketedDecoder(decode_impl="kernel-interpret").greedy_orders(
        _PARAMS, graphs)
    assert np.array_equal(o_interp[0], o_scan[0])


def test_forced_kernel_on_unsupported_shape_raises(monkeypatch):
    """On TPU a forced kernel on a bucket the gate refuses, or on a
    heterogeneous system, raises; auto picks the scan for those and the
    kernel for a good uniform bucket."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    forced = BucketedDecoder(decode_impl="kernel")
    assert forced._resolve_decode_impl(32, 128) == "kernel"
    for bucket_n, hidden in ((12, 128), (32, 100), (4096, 128)):
        with pytest.raises(ValueError, match="do not tile or fit VMEM"):
            forced._resolve_decode_impl(bucket_n, hidden)
        assert BucketedDecoder()._resolve_decode_impl(
            bucket_n, hidden) == "scan"
    assert BucketedDecoder()._resolve_decode_impl(1024, 128) == "kernel"
    for impl in ("kernel", "kernel-interpret"):
        with pytest.raises(ValueError, match="profile-conditioned"):
            BucketedDecoder(decode_impl=impl)._resolve_decode_impl(
                32, 128, conditioned=True)
    assert BucketedDecoder()._resolve_decode_impl(
        32, 128, conditioned=True) == "scan"


def test_untileable_pointer_step_raises_on_tpu(monkeypatch):
    """Auto ``pointer_step`` on TPU raises on a shape the kernel cannot
    tile instead of swapping in the reference op."""
    n, hidden = 12, 32
    C = jnp.ones((n, hidden), jnp.float32)
    CWg, CWp = ptr_ops.precompute_refs(_PARAMS, C)
    h = jnp.ones((hidden,), jnp.float32)
    mask = jnp.ones((n,), bool)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with pytest.raises(ValueError, match="do not tile"):
        ptr_ops.pointer_step(_PARAMS, C, CWg, CWp, h, mask)
    ref = ptr_ops.pointer_step(_PARAMS, C, CWg, CWp, h, mask, impl="ref")
    assert ref.shape == (n,)
