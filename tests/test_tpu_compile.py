"""Compile the main path's Pallas kernels and device programs for a
described TPU v5e chip.

No chip is needed: the TPU compiler is installed, and compiling for a
described topology raises what the chip's compiler would raise (block
tiling, VMEM, unsupported primitives) — faults interpret mode cannot
show.  The topology is described inside a module fixture, never at
import: only one process at a time may load the TPU library, and test
workers import every file.  Nothing here runs; results are covered by
the interpret-mode parity tests.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core import PipelineSystem, ptrnet
from repro.core.batching import BucketedDecoder, PaddedGraphBatch
from repro.core.embedding import embed_dim
from repro.eval import ExactOracle
from repro.kernels.flash.kernel import flash_attention_pallas
from repro.kernels.ptr import decode as ptr_decode
from repro.kernels.ptr import ops as ptr_ops
from repro.kernels.ptr.kernel import pointer_step_pallas
from repro.kernels.ssd.kernel import ssd_scan_pallas

HIDDEN = 128           # the shipped respect-v1 policy
MAX_DEG = 6
BATCH = 16


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import compilation_cache as cc
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setitem(os.environ, "TPU_LOG_DIR", "disabled")
        try:
            desc = topologies.get_topology_desc(
                platform="tpu", topology_name="v5e:2x2")
        except Exception as e:
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a compile for a described chip is written to the persistent
        # cache but cannot be read back without one: keep the cache off
        enabled = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        cc.compilation_cache.reset_cache()
        yield desc
        jax.config.update("jax_enable_compilation_cache", enabled)
        cc.compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def spec(topo):
    """ShapeDtypeStruct builder placed on one described v5e chip."""
    one_chip = SingleDeviceSharding(topo.devices[0])
    return lambda shape, dtype=jnp.float32: jax.ShapeDtypeStruct(
        shape, dtype, sharding=one_chip)


@pytest.fixture(scope="module")
def params(spec):
    shapes = jax.eval_shape(lambda: ptrnet.init_params(
        jax.random.PRNGKey(0), embed_dim(MAX_DEG), HIDDEN))
    return jax.tree.map(lambda a: spec(a.shape, a.dtype), shapes)


@pytest.mark.parametrize("sampled", [False, True],
                         ids=["greedy", "sampled"])
@pytest.mark.parametrize("bucket_n", [32, 1024])
def test_whole_decode_kernel_compiles(spec, params, bucket_n, sampled):
    """Largest real bucket: InceptionResNetv2 (782 nodes) pads to 1024;
    the VMEM gate accepts it and the kernel's own limit must suffice."""
    assert ptr_ops.decode_kernel_supported(bucket_n, HIDDEN)
    B, n, H = BATCH, bucket_n, HIDDEN
    args = (params, spec((B, n, H)), spec((B, n, H)), spec((B, H)),
            spec((B, H)), spec((B, n, MAX_DEG), jnp.int32),
            spec((B,), jnp.int32), spec((B, n)) if sampled else None)
    compiled = jax.jit(lambda *a: ptr_decode.decode_batch(
        *a, sampled=sampled)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_pointer_step_kernel_compiles(spec):
    B, n, H = BATCH, 64, HIDDEN
    compiled = pointer_step_pallas.lower(
        spec((B, n, H)), spec((B, n, H)), spec((B, n, H)), spec((B, H)),
        spec((H, H)), spec((H,)), spec((H, H)), spec((H,)),
        spec((B, n), jnp.bool_)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("head_dim", [512, 1], ids=["values", "normalizer"])
def test_ssd_kernel_compiles_at_xlstm_widths(spec, head_dim):
    """xlstm-350m's mLSTM: 4 heads of 512 (d_model 1024 x expand 2), k/q
    as B/C per head, chunk 64; the normalizer scan runs with p = 1."""
    bt, s, h, n = 1, 128, 4, 512
    compiled = ssd_scan_pallas.lower(
        spec((bt, s, h, head_dim), jnp.bfloat16), spec((bt, s, h)),
        spec((h,)), spec((bt, s, h, n), jnp.bfloat16),
        spec((bt, s, h, n), jnp.bfloat16), chunk=64,
        in_scale=spec((bt, s, h))).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_flash_kernel_compiles_at_whisper_tiny_widths(spec):
    """whisper-tiny self-attention: 6 heads of 64 at the ingest length."""
    q = spec((1, 6, 64, 64))
    compiled = flash_attention_pallas.lower(
        q, q, q, block_q=64, block_k=64).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("conditioned", [False, True],
                         ids=["kernel-uniform", "scan-hetero"])
def test_fused_serving_program_compiles(spec, params, conditioned):
    """One whole fused decode -> rho DP -> repair program at bucket 64 x
    batch 16: uniform systems run the whole-decode kernel, conditioned
    ones the scan with the per-step pointer kernel, as auto picks on TPU."""
    n, cw = 64, 4
    if conditioned:
        system = PipelineSystem(4, compute_rate=(4e12, 8e12, 4e12, 2e12))
        decoder, impl = BucketedDecoder(logits_impl="pallas"), "scan"
    else:
        system, decoder, impl = PipelineSystem(4), BucketedDecoder(), "kernel"
    batch = PaddedGraphBatch(
        feats=spec((BATCH, n, embed_dim(MAX_DEG))),
        parent_mat=spec((BATCH, n, MAX_DEG), jnp.int32),
        child_mat=spec((BATCH, n, cw), jnp.int32),
        ancestor_mat=spec((BATCH, n, n), jnp.bool_),
        flops=spec((BATCH, n)), param_bytes=spec((BATCH, n)),
        out_bytes=spec((BATCH, n)), n_valid=spec((BATCH,), jnp.int32))
    fn = decoder._fused_fn(n, BATCH, cw, 4, system, impl)
    compiled = fn.lower(params, batch).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("system", [PipelineSystem(4), PipelineSystem(
    4, compute_rate=(4e12, 8e12, 4e12, 2e12))], ids=["uniform", "hetero"])
def test_exact_oracle_program_compiles(spec, system):
    """The batched exact DP (oracle and training labels) at bucket 32 x
    batch 16.  Its identity order must not reach the program as a scatter
    of an iota by itself: the TPU compiler's fusion pass aborts on it."""
    b, n = BATCH, 32
    oracle = ExactOracle()
    compiled = oracle._fn(n, b, 4, system).lower(
        spec((b, n)), spec((b, n)), spec((b, n)),
        spec((b, n, MAX_DEG), jnp.int32), spec((b,), jnp.int32)).compile()
    assert compiled.memory_analysis() is not None
