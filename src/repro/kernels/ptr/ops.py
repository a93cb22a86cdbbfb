"""Public wrapper for the fused pointer/glimpse ops (single-step kernel,
whole-decode kernel) plus the TPU shape-validation shared by both.

The block specs of both kernels keep each graph's context/projection
blocks fully VMEM-resident — which is only legal when the block shapes
land on the TPU vector-register tiling (f32 tiles are 8 sublanes x 128
lanes) and the per-step working set fits VMEM.  :func:`pointer_shapes_ok`
/ :func:`decode_kernel_supported` check exactly that.  A shape they
refuse raises where a caller asked for the kernel; only the auto choice
of the serving engine routes such a bucket to the scan, as a choice made
from the shape before anything compiles.
"""

from __future__ import annotations

import jax

from .kernel import pointer_step_pallas
from .ref import reference_pointer_step

__all__ = [
    "precompute_refs",
    "pointer_step",
    "make_logits_fn",
    "pointer_shapes_ok",
    "decode_kernel_supported",
    "decode_kernel_vmem_bytes",
    "make_decode_fn",
]

# f32 VREG tiling on TPU: 8 sublanes x 128 lanes
_SUBLANE = 8
_LANE = 128
# VMEM the whole-decode kernel may claim: v5e has 128 MiB of VMEM per
# TensorCore; the kernel raises its scoped limit (default 16 MiB) to its
# own footprint estimate, and the gate refuses buckets above this budget.
_VMEM_LIMIT_BYTES = 96 << 20


def _round_up(x: int, m: int) -> int:
    return -(-x // m) * m


def _tile_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """VMEM bytes of a (rows, cols) block laid out on (8 x itemsize-packed
    sublanes) x 128-lane tiles: a column vector costs a full lane tile."""
    sublanes = _SUBLANE * (4 // itemsize)
    return _round_up(rows, sublanes) * _round_up(cols, _LANE) * itemsize


def pointer_shapes_ok(n: int, hidden: int) -> bool:
    """True when the SINGLE-STEP kernel's full-block specs are tileable:
    the node dim must land on the sublane grid and ``hidden`` on the lane
    grid (the specs load whole (n, hidden) blocks)."""
    return n % _SUBLANE == 0 and hidden % _LANE == 0


def decode_kernel_vmem_bytes(bucket_n: int, hidden: int, *,
                             sampled: bool = False,
                             bf16: bool = False) -> int:
    """VMEM footprint of one grid step of the WHOLE-DECODE kernel: every
    block double-buffered by the pipeline — the four big (n, H) operands
    (C, the two hoisted projections, emb), the (n, n) parent adjacency,
    the lane-padded per-node input/output columns and the weights — plus
    the body's f32 temporaries: two (n, n) copies feeding the feasibility
    matvec, the upcast operands, the loop-carried columns, and for the
    sampled pick the (n, n) prefix-sum mask.  At hidden 128 the v5e
    compiler needs 17.7 MiB (greedy) / 18.7 MiB (sampled) at n = 1024 and
    78.8 / 82.7 MiB at n = 2048, against 33.6 / 37.6 and 97.6 / 113.6
    MiB estimated here."""
    n, h = bucket_n, hidden
    store = 2 if bf16 else 4
    col = _tile_bytes(n, 1)
    blocks = (4 * _tile_bytes(n, h, store)      # C, CWg, CWp, emb
              + _tile_bytes(n, n)               # parent adjacency
              + 5 * col                         # valid, uniforms, 3 outputs
              + 2 * _tile_bytes(1, h)           # h0, c0
              + _tile_bytes(1, h, store)        # dec0
              + 2 * _tile_bytes(h, 4 * h, store)  # dec wx, wh
              + _tile_bytes(1, 4 * h)           # dec bias
              + 2 * _tile_bytes(h, h, store)    # glimpse/pointer w_q
              + 2 * _tile_bytes(h, 1, store))   # glimpse/pointer v
    temps = 2 * _tile_bytes(n, n) + 6 * _tile_bytes(n, h) + 8 * col
    if sampled:
        temps += _tile_bytes(n, n)
    return 2 * blocks + temps


def decode_kernel_supported(
        bucket_n: int, hidden: int, *,
        vmem_limit_bytes: int = _VMEM_LIMIT_BYTES) -> bool:
    """True when the WHOLE-DECODE kernel can hold one graph's working set
    in VMEM at this (bucket, hidden): tiling-aligned blocks and a
    :func:`decode_kernel_vmem_bytes` footprint within the budget, taken
    for the larger (sampled, f32) variant so one answer covers both."""
    if bucket_n % _SUBLANE != 0 or hidden % _LANE != 0:
        return False
    return decode_kernel_vmem_bytes(
        bucket_n, hidden, sampled=True) <= vmem_limit_bytes


def precompute_refs(params, C):
    """Hoist the decode-loop-invariant context projections.

    params: a ptrnet parameter pytree (uses glimpse/pointer heads).
    C: (n, H) or (B, n, H).  Returns (CWg, CWp).
    """
    return C @ params["glimpse"]["w_ref"], C @ params["pointer"]["w_ref"]


def pointer_step(params, C, CWg, CWp, h, mask, *, impl: str | None = None):
    """One decode step; shapes as in the kernel (batched) or unbatched.

    impl: "pallas" | "interpret" | "ref" (auto: pallas on TPU else ref).
    Auto on TPU requires :func:`pointer_shapes_ok` and raises
    ``ValueError`` on a shape the kernel cannot tile, so a TPU run never
    leaves the kernel without saying so.
    """
    n, hidden = C.shape[-2], C.shape[-1]
    if impl is None:
        impl = "pallas" if jax.default_backend() == "tpu" else "ref"
        if impl == "pallas" and not pointer_shapes_ok(n, hidden):
            raise ValueError(
                f"pointer kernel blocks (n={n}, hidden={hidden}) do not "
                f"tile to {_SUBLANE}x{_LANE}; pass impl='ref' to run the "
                "reference op for this shape")
    g, p = params["glimpse"], params["pointer"]
    unbatched = C.ndim == 2
    if impl == "ref":
        fn = lambda c, cg, cp, hh, mm: reference_pointer_step(
            c, cg, cp, hh, g["w_q"], g["v"], p["w_q"], p["v"], mm)
        if unbatched:
            return fn(C, CWg, CWp, h, mask)
        return jax.vmap(fn)(C, CWg, CWp, h, mask)
    if unbatched:
        C, CWg, CWp, h, mask = (x[None] for x in (C, CWg, CWp, h, mask))
    out = pointer_step_pallas(
        C, CWg, CWp, h, g["w_q"], g["v"], p["w_q"], p["v"], mask,
        interpret=(impl == "interpret"))
    return out[0] if unbatched else out


def make_logits_fn(params, C, *, impl: str | None = None):
    """Build a ``logits_fn(C, h, mask)`` for the ptrnet decode scan.

    Precomputes the loop-invariant context projections once (per graph,
    after encoding) and dispatches every decode step to
    :func:`pointer_step` — the Pallas kernel on TPU, the pure-jnp oracle
    elsewhere.  Plugs into ``ptrnet.greedy_order(..., logits_builder=...)``
    so the batched serving path hits the fused kernel on TPU deployments.
    """
    CWg, CWp = precompute_refs(params, C)

    def logits_fn(C_, h, mask):
        return pointer_step(params, C_, CWg, CWp, h, mask, impl=impl)

    return logits_fn


def make_decode_fn(*, interpret: bool = False, bf16: bool = False):
    """Whole-decode builder (see :func:`.decode.make_decode_fn`) —
    re-exported here so callers select single-step and whole-decode
    kernels through one module."""
    from .decode import make_decode_fn as _mk
    return _mk(interpret=interpret, bf16=bf16)
