"""Operations and bytes of the policy and of its decode kernel, from shapes.

Only matrix products are counted (two operations per multiply-add), the
work that the chip's peak is quoted for; elementwise and transcendental
work is left out, so every share built on these counts is a lower bound.

Shapes: ``n`` nodes, ``hidden`` H, ``feat`` F input features per node.
"""

from __future__ import annotations


def policy_forward_flops(n: int, hidden: int, feat: int = 16) -> float:
    """One greedy decode of one graph at its true size (paper §III-B):

    * input projection 2nFH; encoder LSTM n x (x@Wx + h@Wh) = 16nH^2;
    * step-invariant projections C@W_ref for glimpse and pointer 4nH^2;
    * per decode step: LSTM 16H^2, the two query projections 4H^2, glimpse
      scores, glimpse read-out and pointer scores 6nH.
    """
    H = hidden
    return float(2 * n * feat * H + 16 * n * H * H + 4 * n * H * H
                 + n * (20 * H * H + 6 * n * H))


def decode_kernel_cost(bucket_n: int, batch: int, hidden: int,
                       mask_infeasible: bool = True) -> tuple[float, float]:
    """(operations, HBM bytes) of one whole-decode kernel call over
    ``batch`` graphs padded to ``bucket_n`` nodes, f32 operands.

    Per graph and step (bucket_n steps): LSTM 16H^2, queries 4H^2,
    glimpse scores + read-out + pointer scores 6NH, and the parent
    feasibility matvec 2N^2.  Bytes: per graph the contexts, both
    projections and embeddings (4NH), the parent counts (N^2), validity
    and uniforms (2N), the encoder state (2H) and three outputs (3N);
    once per call the decoder weights (10H^2 + 7H)."""
    N, H = bucket_n, hidden
    step = 20 * H * H + 6 * N * H + (2 * N * N if mask_infeasible else 0)
    flops = batch * N * step
    words = batch * (4 * N * H + N * N + 2 * N + 2 * H + 3 * N) \
        + 10 * H * H + 7 * H
    return float(flops), float(4 * words)


def roofline_seconds(flops: float, nbytes: float, peaks: dict
                     ) -> tuple[float, str]:
    """Least time the chip could take and which bound sets it."""
    t_c = flops / peaks["flops_per_s"]
    t_m = nbytes / peaks["hbm_bytes_per_s"]
    return (t_c, "compute") if t_c >= t_m else (t_m, "memory")
