"""The operation and byte counts against hand counts at one shape."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench.lib import flops as F  # noqa: E402


def test_policy_forward_flops_by_hand():
    n, H, feat = 30, 128, 16
    proj = 2 * 30 * 16 * 128                  # 122,880
    enc = 30 * 2 * (2 * 128 * 512)            # 7,864,320
    refs = 2 * (2 * 30 * 128 * 128)           # 1,966,080
    step = 2 * (2 * 128 * 512) + 2 * (2 * 128 * 128) + 3 * (2 * 30 * 128)
    assert F.policy_forward_flops(n, H, feat) == proj + enc + refs + 30 * step


def test_decode_kernel_cost_by_hand():
    N, B, H = 32, 16, 128
    step = 2 * 128 * 512 * 2 + 2 * 128 * 128 * 2 + 3 * 2 * 32 * 128 \
        + 2 * 32 * 32
    flops, nbytes = F.decode_kernel_cost(N, B, H)
    assert flops == B * N * step
    per_graph = 4 * 32 * 128 + 32 * 32 + 2 * 32 + 2 * 128 + 3 * 32
    weights = 10 * 128 * 128 + 7 * 128
    assert nbytes == 4 * (B * per_graph + weights)
    f2, _ = F.decode_kernel_cost(N, B, H, mask_infeasible=False)
    assert flops - f2 == B * N * 2 * 32 * 32


def test_roofline_picks_the_larger_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert F.roofline_seconds(1000.0, 50.0, peaks) == (10.0, "compute")
    assert F.roofline_seconds(100.0, 50.0, peaks) == (5.0, "memory")
    t, _ = F.roofline_seconds(*F.decode_kernel_cost(1024, 16, 128),
                              {"flops_per_s": 197e12,
                               "hbm_bytes_per_s": 819e9})
    assert t == pytest.approx(16 * 1024 * (20 * 128 ** 2 + 6 * 1024 * 128
                                           + 2 * 1024 ** 2) / 197e12)
