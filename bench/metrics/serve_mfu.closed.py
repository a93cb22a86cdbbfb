"""Whole served step: matrix FLOPs of the policy decodes that the window
ran (each graph at its true size, ``bench/lib/flops``; cache hits run
none), per second of the window, over the chip's bf16 peak."""


def read(rec):
    peaks = rec["peaks"]
    if peaks is None or rec["policy_flops_window"] <= 0:
        return None
    return 100.0 * rec["policy_flops_window"] / rec["window_s"] / \
        peaks["flops_per_s"]
