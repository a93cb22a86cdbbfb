"""Whole served step: matrix FLOPs of the policy decodes of the flushes
wholly inside the traced part of the window (each graph at its true
size, ``bench/lib/flops``) over the device's busy time in the trace times
the chip's bf16 peak.  (A share of the window would be fixed by the
offered rate.)"""


def read(rec):
    red, peaks = rec["trace"], rec["peaks"]
    if red is None or peaks is None or rec["policy_flops_traced"] <= 0:
        return None
    return 100.0 * rec["policy_flops_traced"] / red["busy_s"] / \
        peaks["flops_per_s"]
