"""Whole-decode Pallas kernel (``kernels/ptr/decode.py``): least time the
chip could take for the kernel calls of the flushes wholly inside the
traced part of the window (operations over peak
FLOP/s or bytes over HBM bandwidth, whichever is larger;
``bench/lib/flops.decode_kernel_cost`` from each call's bucket, batch and
width) over the kernel's device time in the trace."""

from bench.lib.flops import roofline_seconds

KERNEL_NAMES = ("decode_batch",)


def read(rec):
    red, peaks = rec["trace"], rec["peaks"]
    if red is None or peaks is None or rec["kernel_flops"] <= 0:
        return None
    t = sum(s for name, s in red["ops"].items()
            if name.startswith(KERNEL_NAMES))
    if t <= 0:
        return None
    least, _ = roofline_seconds(rec["kernel_flops"], rec["kernel_bytes"],
                                peaks)
    return 100.0 * least / t
