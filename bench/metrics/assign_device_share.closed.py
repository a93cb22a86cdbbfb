"""Share of the chip's busy time in the traced window spent in the stage
assignment: the segmentation DP and the repair loops (operations under
the ``rho_dp`` and ``repair`` named scopes), from the device trace
(``bench/lib/spans.py``)."""

from bench.lib.spans import device_share


def read(rec):
    return device_share(rec, ["rho_dp", "repair"])
