"""Share of the traced window in which the chip sat idle while the serving
worker was packing a bucket (``respect.pack`` and its children: embed,
ancestor closure, host-to-device copies, batch padding), from the
program's spans on the device trace's clock (``bench/lib/spans.py``)."""

from bench.lib.spans import idle_share


def read(rec):
    return idle_share(rec, ["respect.pack*"])
