"""Share of the traced window in which the chip sat idle while the serving
worker was in the service front end: filling a micro-batch
(``respect.collect``), resolving futures and their callbacks
(``respect.resolve``) and the flush's own work (``respect.flush`` self
time); from the program's spans (``bench/lib/spans.py``)."""

from bench.lib.spans import idle_share


def read(rec):
    return idle_share(rec, ["respect.collect", "respect.resolve",
                            "respect.flush"])
