"""Share of the traced window in which the chip sat idle while the serving
worker was in the scheduler facade: content hashing and the schedule
cache lookup (``respect.lookup``), the cache fill and result copies
(``respect.results``); from the program's spans (``bench/lib/spans.py``)."""

from bench.lib.spans import idle_share


def read(rec):
    return idle_share(rec, ["respect.lookup", "respect.results"])
