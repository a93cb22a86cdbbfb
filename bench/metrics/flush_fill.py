"""Requests per service flush: requests that reached the scheduler
(``ServiceStats.completed`` less coalesced duplicates) over
``ServiceStats.batches``."""


def read(rec):
    if not rec["flushes"]:
        return None
    return (rec["served_requests"] - rec["dedup_hits"]) / rec["flushes"]
