"""Share of requests answered without a decode: schedule-cache hits plus
single-flight duplicates (``ServiceStats``), over the requests offered."""


def read(rec):
    if not rec["requests"]:
        return None
    return 100.0 * (rec["cache_hits"] + rec["dedup_hits"]) / rec["requests"]
