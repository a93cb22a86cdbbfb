"""Closed loop: ``clients`` requests are outstanding at all times, and
each result frees its slot for the next request.  A run can offer at
most ``max_per_s`` requests per window second; one that would need more
fails rather than serve a request twice."""

from __future__ import annotations

import math

LOOP = "closed"
KEYS = {"clients", "max_per_s"}


def requests(traffic: dict, seed: int, seconds: float):
    """(most requests the run can offer, None: they fall due as slots
    free)."""
    return math.ceil(float(traffic["max_per_s"]) * seconds), None
