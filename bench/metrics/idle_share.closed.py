"""Share of the traced window in which no operation ran on the chip:
1 - (union of device operation intervals) / window, from the trace."""


def read(rec):
    red = rec["trace"]
    if red is None or red["window_s"] <= 0:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
