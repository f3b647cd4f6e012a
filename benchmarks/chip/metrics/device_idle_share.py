"""Device: share of the traced window in which no operation ran, the mean
over the cell's chips."""


def read(rec):
    t = rec["trace"]
    if t["window_s"] <= 0 or not t["chips"]:
        return None
    busy = sum(c["busy_s"] for c in t["chips"]) / len(t["chips"])
    return 100.0 * (1.0 - busy / t["window_s"])
