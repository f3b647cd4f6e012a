"""Replica scheduler: decode rows per decode step over max_batch, mean
over the window's decode steps."""


def read(rec):
    calls = rec["decode_all"]
    if not calls:
        return None
    cap = rec["ecfg"].max_batch
    return 100.0 * sum(len(c[2]) for c in calls) / (len(calls) * cap)
