"""Front API: how late the generator submitted, median of submit time
minus due time over the requests due in the window (host clock)."""
from benchmarks.chip.stats import in_window, pct


def read(rec):
    timed = in_window(rec["records"], rec["seconds"])
    late = [1e3 * (r.submitted - r.due) for r in timed]
    return pct(late, 50) if late else None
