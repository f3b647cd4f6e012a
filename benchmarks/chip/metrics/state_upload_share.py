"""Engine backend: share of decode calls that re-uploaded the batch state
(a batch-membership change), from the program's `backend.upload` and
`backend.decode` spans in the traced part of the window."""
from benchmarks.chip import spans


def read(rec):
    p = spans.program(rec)
    if p is None or "backend.decode" not in p["spans"]:
        return None
    uploads = p["spans"].get("backend.upload", {}).get("count", 0)
    return 100.0 * uploads / p["spans"]["backend.decode"]["count"]
