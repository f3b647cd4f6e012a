"""Engine backend: chip-idle self time of the program's `backend.prefill`,
`backend.decode` and `backend.upload` spans (host packing, uploads and
dispatch, less the blocking sync inside them), per engine step, in the
traced part of the window."""
from benchmarks.chip import spans


def read(rec):
    p = spans.program(rec)
    if p is None:
        return None
    idle = sum(p["spans"].get(n, {}).get("idle_self_s", 0.0)
               for n in ("backend.prefill", "backend.decode",
                         "backend.upload"))
    return 1e3 * idle / p["engine_steps"]
