"""Replica scheduler: chip-idle self time of the program's `replica.admit`
and `replica.finish` spans (ReplicaCore.begin_step and finish_step, less
the backend and garbage-collection spans inside them), per engine step, in
the traced part of the window."""
from benchmarks.chip import spans


def read(rec):
    p = spans.program(rec)
    if p is None:
        return None
    idle = sum(p["spans"].get(n, {}).get("idle_self_s", 0.0)
               for n in ("replica.admit", "replica.finish"))
    return 1e3 * idle / p["engine_steps"]
