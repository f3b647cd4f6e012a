"""Engine backend: Engine.step span time in which the engine's own chip
ran no operation, per step, in the traced part of the window."""


def read(rec):
    t = rec["trace"]
    if not t["engine_steps"]:
        return None
    return 1e3 * t["engine_host_s"] / t["engine_steps"]
