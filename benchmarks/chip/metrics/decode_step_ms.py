"""Step programs: device time per execution of decode_step (trace)."""


def read(rec):
    runs = [c["programs"].get("decode_step") for c in rec["trace"]["chips"]]
    runs = [p for p in runs if p and p["calls"]]
    if not runs:
        return None
    return 1e3 * sum(p["time_s"] for p in runs) / sum(p["calls"] for p in runs)
