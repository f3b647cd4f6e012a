"""Router: InProcessRouter.step time not spent inside Engine.step, per
tick, over the window (host clock, spans the benchmark opens around the
program's calls)."""


def read(rec):
    sp = rec["spans"]
    ticks = sp["InProcessRouter.step"]
    if not ticks:
        return None
    total = sum(e - s for s, e in ticks)
    engine = sum(e - s for k, v in sp.items() if k.startswith("Engine.step")
                 for s, e in v)
    return 1e3 * (total - engine) / len(ticks)
