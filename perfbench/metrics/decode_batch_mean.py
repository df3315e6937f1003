"""Sequences advanced per batched decode tick in the host window: tokens
decoded over ticks, both counted around the scheduler's decode callback."""


def read(run):
    ticks = run.host_ticks()
    return sum(t[2] for t in ticks) / len(ticks) if ticks else None
