"""Mean prefill, in milliseconds: the host clock around the scheduler's
prefill callback (one prompt through the single-sequence prefill plan,
until its first token is on the host), over the prefills that end in the
host window."""


def read(run):
    ps = run.host_prefills()
    return 1000.0 * sum(t1 - t0 for t0, t1, _ in ps) / len(ps) if ps else None
