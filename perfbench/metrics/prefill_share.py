"""Share of the host window, in percent, that the scheduler's thread
spent prefilling: the time inside its prefill callback, clipped to the
host window, over the window's length.  Prefill runs serially ahead of
the next decode tick, so every decoding sequence waits through it."""


def read(run):
    a, b = run.host_window
    spent = sum(max(0.0, min(t1, b) - max(t0, a))
                for t0, t1, _ in run.recorder.prefills)
    return 100.0 * spent / (b - a) if spent > 0 else None
