"""Mean batched decode tick in the host window, in milliseconds, from the
program's own ``decoder_tick_seconds`` histogram: the change of its sum
over the change of its count between the host window's two ends."""


def read(run):
    t0, t1 = run.host_window
    before = [t for t in run.recorder.ticks if t[1] < t0]
    upto = [t for t in run.recorder.ticks if t[1] < t1]
    s0, c0 = (before[-1][4], before[-1][5]) if before else (0.0, 0)
    if not upto or upto[-1][5] == c0:
        return None
    return 1000.0 * (upto[-1][4] - s0) / (upto[-1][5] - c0)
