"""The decode tick's share of its roofline, in percent: the least time
the chip could take for the host window's ticks over the time they took.

The least time of a tick is the larger of its operations at the bf16 peak
and its bytes at the HBM bandwidth (``flops.decode_tick``: every layer
matrix and the output head once, the looked-up embedding rows, the cached
keys and values at the live lengths).  The time taken is the host clock
around the scheduler's decode callback, which ends when the logits are on
the host."""

from perfbench import flops


def read(run):
    ticks = run.host_ticks()
    if not ticks or run.peak is None:
        return None
    least = sum(flops.least_time_s(*flops.decode_tick(run.cfg, b, live),
                                   run.peak)
                for _, _, b, live, _, _ in ticks)
    took = sum(t1 - t0 for t0, t1, *_ in ticks)
    return 100.0 * least / took
