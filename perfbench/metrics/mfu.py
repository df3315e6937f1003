"""Model FLOP/s utilization of the whole serving step, in percent: the
operations the tokens received in the host window needed (a prompt's
prefill for a request's first token, one decode step at its context
length for each later one), per second of that window, over the chip's
bf16 peak.  The configuration computes in f32, which has no published
peak."""

from perfbench import flops


def read(run):
    if run.peak is None:
        return None
    work = 0
    for r in run.requests:
        p = len(r["prompt"])
        for k, t in enumerate(r["t"]):
            if run.in_host_window(t):
                work += (flops.prefill_flops(run.cfg, p) if k == 0
                         else flops.decode_token_flops(run.cfg, p + k))
    if not work:
        return None
    t0, t1 = run.host_window
    return 100.0 * work / (t1 - t0) / run.peak["bf16_flops_per_s"]
