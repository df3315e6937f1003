"""Share of the traced window, in percent, in which no operation ran on
the device: one minus the union of the operations' intervals over the
window, from the profiler trace."""


def read(run):
    if run.trace is None or run.trace["busy_s"] <= 0:
        return None
    return 100.0 * run.trace["idle_share"]
