"""Program executions on the device that start inside an annotated
decode tick, per tick, from the profiler trace."""


def read(run):
    if run.trace is None:
        return None
    return run.trace["launches_per_tick"]
