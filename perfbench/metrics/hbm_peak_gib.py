"""Peak device memory in use over the run, in GiB, as the device's
allocator reports it after the window."""


def read(run):
    if not run.memory or "peak_bytes_in_use" not in run.memory:
        return None
    return run.memory["peak_bytes_in_use"] / 2 ** 30
