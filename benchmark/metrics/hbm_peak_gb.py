"""Peak device memory on the fullest chip after the window, in GB."""


def read(run):
    return run.counters["memory_peak_bytes"] / 1e9
