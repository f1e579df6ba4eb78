"""Device milliseconds of the `slide` phase (the pod window's slide: its
shift, the capacity read that triggers it, and the move of the pod planes)
per simulated window (benchmark/phase_times.py). None where the program has
no map."""

from benchmark import phase_times


def read(run):
    return phase_times.device_ms(run, "slide")
