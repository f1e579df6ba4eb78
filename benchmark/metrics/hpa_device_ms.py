"""Device milliseconds of the `hpa_pass` phase (the horizontal pod
autoscaler's pass of a window) per simulated window, or per pump round in a
served cell (benchmark/phase_times.py). None where the program has no map."""

from benchmark import phase_times


def read(run):
    return phase_times.device_ms(run, "hpa_pass")
