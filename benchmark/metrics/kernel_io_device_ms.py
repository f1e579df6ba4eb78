"""Device milliseconds of the nested `kernel_io` phase (the pads, transposes,
casts and slices a kernel wrapper marshals its operands and results with,
never the kernel itself), under whichever top-level phase, per simulated
window, or per pump round in a served cell (benchmark/phase_times.py). None
where the program has no map."""

from benchmark import phase_times


def read(run):
    return phase_times.device_ms(run, "kernel_io", innermost=True)
