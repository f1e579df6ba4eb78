"""Device milliseconds of the `cycle` phase (the scheduling cycle: queue,
candidates, the decision kernels and their wrappers' `kernel_io`, the commit)
per simulated window, or per pump round in a served cell, from the program's
op-to-phase map joined with `trace.op_self_s` (benchmark/phase_times.py).
None where the program has no map."""

from benchmark import phase_times


def read(run):
    return phase_times.device_ms(run, "cycle")
