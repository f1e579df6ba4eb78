"""Device milliseconds of the cluster autoscaler's phases, `ca_pass` (scale-up
and scale-down, their kernels included) plus `ca_reclaim` (the slot
compaction), per simulated window, or per pump round in a served cell
(benchmark/phase_times.py). None where the program has no map."""

from benchmark import phase_times


def read(run):
    return phase_times.device_ms(run, "ca_pass", "ca_reclaim")
