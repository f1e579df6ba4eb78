"""Device milliseconds of the `events` phase (the window's event application:
the razor's predicate, the slab read, the event loop with its scatter kernel,
the frees, the wake) per simulated window, or per pump round in a served cell,
from the program's op-to-phase map joined with `trace.op_self_s`
(benchmark/phase_times.py). None where the program has no map."""

from benchmark import phase_times


def read(run):
    return phase_times.device_ms(run, "events")
