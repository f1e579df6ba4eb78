"""Device milliseconds of the `node_faults` scope per simulated window: what
crash and recovery add to the event application outside the event kernel (the
two kinds' masks and counts a chunk, the crash plane's merge, the counters,
the downtime look-up, the per-pod crash attribution, the reschedule order of
a dead node's pods), nested in `events`, from the program's op-to-phase map
joined with `trace.op_self_s` (benchmark/phase_times.py): the ops whose
innermost phase it is. Nothing where the program has no map or no such
phase."""

from benchmark import phase_times

PHASE = "node_faults"


def read(run):
    times = phase_times.read(run)
    if times is None or PHASE not in times.inner_s:
        return None
    return phase_times.device_ms(run, PHASE, innermost=True)
