"""Median duration of the fleet's admission step (`pump_admit`: seeding idle
lanes from the queue, from the first write of a lane's scenario row to the end
of `set_lane_plan`) over the window's pump rounds that admitted."""

from benchmark import program_spans
from benchmark.spans import median


def read(run):
    rows = program_spans.window_rows(run)
    admits = rows.of("pump_admit") if rows is not None else ()
    return program_spans.ms(median(admits[:, program_spans.DUR])) if len(admits) else None
