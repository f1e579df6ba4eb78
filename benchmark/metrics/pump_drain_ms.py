"""Median self time of the fleet's drain step (`pump_drain` less the
`result_wait` inside it, where the host waits for the device to finish the
round) over the window's pump rounds that drained: result assembly on the
host."""

from benchmark import program_spans
from benchmark.spans import median


def read(run):
    rows = program_spans.window_rows(run)
    if rows is None:
        return None
    nest = rows.of("pump_drain", "result_wait")
    if not len(nest):
        return None
    own = program_spans.self_ns(nest)[nest[:, program_spans.PHASE] == rows.names.index("pump_drain")]
    return program_spans.ms(median(own)) if len(own) else None
