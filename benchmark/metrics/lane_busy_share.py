"""Of the lane-windows the device stepped in the window (every lane's windows
in every pump round, idle lanes too), the share that did a query's work:
growth of the recorder's `lane_windows_busy` over growth of
`lane_windows_dispatched`, in percent."""

from benchmark import program_spans


def read(run):
    grew = program_spans.counter_deltas(run, "lane_windows_busy", "lane_windows_dispatched")
    if not grew or not grew["lane_windows_dispatched"]:
        return None
    return 100.0 * grew["lane_windows_busy"] / grew["lane_windows_dispatched"]
