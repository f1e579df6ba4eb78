"""Host-device transfers a pump round of the window made: growth of the
recorder's `pump_transfers_down` (blocking device-to-host copies: the packed
readback of the finished lanes) plus `pump_transfers_up` (host-to-device puts:
the packed admission, a changed lane's trace rows, a dispatch's window
indices) over the window, divided by the `pump` rounds that started in it.
None where the program has neither counter (a commit before PR 33) or no
round ran."""

from benchmark import program_spans


def read(run):
    grew = program_spans.counter_deltas(run, "pump_transfers_down", "pump_transfers_up")
    rows = program_spans.window_rows(run)
    rounds = len(rows.of("pump")) if rows is not None else 0
    if not grew or not rounds:
        return None
    return (grew["pump_transfers_down"] + grew["pump_transfers_up"]) / rounds
