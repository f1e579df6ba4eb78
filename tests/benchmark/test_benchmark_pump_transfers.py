"""`pump_transfers_per_round` on a hand-filled recorder, beside the cases of
test_benchmark_program_spans.py: the growth of the two counters inside the
window over the `pump` rounds that started in it, None where a counter never
counted (the parent of PR 33), where no round ran, or where the program has no
recorder; and the traced rehearsal of the what-if cell, which reports it."""

import time

import pytest

from benchmark import program_spans
from benchmark.harness import reader

from test_benchmark_harness import manifest_metrics, run_cell
from test_benchmark_program_spans import MS, filled, run_of  # noqa: F401 (filled is a fixture)

NAME = "pump_transfers_per_round"


def fill(tracer, rounds):
    """`rounds` = [(down, up), ...], one `pump` row and its counts a round;
    returns the window (t0, seconds) that holds exactly those rounds."""
    pump = program_spans._program()[1].index("pump")
    tracer.count("pump_transfers_down", 40)  # set-up's rounds
    tracer.count("pump_transfers_up", 70)
    tracer.end(pump, time.perf_counter_ns() - 2 * MS, dur=MS, ident=0)
    time.sleep(0.002)
    t0 = time.perf_counter()
    for k, (down, up) in enumerate(rounds, start=1):
        start = time.perf_counter_ns()
        if down:
            tracer.count("pump_transfers_down", down)
        if up:
            tracer.count("pump_transfers_up", up)
        tracer.end(pump, start, dur=1000, ident=k)
    seconds = time.perf_counter() - t0
    time.sleep(0.002)
    tracer.count("pump_transfers_down", 500)  # after the window closed
    tracer.count("pump_transfers_up", 500)
    tracer.end(pump, time.perf_counter_ns(), dur=1000, ident=99)
    return t0, seconds


@pytest.mark.parametrize(
    "rounds,expected",
    [
        ([(1, 2), (1, 2), (1, 2)], 3.0),  # every round drains and admits
        ([(1, 2), (0, 1), (0, 1), (1, 4)], 2.5),  # two rounds only dispatch; one installs two lane ranges
        ([(15 + 3 * 7, 30)], 66.0),  # what the per-leaf transport of the parent would have read
    ],
)
def test_transfers_per_round_is_the_growth_over_the_rounds(filled, rounds, expected):
    tracer, _ = filled
    t0, seconds = fill(tracer, rounds)
    assert reader(NAME).read(run_of(t0, seconds)) == expected


@pytest.mark.parametrize("missing", ["pump_transfers_down", "pump_transfers_up", "both", "rounds", "recorder"])
def test_absent_counters_or_rounds_read_as_none(filled, monkeypatch, missing):
    """A program that never counted one of the two (the parent), a window in
    which no round started, no recorder at all: no number and no error."""
    tracer, add = filled
    if missing == "recorder":
        monkeypatch.setattr(program_spans, "_program", lambda: None)
    else:
        for name in ("pump_transfers_down", "pump_transfers_up"):
            if missing not in (name, "both"):
                tracer.count(name, 3)
        if missing != "rounds":
            add("pump", 5, 1, 1)
    assert reader(NAME).read(run_of()) is None


def test_traced_whatif_rehearsal_reports_it(capsys):
    assert NAME in manifest_metrics("per_layer", "autoscaled.whatif")
    assert NAME not in manifest_metrics("per_layer", "autoscaled.stream")
    rc, lines = run_cell(capsys, "autoscaled.whatif", trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    # a readback, an admission and a dispatch at most in a round that does all three; never the parent's dozens
    assert 1.0 <= result["metrics"][NAME]["value"] <= 4.0, result["metrics"]
    assert result["metrics"][NAME]["unit"] == "count"
