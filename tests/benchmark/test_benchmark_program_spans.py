"""The readers of the program's own spans (benchmark/program_spans.py and the
seven per-layer metrics over it) on a hand-filled recorder: the window cut,
self time, each reduction, None where the spans are absent or the program has
no recorder, the refusal of a window that wrapped out; and the traced
rehearsals of the two cells that report them, every new metric above zero."""

import json
import time
from types import SimpleNamespace

import numpy as np
import pytest

from benchmark import program_spans
from benchmark.harness import reader
from kubernetriks_tpu.telemetry.tracer import PHASE_NAMES, SpanTracer

from test_benchmark_harness import manifest_metrics, run_cell

NEW = ("compile_or_load_s", "superspan_gap_ms", "pump_admit_ms", "pump_drain_ms",
       "query_queue_wait_p95_ms", "query_service_p95_ms", "lane_busy_share")
MS = 1_000_000
WINDOW_T0_S, WINDOW_S = 100.0, 10.0
LO = int(WINDOW_T0_S * 1e9)


def run_of(window_t0=WINDOW_T0_S, window_s=WINDOW_S, harness_spans=(), process_t0=0.0):
    return SimpleNamespace(
        spans=SimpleNamespace(window_t0=window_t0, rows=list(harness_spans)), window_s=window_s,
        process_t0=process_t0,
    )


@pytest.fixture
def filled(monkeypatch):
    """A recorder of its own in the program's place; `add(phase, start_ms,
    dur_ms, ident)` writes a row at `start_ms` after the window opened."""
    tracer = SpanTracer(capacity=64)
    monkeypatch.setattr(program_spans, "_program", lambda: (tracer, PHASE_NAMES))

    def add(phase, start_ms, dur_ms, ident=0):
        tracer.end(PHASE_NAMES.index(phase), LO + int(start_ms * MS), dur=int(dur_ms * MS), ident=ident)

    return tracer, add


def lines_of(capsys, name):
    return [json.loads(l) for l in capsys.readouterr().out.splitlines() if f'"line": "{name}"' in l]


def test_window_cut_takes_the_spans_that_started_inside(filled):
    _, add = filled
    add("pump", -5, 10, 1)  # started before the window opened
    add("pump", 0, 10, 2)
    add("pump", 9_999, 50, 3)  # started inside, ended after the close
    add("pump", 10_000, 1, 4)  # started as it closed
    assert program_spans.window_rows(run_of()).of("pump")[:, program_spans.IDENT].tolist() == [2, 3]
    assert program_spans.window_rows(run_of()).of("a phase the program does not know").shape == (0, 4)
    # set-up: what ENDED before the window opened ...
    add("compile", -300, 200, 0)
    assert program_spans.setup_rows(run_of()).of("compile", "pump")[:, program_spans.DUR].tolist() == [200 * MS]
    # ... and started no earlier than the harness's first span (an earlier run of the process is not set-up)
    add("compile", -900, 100, 1)
    first_span = [("trace_generation", WINDOW_T0_S - 0.5, WINDOW_T0_S - 0.4)]
    rows = program_spans.setup_rows(run_of(harness_spans=first_span)).of("compile")
    assert rows[:, program_spans.IDENT].tolist() == [0]


def test_self_time_charges_a_child_to_its_innermost_parent():
    rows = np.array([[0, 100, 0, 0], [10, 20, 1, 0], [40, 30, 1, 0], [45, 10, 2, 0], [100, 5, 3, 0]])
    assert program_spans.self_ns(rows).tolist() == [50, 20, 20, 10, 5]
    assert program_spans.self_ns(rows[::-1]).tolist() == [5, 10, 20, 20, 50]


def test_compile_or_load_sums_what_ended_before_the_window(filled, capsys):
    tracer, _ = filled
    for name, seconds, ago_s in (("jit(big)", 2.0, 5.0), ("jit(small)", 0.5, 2.0)):
        tracer.compiles.append((name, seconds))
        tracer.end(PHASE_NAMES.index("compile"), LO - int((ago_s + seconds) * 1e9), dur=int(seconds * 1e9),
                   ident=tracer.compiles_recorded)
        tracer.compiles_recorded += 1
    tracer.compile_event("jit(after the run)", 0.25)  # ends now: not before a window opened in the past
    assert reader("compile_or_load_s").read(run_of(window_t0=LO / 1e9)) == 2.5
    (line,) = lines_of(capsys, "compiles")
    assert line["programs"] == 2 and line["largest"] == [["jit(big)", 2.0], ["jit(small)", 0.5]]


def test_superspan_gap_is_the_time_with_nothing_queued(filled, capsys):
    _, add = filled
    # job 1: lead-in 4 ms, a superspan dispatched at 4, done at 60; next at 70, done at 95; tail 5
    add("step_until_time", 0, 100)
    add("stage_wait_feeder", 1, 2, 0)  # in the lead-in: 2 of 19 gap ms
    add("superspan", 4, 1, 1)
    add("stage_prefetch", 5, 10, 1)  # while the device runs: not in a gap
    add("progress_wait", 20, 40, 1)
    add("window_grow", 62, 6, 1)  # between superspans: 6 of 19
    add("superspan", 70, 1, 2)
    add("progress_wait", 71, 24, 2)
    # job 2: one superspan, lead-in 1 ms, no tail
    add("step_until_time", 200, 50)
    add("superspan", 201, 1, 3)
    add("progress_wait", 202, 48, 3)
    # a call with no superspan (a ladder job) is no job of this metric
    add("step_until_time", 400, 50)
    assert reader("superspan_gap_ms").read(run_of()) == pytest.approx((19 + 1) / 2)
    (line,) = lines_of(capsys, "superspan_gap")
    assert line["jobs"] == 2 and line["gap_ms_per_job"] == [19.0, 1.0]
    assert line["shares"] == {"stage_wait_feeder": pytest.approx(2 / 20), "window_grow": pytest.approx(6 / 20)}


def test_pump_metrics_read_the_rounds_of_the_window(filled):
    _, add = filled
    add("pump_admit", -50, 100, 0)  # before the window: left out
    for round_, (admit_ms, drain_ms, wait_ms) in enumerate([(2, 30, 25), (4, 50, 41), (9, 20, 19)], start=1):
        t = round_ * 200
        add("pump", t, 150, round_)
        add("pump_admit", t + 1, admit_ms, round_)
        add("lane_dispatch", t + 20, 5, round_)
        add("pump_drain", t + 60, drain_ms, round_)
        add("result_wait", t + 61, wait_ms, round_)
    add("pump", 900, 100, 4)  # a round that neither admitted nor drained
    assert reader("pump_admit_ms").read(run_of()) == 4.0
    assert reader("pump_drain_ms").read(run_of()) == 5.0  # median of 30-25, 50-41, 20-19


def test_query_percentiles_are_of_the_queries_submitted_in_the_window(filled):
    _, add = filled
    add("query_queue", -20, 5, 99)  # submitted before the window opened
    add("query_service", -15, 9000, 99)  # ... so its service is not read either
    for qid in range(1, 21):
        add("query_queue", qid, qid, qid)  # waits 1..20 ms
        add("query_service", 2 * qid, 10 * qid, qid)
    assert reader("query_queue_wait_p95_ms").read(run_of()) == pytest.approx(19.05)
    assert reader("query_service_p95_ms").read(run_of()) == pytest.approx(190.5)


def test_lane_busy_share_is_the_growth_inside_the_window(filled):
    tracer, _ = filled
    tracer.count("lane_windows_busy", 1000)  # set-up's rounds
    tracer.count("lane_windows_dispatched", 1000)
    time.sleep(0.002)
    t0 = time.perf_counter()
    for busy in (3, 1):
        tracer.count("lane_windows_busy", busy)
        tracer.count("lane_windows_dispatched", 8)
    window_s = time.perf_counter() - t0
    time.sleep(0.002)
    tracer.count("lane_windows_busy", 500)  # after the window closed
    tracer.count("lane_windows_dispatched", 500)
    assert reader("lane_busy_share").read(run_of(t0, window_s)) == 25.0


@pytest.mark.parametrize("name", NEW)
def test_absent_spans_read_as_none(name, filled, monkeypatch):
    """Nothing recorded: no number. No recorder in the program (a commit
    before PR 26, where the import fails): no number and no error."""
    assert reader(name).read(run_of()) is None
    monkeypatch.setattr(program_spans, "_program", lambda: None)
    assert reader(name).read(run_of()) is None


def test_a_window_that_wrapped_out_is_refused(filled):
    tracer, add = filled
    for k in range(70):  # the ring holds 64
        add("pump", k, 0.5, k)
    with pytest.raises(RuntimeError, match="wrapped out"):
        reader("pump_admit_ms").read(run_of())
    with pytest.raises(RuntimeError, match="wrapped out"):
        reader("compile_or_load_s").read(run_of())
    # rows dropped before the window opened do not matter to the window's readers
    late = run_of(window_t0=(LO + 7 * MS) / 1e9)
    assert program_spans.window_rows(late).of("pump")[:, program_spans.IDENT].tolist() == list(range(7, 70))
    for _ in range(20):
        tracer.count("lane_windows_busy")
    tracer._samples = tracer._samples[:8]  # a sample ring that holds 8
    tracer._n_samples = 20
    with pytest.raises(RuntimeError, match="wrapped out"):
        program_spans.counter_deltas(run_of(), "lane_windows_busy")


@pytest.mark.parametrize("workload", ["autoscaled.whatif", "autoscaled.stream"])
def test_traced_rehearsal_reports_every_new_metric(capsys, workload):
    rc, lines = run_cell(capsys, workload, trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    wanted = set(NEW) & set(manifest_metrics("per_layer", workload))
    assert wanted and all(result["metrics"][name]["value"] > 0 for name in wanted), result["metrics"]
    by_line = {row["line"]: row for row in lines if "line" in row}
    # inside agrees with outside: every set-up compile lies in the harness's build or first dispatch
    assert result["metrics"]["compile_or_load_s"]["value"] <= result["metrics"]["engine_build_s"]["value"]
    assert by_line["compiles"]["programs"] == result["metrics"]["compiles_in_setup"]["value"]
    if workload == "autoscaled.stream":
        assert by_line["superspan_gap"]["jobs"] == by_line["window"]["jobs"]
