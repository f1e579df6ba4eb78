"""`events_compacted_share` (PR 49): of the cluster-windows in which a cluster
had more slab events due than one pass of the event chunk loop applies, the
share that finished in a lane tile of the batch's deep ones, in percent, from
the program's `events_compacted` over its `events_deep`. The reader on
counters set by hand; None where the program publishes none (a commit before
PR 49, a batch of one lane tile) or no window was deep; its `BENCHMARK.json`
entry, found by its name; and the traced rehearsal of
`sched1k-backlog.bursts`, one tile, whose line leaves the metric out."""

import contextlib
import io
import json
import os

import pytest

from benchmark import harness
from benchmark import run as bench_run
from benchmark.harness import reader

from test_benchmark_harness import ROOT, manifest_metrics

METRIC = "events_compacted_share"
BURSTS = "sched1k-backlog.bursts"
COUNTERS = ("events_deep", "events_compacted")


@pytest.fixture
def counters():
    """The program recorder's counters with the loop's two set aside, put
    back as they were."""
    from kubernetriks_tpu.telemetry import recorder

    held = recorder().counters
    saved = {k: held.pop(k) for k in COUNTERS if k in held}
    yield held
    for key in COUNTERS:
        held.pop(key, None)
    held.update(saved)


@pytest.mark.parametrize(
    "deep,compacted,share",
    [(2_500, 2_500, 100.0), (3_750, 2_500, 100.0 * 2_500 / 3_750), (80, 0, 0.0)],
    ids=["every-burst", "with-a-jobs-first-window", "too-few-past-the-chunk"],
)
def test_the_reader_divides_the_two_counters(counters, deep, compacted, share):
    counters.update(events_deep=deep, events_compacted=compacted)
    assert reader(METRIC).read(None) == pytest.approx(share, rel=1e-12)


@pytest.mark.parametrize(
    "published",
    [{}, {"events_deep": 5}, {"events_compacted": 0}, {"events_deep": 0, "events_compacted": 0}],
    ids=["no-counters", "no-compacted", "no-deep", "no-window-was-deep"],
)
def test_without_the_counters_or_a_deep_window_it_reads_none(counters, published):
    counters.update(published)
    assert reader(METRIC).read(None) is None


def test_without_a_program_recorder_it_reads_none(monkeypatch):
    from benchmark import program_spans

    monkeypatch.setattr(program_spans, "_program", lambda: None)
    assert reader(METRIC).read(None) is None


def test_the_entry_lists_the_bursts_cell_and_moves_its_rate():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "window body", "moves": "decisions_per_s", "workloads": [BURSTS],
    }
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert BURSTS in moved["workloads"]
    assert harness.reader_path(METRIC).endswith(os.path.join("metrics", METRIC + ".py"))
    for cell in manifest["workloads"]:
        assert (METRIC in manifest_metrics("per_layer", cell["name"])) == (cell["name"] == BURSTS)


def test_traced_bursts_rehearsal_leaves_it_out(tmp_path, monkeypatch, counters):
    """The rehearsal is 4 clusters: one lane tile, which has nothing to choose,
    so its state has no such counters and its program is the one before them
    (tests/test_event_compact.py runs the tile on three). The line leaves the
    metric out, as the parent's does, and is a result all the same. Its trace
    under a directory of this test's own (PERF.md section 7: traced
    rehearsals of one cell race)."""
    plain = harness.Harness.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self._trace_dir = str(tmp_path / ("trace-" + BURSTS))

    monkeypatch.setattr(harness.Harness, "__init__", init)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(
            [
                "--workload", BURSTS, "--seed", str(2**31 + 491), "--seconds", "1", "--trace", "1",
                "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", BURSTS + ".json"),
            ]
        )
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["rehearsal"] is True
    assert METRIC not in result["metrics"] and "cycle_compacted_share" in result["metrics"]
    assert not set(COUNTERS) & set(counters)
