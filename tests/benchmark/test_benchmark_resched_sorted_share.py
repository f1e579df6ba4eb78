"""`resched_sorted_share` (PR 44): of the cluster-windows in which the
reschedule order ranked a removed node's pods, the share that had more of them
than the compacted rank holds (any of which sends its window of the batch to
the sort of the whole pod axis), in percent, from the program's
`resched_rank_sorted` over its `resched_rank_windows`. The reader on counters
set by hand; None where the program publishes none (a commit before PR 44) or
ranked in no window; its `BENCHMARK.json` entry; and the traced rehearsal of
`sched1k-faults.montecarlo`, the plumbing end to end (a count, never a device
number)."""

import contextlib
import io
import json
import os

import pytest

from benchmark import harness
from benchmark import run as bench_run
from benchmark.harness import reader

from test_benchmark_harness import ROOT, manifest_metrics

METRIC = "resched_sorted_share"
FAULTS = "sched1k-faults.montecarlo"
COUNTERS = ("resched_rank_windows", "resched_rank_sorted")


@pytest.fixture
def counters():
    """The program recorder's counters with the rank's two set aside, put
    back as they were."""
    from kubernetriks_tpu.telemetry import recorder

    held = recorder().counters
    saved = {k: held.pop(k) for k in COUNTERS if k in held}
    yield held
    for key in COUNTERS:
        held.pop(key, None)
    held.update(saved)


@pytest.mark.parametrize(
    "windows,by_sort,share",
    [(14_000, 0, 0.0), (14_000, 35, 0.25), (8, 8, 100.0)],
    ids=["never-sorted", "a-few", "always"],
)
def test_the_reader_divides_the_two_counters(counters, windows, by_sort, share):
    counters.update(resched_rank_windows=windows, resched_rank_sorted=by_sort)
    assert reader(METRIC).read(None) == pytest.approx(share, rel=1e-12)


@pytest.mark.parametrize(
    "published",
    [{}, {"resched_rank_windows": 5}, {"resched_rank_sorted": 0}, {"resched_rank_windows": 0, "resched_rank_sorted": 0}],
    ids=["no-counters", "no-sorted", "no-windows", "ranked-in-no-window"],
)
def test_without_the_counters_or_a_ranked_window_it_reads_none(counters, published):
    counters.update(published)
    assert reader(METRIC).read(None) is None


def test_without_a_program_recorder_it_reads_none(monkeypatch):
    from benchmark import program_spans

    monkeypatch.setattr(program_spans, "_program", lambda: None)
    assert reader(METRIC).read(None) is None


def test_the_entry_lists_the_faults_cell_and_moves_its_rate():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == METRIC]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "lower", "source": "program_counter",
        "layer": "window body", "moves": "decisions_per_s", "workloads": [FAULTS],
    }
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert FAULTS in moved["workloads"]
    assert harness.reader_path(METRIC).endswith(os.path.join("metrics", METRIC + ".py"))
    for cell in manifest["workloads"]:
        assert (METRIC in manifest_metrics("per_layer", cell["name"])) == (cell["name"] == FAULTS)


def test_traced_faults_rehearsal_reports_it(tmp_path, monkeypatch):
    """The rehearsal's racks are 4 nodes: no cluster re-queues more than the
    rank's slots in a window, so the share reads 0.0, a number and not None, from
    counters that say the rank ran. Its trace under a directory of this
    test's own (PERF.md section 7: traced rehearsals of one cell race)."""
    plain = harness.Harness.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self._trace_dir = str(tmp_path / ("trace-" + FAULTS))

    monkeypatch.setattr(harness.Harness, "__init__", init)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(
            [
                "--workload", FAULTS, "--seed", str(2**31 + 361), "--seconds", "1", "--trace", "1",
                "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", FAULTS + ".json"),
            ]
        )
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["rehearsal"] is True
    assert result["metrics"][METRIC] == {"value": 0.0, "unit": "%"}
    assert result["metrics"]["pods_interrupted_share"]["value"] > 0

    from kubernetriks_tpu.telemetry import recorder

    published = recorder().counters
    assert published["resched_rank_windows"] > 0 and published["resched_rank_sorted"] == 0
