"""`cycle_compacted_share` (PR 45): of the cluster-cycles whose queue was
deeper than one pass, the share the megakernel's second launch drained (the
cluster brought into a lane tile of the batch's deep ones), in percent, from
the program's `cycle_compacted` over its `cycle_deep`. The reader on counters
set by hand; None where the program publishes none (a commit before PR 45) or
no cycle was deep; its `BENCHMARK.json` entry; and the traced rehearsal of
`sched1k-backlog.bursts`, the plumbing end to end (a count, never a device
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

METRIC = "cycle_compacted_share"
BURSTS = "sched1k-backlog.bursts"
COUNTERS = ("cycle_deep", "cycle_compacted")


@pytest.fixture
def counters():
    """The program recorder's counters with the split's two set aside, put
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
    [(2_500, 2_500, 100.0), (2_500, 2_000, 80.0), (320, 0, 0.0)],
    ids=["every-deep-cycle", "a-tile-overflowed", "one-tile"],
)
def test_the_reader_divides_the_two_counters(counters, deep, compacted, share):
    counters.update(cycle_deep=deep, cycle_compacted=compacted)
    assert reader(METRIC).read(None) == pytest.approx(share, rel=1e-12)


@pytest.mark.parametrize(
    "published",
    [{}, {"cycle_deep": 5}, {"cycle_compacted": 0}, {"cycle_deep": 0, "cycle_compacted": 0}],
    ids=["no-counters", "no-compacted", "no-deep", "no-cycle-was-deep"],
)
def test_without_the_counters_or_a_deep_cycle_it_reads_none(counters, published):
    counters.update(published)
    assert reader(METRIC).read(None) is None


def test_without_a_program_recorder_it_reads_none(monkeypatch):
    from benchmark import program_spans

    monkeypatch.setattr(program_spans, "_program", lambda: None)
    assert reader(METRIC).read(None) is None


def test_the_entry_lists_the_bursts_cell_and_moves_its_rate():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    entry = manifest["per_layer"][-1]
    assert entry == {
        "name": METRIC, "unit": "%", "better": "higher", "source": "program_counter",
        "layer": "window body", "moves": "decisions_per_s", "workloads": [BURSTS],
    }
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert BURSTS in moved["workloads"]
    assert harness.reader_path(METRIC).endswith(os.path.join("metrics", METRIC + ".py"))
    for cell in manifest["workloads"]:
        assert (METRIC in manifest_metrics("per_layer", cell["name"])) == (cell["name"] == BURSTS)


def test_traced_bursts_rehearsal_reports_it(tmp_path, monkeypatch):
    """The rehearsal is 4 clusters on the candidate kernel (at 4 clusters the
    gates pick it): one lane tile and no megakernel, so there is no second
    launch to take a deep cycle, and the toy build cannot show the split
    (tests/test_cycle_compact.py runs it on three tiles). What it shows is the
    plumbing: bursts of 40 on a pass of 8 are deep, the counters say so, and
    the share reads 0.0, a number and not None. Its trace under a directory
    of this test's own (PERF.md section 7: traced rehearsals of one cell
    race)."""
    plain = harness.Harness.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self._trace_dir = str(tmp_path / ("trace-" + BURSTS))

    monkeypatch.setattr(harness.Harness, "__init__", init)
    out = io.StringIO()
    with contextlib.redirect_stdout(out):
        rc = bench_run.main(
            [
                "--workload", BURSTS, "--seed", str(2**31 + 451), "--seconds", "1", "--trace", "1",
                "--rehearsal", os.path.join(ROOT, "benchmark", "rehearsal", BURSTS + ".json"),
            ]
        )
    lines = [json.loads(line) for line in out.getvalue().splitlines() if line.startswith("{")]
    result = lines[-1]
    assert rc == 0 and result["correct"] is True and result["rehearsal"] is True
    assert result["metrics"][METRIC] == {"value": 0.0, "unit": "%"}
    assert result["metrics"]["cycle_passes_per_cycle"]["value"] > 1

    from kubernetriks_tpu.telemetry import recorder

    published = recorder().counters
    assert published["cycle_deep"] > 0 and published["cycle_compacted"] == 0
