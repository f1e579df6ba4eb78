"""`slide_device_ms` (PR 42): device milliseconds of the `slide` phase a
simulated window, read as `hpa_device_ms` reads its phase. On the recorded
neutral trace of test_benchmark_trace_reduce.py under a hand-written map that
puts two ops under `slide`; listed for the two cells whose pod window slides
and for none of the seven that hold their whole trace resident or step lanes;
None where the program has no map (a commit before PR 39), the run no trace;
and the traced rehearsal of `autoscaled.stream`, the plumbing end to end
(never a device number)."""

import json
import os

import pytest

from benchmark import harness, phase_times
from benchmark.harness import reader
from kubernetriks_tpu.telemetry.tracer import DEVICE_PHASES

from test_benchmark_harness import ROOT, manifest_metrics, run_cell
from test_benchmark_phase_times import WINDOWS, ops, run_of, summary  # noqa: F401 (fixtures)
from test_benchmark_program_spans import lines_of

SLIDING = {"stream": "autoscaled.stream", "replay": "alibaba1313.replay"}
with open(os.path.join(ROOT, "BENCHMARK.json")) as _fh:
    CELLS = [cell["name"] for cell in json.load(_fh)["workloads"]]
NEVER_SLIDE = [cell for cell in CELLS if cell not in SLIDING.values()]


def slide_map(ops):  # noqa: F811
    """The superspan as a traced window sees it: the slide's block moves
    (two fusions, one known by its scope and one, an operand copy of the
    branch, through its consumer), the window body round them."""
    n = [name for name, _ in ops[:6]]
    return {
        "run_superspan[2048, 8192]@engine1": {
            n[0]: ("cycle", "cycle", "scope"),
            n[1]: ("slide", "slide", "scope"),
            n[2]: ("events", "events", "scope"),
            n[3]: ("slide", "slide", "consumer"),
            n[4]: ("ca_pass", "ca_pass", "scope"),
            n[5]: None,
        }
    }


@pytest.fixture
def mapped(monkeypatch, ops):  # noqa: F811
    monkeypatch.setattr(
        phase_times, "_program", lambda: (lambda since_ns=0, until_ns=None: slide_map(ops), DEVICE_PHASES)
    )


@pytest.mark.parametrize("suffix", sorted(SLIDING))
def test_slide_device_ms_reads_the_slide_phase(suffix, summary, ops, mapped, capsys):  # noqa: F811
    metric = f"slide_device_ms.{suffix}"
    assert manifest_metrics("per_layer", SLIDING[suffix])[metric] == "ms"
    value = reader(metric).read(run_of(summary, suffix))
    assert value == pytest.approx((ops[1][1] + ops[3][1]) * 1e3 / WINDOWS, rel=1e-9) and value > 0
    (line,) = lines_of(capsys, "phases")
    assert line["top_ms"]["slide"] == pytest.approx(value, rel=1e-9)
    assert line["inherited_ms"]["slide"] == pytest.approx(ops[3][1] * 1e3 / WINDOWS, rel=1e-9)


@pytest.mark.parametrize("suffix", sorted(SLIDING))
def test_slide_device_ms_moves_its_cells_own_metric(suffix):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        manifest = json.load(fh)
    (entry,) = [m for m in manifest["per_layer"] if m["name"] == f"slide_device_ms.{suffix}"]
    assert entry["workloads"] == [SLIDING[suffix]]
    assert entry["layer"] == "window body" and entry["source"] == "device_trace" and entry["better"] == "lower"
    (moved,) = [m for m in manifest["end_to_end"] if m["name"] == entry["moves"]]
    assert SLIDING[suffix] in moved["workloads"]
    # one file reads both suffixes
    assert harness.reader_path(entry["name"]).endswith(os.path.join("metrics", "slide_device_ms.py"))


@pytest.mark.parametrize("cell", NEVER_SLIDE)
def test_a_cell_that_never_slides_does_not_list_it(cell):
    assert len(NEVER_SLIDE) == 7
    assert not [name for name in manifest_metrics("per_layer", cell) if name.startswith("slide_device_ms")]


@pytest.mark.parametrize("suffix", sorted(SLIDING))
def test_without_a_map_or_a_trace_it_reads_none(suffix, summary, monkeypatch, capsys):  # noqa: F811
    monkeypatch.setattr(phase_times, "_program", lambda: None)
    read = reader(f"slide_device_ms.{suffix}").read
    assert read(run_of(summary, suffix)) is None
    assert read(run_of(None, suffix)) is None
    assert lines_of(capsys, "phases") == []


def test_a_window_that_never_slid_reads_zero(summary, ops, monkeypatch):  # noqa: F811
    """A program with the map and no op under `slide` in the traced window
    (a replay window between two slides): 0.0, a number, not None."""
    programs = {"run_superspan[4096, 16384]@engine1": {ops[0][0]: ("cycle", "cycle", "scope")}}
    monkeypatch.setattr(phase_times, "_program", lambda: (lambda since_ns=0, until_ns=None: programs, DEVICE_PHASES))
    assert reader("slide_device_ms.replay").read(run_of(summary, "replay")) == 0.0


def test_traced_stream_rehearsal_reports_the_slide(capsys, monkeypatch, tmp_path):
    """The stream cell's traced rehearsal slides on the device (every job's
    superspans complete slides) and reports the phase's time, equal to the
    `phases` line's; its trace under a directory of this test's own."""
    plain = harness.Harness.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self._trace_dir = str(tmp_path / ("trace-" + self.cell.name))

    monkeypatch.setattr(harness.Harness, "__init__", init)
    rc, lines = run_cell(capsys, "autoscaled.stream", trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    (window,) = [row for row in lines if row.get("line") == "window"]
    assert window["dispatch_stats"]["superspan_spans"] >= window["jobs"] > 0
    (line,) = [row for row in lines if row.get("line") == "phases"]
    got = result["metrics"]["slide_device_ms.stream"]
    assert got["unit"] == "ms" and got["value"] > 0
    assert got["value"] == pytest.approx(line["top_ms"]["slide"], rel=1e-9)
    assert got["value"] < result["metrics"]["window_device_ms.stream"]["value"]
