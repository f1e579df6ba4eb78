"""Device time by phase (benchmark/phase_times.py and the six readers over it)
on the recorded neutral trace of test_benchmark_trace_reduce.py and a
hand-written op-to-phase map: phases + unscoped equal the ops' self time (the
busy time within a percent), a name two programs put in different phases is
unscoped, each reader's value for each of its suffixes, None for all six
where the program has no map (the parent of PR 39); and the traced rehearsal
of `autoscaled.stream`, the plumbing end to end (never a device number), with
its trace under a directory of its own."""

import json
import os
from types import SimpleNamespace

import pytest

from benchmark import harness, phase_times, trace_reduce
from benchmark.harness import reader
from kubernetriks_tpu.telemetry.tracer import DEVICE_PHASES

from test_benchmark_harness import manifest_metrics, run_cell
from test_benchmark_program_spans import WINDOW_S, WINDOW_T0_S, filled, lines_of  # noqa: F401 (filled is a fixture)

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
READERS = {
    "events_device_ms": ("batch", "stream", "serve"),
    "cycle_device_ms": ("batch", "stream", "serve"),
    "kernel_io_device_ms": ("batch", "stream", "serve"),
    "hpa_device_ms": ("stream", "serve"),
    "ca_device_ms": ("stream", "serve"),
    "unscoped_device_share": ("batch", "stream", "serve"),
}
CELL_OF = {"batch": "sched1k.montecarlo", "stream": "autoscaled.stream", "serve": "autoscaled.whatif"}
WINDOWS, ROUNDS = 40, 5


@pytest.fixture(scope="module")
def summary():
    with open(os.path.join(DATA, "sched1k_montecarlo_v5e.trace.json")) as fh:
        return trace_reduce.reduce_events(trace_reduce.TraceEvents.from_json(json.load(fh)))


@pytest.fixture(scope="module")
def ops(summary):
    """The trace's ops, largest self time first: [(name, seconds)]."""
    return sorted(summary.op_self_s.items(), key=lambda kv: kv[1], reverse=True)


def programs_of(ops):
    """A hand-written map over the trace's eleven largest ops, as two
    programs carry them. The window program: a kernel and its wrapper's pad
    in the cycle and in the event application, one op in each autoscaler
    phase, a copy nothing places. The other program shares three names with
    it: one under the SAME phases (no conflict), one under others (goes to
    neither), one it gives no phase (stays the window program's)."""
    n = [name for name, _ in ops[:11]]
    window = {
        n[0]: ("cycle", "cycle", "scope"),
        n[1]: ("events", "events", "scope"),
        n[2]: ("cycle", "kernel_io", "scope"),
        n[3]: ("events", "kernel_io", "consumer"),  # a copy that feeds the event kernel's pad
        n[4]: ("hpa_pass", "hpa_pass", "scope"),
        n[5]: ("ca_pass", "ca_pass", "scope"),
        n[6]: ("ca_reclaim", "ca_reclaim", "producer"),
        n[7]: None,
        n[8]: ("slide", "slide", "scope"),
        n[9]: ("bookkeeping", "bookkeeping", "scope"),
    }
    other = {
        n[0]: ("cycle", "cycle", "consumer"),  # the same phases, however known: no conflict
        n[8]: ("bookkeeping", "bookkeeping", "scope"),
        n[10]: ("bookkeeping", "bookkeeping", "scope"),
        n[1]: None,  # a program that gives a name no phase does not contest it
    }
    return {"run_windows[121]@engine1": window, "reset_lanes[]@engine1": other}


@pytest.fixture
def mapped(monkeypatch, ops):
    calls = []

    def program_phases(since_ns=0, until_ns=None):
        calls.append((since_ns, until_ns))
        return programs_of(ops)

    monkeypatch.setattr(phase_times, "_program", lambda: (program_phases, DEVICE_PHASES))
    return calls


def run_of(summary, suffix):
    """A run as the readers see it: a batch cell counts the windows it
    stepped, a served cell none (its unit is the `pump` round)."""
    return SimpleNamespace(
        trace=summary,
        counters={} if suffix == "serve" else {"windows_stepped": WINDOWS},
        spans=SimpleNamespace(window_t0=WINDOW_T0_S, rows=[]),
        window_s=WINDOW_S,
        process_t0=0.0,
    )


def expected(name, ops, summary, units):
    s = [seconds for _, seconds in ops]
    ms = 1e3 / units
    scoped = sum(s[:7]) + s[9] + s[10]  # s[7] has no op_name, s[8] is the conflict
    return {
        "events_device_ms": (s[1] + s[3]) * ms,
        "cycle_device_ms": (s[0] + s[2]) * ms,
        "kernel_io_device_ms": (s[2] + s[3]) * ms,
        "hpa_device_ms": s[4] * ms,
        "ca_device_ms": (s[5] + s[6]) * ms,
        "unscoped_device_share": 100.0 * (sum(s) - scoped) / summary.busy_s,
    }[name]


def read(name, suffix, run):
    metric = f"{name}.{suffix}"
    assert metric in manifest_metrics("per_layer", CELL_OF[suffix])
    return reader(metric).read(run)


def check_reader(name, suffix, summary, ops, filled):
    _, add = filled
    for k in range(ROUNDS):
        add("pump", 10 * k, 5, k)
    add("pump", -50, 5, 99)  # before the window opened: not its round
    value = read(name, suffix, run_of(summary, suffix))
    assert value == pytest.approx(expected(name, ops, summary, ROUNDS if suffix == "serve" else WINDOWS), rel=1e-9)
    assert value > 0


@pytest.mark.parametrize("suffix", READERS["events_device_ms"])
def test_events_device_ms(suffix, summary, ops, mapped, filled):  # noqa: F811
    check_reader("events_device_ms", suffix, summary, ops, filled)


@pytest.mark.parametrize("suffix", READERS["cycle_device_ms"])
def test_cycle_device_ms(suffix, summary, ops, mapped, filled):  # noqa: F811
    check_reader("cycle_device_ms", suffix, summary, ops, filled)


@pytest.mark.parametrize("suffix", READERS["kernel_io_device_ms"])
def test_kernel_io_device_ms(suffix, summary, ops, mapped, filled):  # noqa: F811
    check_reader("kernel_io_device_ms", suffix, summary, ops, filled)


@pytest.mark.parametrize("suffix", READERS["hpa_device_ms"])
def test_hpa_device_ms(suffix, summary, ops, mapped, filled):  # noqa: F811
    check_reader("hpa_device_ms", suffix, summary, ops, filled)


@pytest.mark.parametrize("suffix", READERS["ca_device_ms"])
def test_ca_device_ms(suffix, summary, ops, mapped, filled):  # noqa: F811
    check_reader("ca_device_ms", suffix, summary, ops, filled)


@pytest.mark.parametrize("suffix", READERS["unscoped_device_share"])
def test_unscoped_device_share(suffix, summary, ops, mapped, filled):  # noqa: F811
    check_reader("unscoped_device_share", suffix, summary, ops, filled)


def test_phases_and_unscoped_partition_the_busy_time(summary, ops, mapped, capsys):
    run = run_of(summary, "batch")
    times = phase_times.read(run)
    total = sum(summary.op_self_s.values())
    assert times.total_s == pytest.approx(total, rel=1e-12)
    assert times.total_s == pytest.approx(summary.busy_s, rel=0.01)  # self times partition the busy time
    assert set(times.top_s) == set(times.inner_s) == set(DEVICE_PHASES)
    # the innermost phases share out the same seconds: `kernel_io` takes from its parents
    assert sum(times.inner_s.values()) == pytest.approx(sum(times.top_s.values()), rel=1e-12)
    assert times.top_s["kernel_io"] == 0.0 and times.inner_s["kernel_io"] > 0
    assert times.inner_s["cycle"] < times.top_s["cycle"]
    # a name two programs put under DIFFERENT phases goes to neither ...
    (name8, s8), (name0, s0) = ops[8], ops[0]
    assert times.top_s["slide"] == 0.0 and times.conflicts_s == pytest.approx(s8)
    assert (name8, s8) in times.unscoped_ops
    # ... one they agree on stays, an op without `op_name` and one no program carries are unscoped
    assert times.top_s["cycle"] >= s0 and times.ops["cycle"][0] == (name0, s0)
    assert ops[7] in times.unscoped_ops and ops[11] in times.unscoped_ops
    assert times.unmapped_s == pytest.approx(sum(s for _, s in ops[11:]))
    assert times.top_s["bookkeeping"] == pytest.approx(ops[9][1] + ops[10][1])
    assert times.top_s["events"] == pytest.approx(ops[1][1] + ops[3][1])  # not contested by the program that gives it none
    # what a phase holds only through its consumers or producers is said apart
    assert times.inherited_s["events"] == pytest.approx(ops[3][1])
    assert times.inherited_s["ca_reclaim"] == pytest.approx(ops[6][1]) and times.inherited_s["cycle"] == 0.0
    # one `phases` line, the map asked for once however many readers read
    for name, suffixes in READERS.items():
        reader(f"{name}.{suffixes[0]}").read(run)
    lo = int(WINDOW_T0_S * 1e9)
    assert mapped == [(lo, lo + int(WINDOW_S * 1e9))]  # and only of the programs dispatched in the window
    (line,) = lines_of(capsys, "phases")
    assert line["per"] == "window" and line["units"] == WINDOWS
    assert set(line["top_ms"]) == set(DEVICE_PHASES) and line["inner_ms"]["kernel_io"] > 0
    assert line["largest"]["cycle"][0][0] == name0 and len(line["largest"]["cycle"]) <= 3
    assert line["total_ms"] == pytest.approx(total * 1e3 / WINDOWS)
    assert set(line["inherited_ms"]) == {"events", "ca_reclaim"}
    assert line["unscoped_over_1pct"] and all(ms > 0.01 * line["busy_ms"] for _, ms in line["unscoped_over_1pct"])
    assert line["program_phases_s"] >= 0 and set(line["programs"]) == set(programs_of(ops))


@pytest.mark.parametrize("name", sorted(READERS))
def test_the_parent_reads_none(name, summary, monkeypatch, capsys):
    """A program without `program_phases` (a commit before PR 39), a run
    without a trace, a served window in which no round started: no number,
    no `phases` line, no error."""
    monkeypatch.setattr(phase_times, "_program", lambda: None)
    for suffix in READERS[name]:
        assert read(name, suffix, run_of(summary, suffix)) is None
        untraced = run_of(None, suffix)
        assert read(name, suffix, untraced) is None
    assert lines_of(capsys, "phases") == []


def test_a_served_window_without_a_round_reads_none(summary, ops, mapped, filled):  # noqa: F811
    assert read("events_device_ms", "serve", run_of(summary, "serve")) is None
    assert read("unscoped_device_share", "serve", run_of(summary, "serve")) > 0  # a share needs no unit


def test_a_long_instruction_name_is_cut_as_the_trace_cuts_it():
    long = "fused_select_cycle_commit." + "9" * 120
    assert len(trace_reduce.short_name("%" + long + " = (s32[8]) custom-call()")) == phase_times.NAME_CUT
    names = phase_times.join_names({"a": {long: ("cycle", "cycle", "scope")}, "b": {long[:100]: ("events", "events", "scope")}})
    assert names == {trace_reduce.short_name(long): phase_times.CONFLICT}


def test_the_import_finds_the_programs_map():
    program_phases, device_phases = phase_times._program()
    assert device_phases == DEVICE_PHASES and callable(program_phases)


def test_traced_stream_rehearsal_reports_the_phases(capsys, monkeypatch, tmp_path):
    """The plumbing end to end on the CPU: the stream cell's traced rehearsal
    reports every metric of its group and prints the `phases` line, phases +
    unscoped equal to the ops' self time. Its trace goes under a directory
    of this test's own (three files already trace `autoscaled.whatif` into
    the one `.bench_out/trace-<cell>` the harness would pick)."""
    plain = harness.Harness.__init__

    def init(self, *args, **kwargs):
        plain(self, *args, **kwargs)
        self._trace_dir = str(tmp_path / ("trace-" + self.cell.name))

    monkeypatch.setattr(harness.Harness, "__init__", init)
    rc, lines = run_cell(capsys, "autoscaled.stream", trace=1)
    result = lines[-1]
    assert rc == 0 and result["correct"] is True
    wanted = {f"{name}.stream" for name in READERS}
    assert wanted <= set(manifest_metrics("per_layer", "autoscaled.stream"))
    got = {k: v for k, v in result["metrics"].items() if k in wanted}
    assert set(got) == wanted
    assert all(v["unit"] == ("%" if k.startswith("unscoped") else "ms") for k, v in got.items())
    (line,) = [row for row in lines if row.get("line") == "phases"]
    # every autoscaler phase ran and was found; the rehearsal's "device" is
    # the CPU client's threads, so only the sums are held to anything
    for phase in ("events", "cycle", "hpa_pass", "ca_pass"):
        assert line["top_ms"][phase] > 0, phase
    assert line["total_ms"] == pytest.approx(sum(line["top_ms"].values()) + line["unscoped_ms"], rel=1e-9)
    assert got["hpa_device_ms.stream"]["value"] == pytest.approx(line["top_ms"]["hpa_pass"], rel=1e-9)
    assert got["ca_device_ms.stream"]["value"] == pytest.approx(
        line["top_ms"]["ca_pass"] + line["top_ms"]["ca_reclaim"], rel=1e-9
    )
    assert any(name.startswith("run_superspan[") or name.startswith("run_windows") for name in line["programs"])
    assert not os.path.exists(tmp_path / "trace-autoscaled.stream")  # reduced and removed, as the harness does
