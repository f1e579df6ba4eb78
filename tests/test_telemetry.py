"""Flight recorder (kubernetriks_tpu/telemetry) — PR 8 mechanics gates.

Two-tier coverage, split to keep tier-1 inside its wall-clock budget:

- The COMPOSED-SCALE gates (HPA + CA + superspan + chaos: telemetry-on
  bit-identical across executors, composed ring columns live, steady-state
  sync budget) ride the existing engines of
  test_superspan.py::test_superspan_composed_bit_identical_under_faults —
  arming the flight recorder there costs zero extra compiles.
- THIS module pins the recorder's mechanics on cheap engines (small
  programs, fast compiles — full-resident for the pair, one sliding
  superspan for the staging pipeline): strict dispatch-stats equality
  telemetry-on vs -off (the no-new-syncs gate),
  ring wrap + pressure-drain losslessness, Chrome trace-event schema
  (spans, flow pairs, counter tracks), checkpoint roundtrip of the ring,
  the <3% overhead gate, the ladder-fallback observable, the tracer
  per-span microbenchmark, and the shared JSON/table render path.
- The host span recorder is ONE process-wide, always-on object (PR 26):
  engines and fleets built with telemetry=False record their spans with
  ids, unchanged in state pytree, dispatch_stats and jit caches; every
  begin/end span is a `ktpu:<phase>` event of a live jax.profiler
  session; compile-log seconds become `compile` rows. Ring rows are read
  as DELTAS of the shared recorder (other tests of the worker write to
  it); `engine.telemetry_report()` is the engine's own (its handle's
  aggregates), exact under any interleaving of engines.
"""

import json
import time

import numpy as np
import pytest

from benchmark import program_spans
from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.state import compare_states, strip_telemetry
from kubernetriks_tpu.telemetry.ring import RING_COLUMNS
from kubernetriks_tpu.telemetry.tracer import (
    PHASE_NAMES,
    PH_WINDOW_CHUNK,
    SpanTracer,
    recorder,
)
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generator import (
    PoissonWorkloadTrace,
    UniformClusterTrace,
)

from test_window_donation_dispatch import _build_dense_sliding

ENDS = (150.0, 300.0, 450.0)


def recorded():
    return recorder().report()["span_events"]["recorded"]


def rows_since(n_before, phase_name=None):
    """The recorder's rows recorded after `n_before` spans had been
    (oldest first), optionally of one phase."""
    rows = recorder().rows()
    rows = rows[len(rows) - (recorded() - n_before):]
    if phase_name is not None:
        rows = rows[rows[:, 2] == PHASE_NAMES.index(phase_name)]
    return rows


def host_annotations(trace_dir):
    """Names of the `ktpu:` events on the host planes of the newest
    jax.profiler capture under `trace_dir`."""
    import glob
    import os

    import jax

    found = sorted(
        glob.glob(os.path.join(trace_dir, "plugins", "profile", "*", "*.xplane.pb"))
    )
    assert found, f"no .xplane.pb under {trace_dir}"
    names = set()
    for plane in jax.profiler.ProfileData.from_file(found[-1]).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                names |= {
                    e.name for e in line.events if e.name.startswith("ktpu:")
                }
    return names


def _build_plain(**kwargs):
    """Cheapest real engine: full-resident, no autoscalers, one small
    run_windows program — the module's workhorse (tier-1 wall-clock:
    the composed/superspan-scale telemetry gates ride test_superspan's
    existing engines instead of recompiling composed programs here)."""
    config = default_test_simulation_config()
    cluster = UniformClusterTrace(8, cpu=64000, ram=128 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=1.0,
        horizon=400.0,
        seed=5,
        cpu=4000,
        ram=4 * 1024**3,
        duration_range=(20.0, 40.0),
    )
    return build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=2,
        max_pods_per_cycle=16,
        fast_forward=False,
        **kwargs,
    )


@pytest.fixture(scope="module")
def cheap_pair():
    """Telemetry-ON vs telemetry-OFF plain runs. telemetry_ring=16 is
    deliberately SMALLER than the executed window count, so the
    pressure-based drain at step_until_time exits must fire mid-run for
    the series to stay lossless."""
    on = _build_plain(telemetry=True, telemetry_ring=16)
    off = _build_plain()
    for end in ENDS:
        on.step_until_time(end)
        off.step_until_time(end)
    return on, off


def test_telemetry_on_is_bit_identical(cheap_pair):
    on, off = cheap_pair
    assert on.dispatch_stats["window_chunks"] > 0
    assert compare_states(strip_telemetry(on.state), off.state) == []
    assert on.metrics_summary() == off.metrics_summary()
    assert on.next_window_idx == off.next_window_idx


def test_telemetry_adds_no_new_syncs(cheap_pair):
    """The dispatch-count regression gate: telemetry must not add a
    single dispatch or blocking readback to the steady-state loop —
    slide_syncs is the budget the lint sync-ok waivers document."""
    on, off = cheap_pair
    assert on.dispatch_stats == off.dispatch_stats


def test_ring_series_is_lossless_and_matches_metrics(cheap_pair):
    """Every executed window has exactly one ring record (the ring
    wrapped several times — capacity 16 < executed windows — so this also
    proves the pressure drain fired at existing boundaries), and the
    per-window decision deltas sum to the run's total decision counter."""
    on, _ = cheap_pair
    executed = on.next_window_idx
    assert executed > on._telemetry_ring_size  # the ring really wrapped
    wins, data = on.telemetry_window_series()
    np.testing.assert_array_equal(wins, np.arange(executed, dtype=np.int32))
    assert on._ring_windows_recorded == executed
    total = on.metrics_summary()["counters"]["scheduling_decisions"]
    assert total > 0
    assert int(data[:, :, RING_COLUMNS.index("decisions")].sum()) == total
    assert int(data[:, :, RING_COLUMNS.index("alive_nodes")].max()) > 0


def test_telemetry_report_shape(cheap_pair):
    on, off = cheap_pair
    rep = on.telemetry_report()
    assert rep["enabled"] and not off.telemetry_report()["enabled"]
    # One ring for the process, one report per engine: `off` ran
    # interleaved with `on` and none of its spans are counted here.
    assert (
        rep["spans"]["window_chunk"]["count"]
        == on.dispatch_stats["window_chunks"]
    )
    assert rep["spans"]["step_until_time"]["count"] == len(ENDS)
    assert rep["spans"]["engine_build"]["count"] == 1
    # Full-resident run: zero slides, zero syncs — budget trivially met
    # (the composed-scale budget gate lives in test_superspan.py).
    assert rep["sync_budget"]["observed_slide_syncs"] == (
        rep["sync_budget"]["steady_state_expected"]
    ) == 0
    assert rep["dispatch_stats"]["ladder_fallbacks"] == 0
    assert rep["ring"]["windows_kept"] == on.next_window_idx


def validate_chrome_trace(path, expect_flows):
    """Chrome trace-event JSON schema check, shared with the superspan
    fault test (which validates a trace WITH async-readback flow pairs):
    X spans with nonnegative durations, process metadata, the device
    ring's sim-time counter track, s/f flows in matched id pairs, and
    every span name drawn from the known phase taxonomy."""
    with open(path) as fh:
        doc = json.load(fh)
    events = doc["traceEvents"]
    assert isinstance(events, list) and events
    phases = {"M": 0, "X": 0, "s": 0, "f": 0, "C": 0}
    flow_ids = {"s": set(), "f": set()}
    for ev in events:
        assert {"ph", "name", "pid"} <= set(ev)
        phases[ev["ph"]] = phases.get(ev["ph"], 0) + 1
        if ev["ph"] == "X":
            assert ev["dur"] >= 0 and ev["ts"] >= 0
        if ev["ph"] in ("s", "f"):
            flow_ids[ev["ph"]].add(ev["id"])
        if ev["ph"] == "C":
            assert ev["args"], "counter event without a value"
    assert phases["X"] > 0, "no host spans"
    assert phases["C"] > 0, "no device-ring counter track"
    assert flow_ids["s"] == flow_ids["f"], (
        "async readback flows must come in matched start/finish pairs"
    )
    if expect_flows:
        assert phases["s"] > 0, "no async-readback flow events"
    # Every span name is a known phase (schema, not free text) — except
    # the lane-swimlane process (pid LANE_PID, PR 17), whose spans are
    # named by the occupying query id ("q<qid>").
    import re

    from kubernetriks_tpu.telemetry import PHASE_NAMES
    from kubernetriks_tpu.telemetry.tracer import LANE_PID

    for ev in events:
        if ev["ph"] == "X":
            if ev["pid"] == LANE_PID:
                assert re.fullmatch(r"q\d+", ev["name"]), (
                    f"lane swimlane span named {ev['name']!r}, expected "
                    "q<qid>"
                )
            else:
                assert ev["name"] in PHASE_NAMES


def test_chrome_trace_schema(cheap_pair, tmp_path):
    """The emitted trace validates (a full-resident run has no async
    readbacks, hence no flow pairs — the superspan fault test validates
    the flow-carrying trace)."""
    on, _ = cheap_pair
    path = on.write_chrome_trace(str(tmp_path / "trace.json"))
    validate_chrome_trace(path, expect_flows=False)


def test_checkpoint_roundtrip_with_telemetry(cheap_pair, tmp_path):
    """The ring is ordinary state: a save→restore roundtrip on a
    telemetry-on engine reproduces it (and the drained series)."""
    pytest.importorskip("orbax.checkpoint")
    on, off = cheap_pair
    path = str(tmp_path / "ckpt")
    on.save_checkpoint(path)
    fresh = _build_plain(telemetry=True, telemetry_ring=16)
    fresh.load_checkpoint(path)
    assert compare_states(fresh.state, on.state) == []
    wins_a, data_a = on.telemetry_window_series()
    wins_b, data_b = fresh.telemetry_window_series()
    # The restored engine re-drains only what the restored ring still
    # holds (capacity 16): the tail of the original series, bit-equal.
    assert len(wins_b) > 0 and set(wins_b) <= set(wins_a)
    np.testing.assert_array_equal(data_b, data_a[-len(wins_b):])
    # Mismatch guard: restoring onto a telemetry-off engine (different
    # state pytree) raises the actionable message, not an opaque orbax
    # structure error — and before touching the engine's state.
    plain = _build_plain()
    with pytest.raises(ValueError, match="telemetry ring mismatch"):
        plain.load_checkpoint(path)
    # The reverse mismatch too: a plain save writes NO meta file at all
    # (full-resident, no ring), and restoring it into an armed engine
    # must raise the same actionable message, not an orbax structure
    # error — the guard runs even with the meta absent.
    plain_path = str(tmp_path / "ckpt_plain")
    off.save_checkpoint(plain_path)
    import os

    assert not os.path.exists(plain_path + ".meta.json")
    with pytest.raises(ValueError, match="telemetry ring mismatch"):
        fresh.load_checkpoint(plain_path)


def test_ring_drain_handles_uneven_spans():
    """Wrap-loss regression: a short call that leaves undrained rows
    under the exit-drain threshold, followed by a call long enough to
    wrap past them, must still produce a lossless series (the entry-side
    guard drains before dispatching the wrapping span)."""
    sim = _build_plain(telemetry=True, telemetry_ring=16)
    sim.step_until_time(60.0)  # 7 windows: below the exit-drain threshold
    sim.step_until_time(180.0)  # 12 more: would overwrite rows 0-2 unguarded
    wins, _ = sim.telemetry_window_series()
    np.testing.assert_array_equal(
        wins, np.arange(sim.next_window_idx, dtype=np.int32)
    )
    assert sim._ring_windows_recorded == sim.next_window_idx


def test_drain_telemetry_rows_survive_donated_dispatches():
    """Explicit mid-run drain (engine.drain_telemetry) vs the
    donated-dispatch aliasing hazard: ring.snapshot forces OWNED numpy
    copies, so rows drained now must stay bit-identical after later
    DONATED dispatches consume (and mutate in place) the device ring
    buffer the fetch may have aliased on CPU."""
    sim = _build_dense_sliding(
        telemetry=True, telemetry_ring=16, donate=True, fuse_slide=True
    )
    sim.step_until_time(120.0)
    rec = sim.drain_telemetry()
    assert rec and rec["window"] == sim.next_window_idx - 1
    assert "occupancy" in rec and "resources" in rec
    wins0, data0 = sim.telemetry_window_series()
    snap = data0.copy()
    sim.step_until_time(400.0)  # donated dispatches consume old buffers
    wins1, data1 = sim.telemetry_window_series()
    np.testing.assert_array_equal(wins1[: len(wins0)], wins0)
    np.testing.assert_array_equal(data1[: len(wins0)], snap)
    # And with telemetry off it degrades to a cheap no-op, not an error.
    off = _build_plain()
    assert off.drain_telemetry() == {}


def test_single_long_call_stays_lossless_on_sliding_engine():
    """The PR 8 known edge, fixed: ONE step_until_time call spanning far
    more windows than the ring stays lossless on engines whose
    steady-state loop has sync points (slides / superspan readbacks) —
    the pressure drain now rides those existing blocks mid-call, so the
    windows_recorded > windows_kept disclosure is reserved for a single
    DISPATCH outrunning the ring, not a single call."""
    sim = _build_dense_sliding(telemetry=True, telemetry_ring=16)
    sim.step_until_time(450.0)  # ~45 windows >> ring capacity, ONE call
    assert sim.next_window_idx > sim._telemetry_ring_size
    assert sim.dispatch_stats["slide_syncs"] > 0  # drains had blocks to ride
    wins, _ = sim.telemetry_window_series()
    np.testing.assert_array_equal(
        wins, np.arange(sim.next_window_idx, dtype=np.int32)
    )
    assert sim._ring_windows_recorded == sim.next_window_idx


def test_series_cap_bounds_host_memory_and_discloses():
    """The host-side series accumulator is BOUNDED (the endurance-run
    guard): past telemetry_series_windows distinct windows the oldest
    rows are pruned, newest kept, and the loss is disclosed in the
    report — the O(T) growth the capacity observatory would otherwise
    reintroduce through its own lossless drains."""
    sim = _build_plain(telemetry=True, telemetry_ring=16)
    sim.telemetry_series_windows = 10
    for end in ENDS:
        sim.step_until_time(end)
    wins, _ = sim.telemetry_window_series()
    assert len(wins) <= 10
    assert wins[-1] == sim.next_window_idx - 1  # newest windows survive
    rep = sim.telemetry_report()
    assert rep["ring"]["series_dropped_windows"] > 0
    assert rep["ring"]["windows_kept"] <= 10


def test_readout_does_not_emit_phantom_export_records():
    """telemetry_report()/telemetry_window_series() force a drain, but a
    drain that re-observes only known rows (fresh_windows == 0) must not
    reach the exporters or re-judge the watchdog — readout stays
    side-effect-free on the JSONL stream."""
    sim = _build_plain(telemetry=True, telemetry_ring=16)
    records = []

    class _Recorder:
        def emit(self, record):
            records.append(record)

    sim.attach_metrics_exporter(_Recorder())
    sim.step_until_time(150.0)
    sim.telemetry_window_series()  # forced drain picking up any residue
    n = len(records)
    assert n > 0
    assert all(r["fresh_windows"] > 0 for r in records)
    for _ in range(3):
        sim.telemetry_report()
    assert len(records) == n, "readout emitted phantom export records"


def test_staged_superspan_records_prefetch_spans(monkeypatch):
    """Over-budget (bounded RefillStage) superspan runs surface the
    staging pipeline in the trace: stage_assemble/stage_put spans for
    every install, stage_prefetch spans for the double-buffered
    successor, and the hit/miss counters feeding
    stage_prefetch_hit_rate — the overlap the flight recorder exists to
    make visible."""
    import kubernetriks_tpu.batched.engine as engine_mod

    monkeypatch.setattr(engine_mod, "_DEVICE_SLIDE_BUDGET_BYTES", 0)
    n0 = recorded()
    sim = _build_dense_sliding(
        telemetry=True, telemetry_ring=16,
        superspan=True, superspan_k=4, superspan_chunk=4,
    )
    assert sim._device_slide is None, "budget monkeypatch did not take"
    for end in ENDS:
        sim.step_until_time(end)
    rep = sim.telemetry_report()
    for phase in ("stage_assemble", "stage_put", "stage_prefetch"):
        assert rep["spans"][phase]["count"] >= 1
        # A stage span carries the ordinal of the superspan it follows.
        ids = rows_since(n0, phase)[:, 3]
        assert len(ids) == rep["spans"][phase]["count"]
        assert ids.min() >= 0 and ids.max() <= sim.dispatch_stats["superspans"]
    hits = rep["counters"].get("stage_prefetch_hit", 0)
    misses = rep["counters"].get("stage_prefetch_miss", 0)
    assert hits + misses >= 1  # at least the initial install counted
    assert rep.get("stage_prefetch_hit_rate", 0) == hits / (hits + misses)


def test_ladder_fallback_counter():
    """A superspan-selected engine forced onto the ladder (log_throughput
    wants per-chunk timings) counts the fallback. One short span keeps the compile bill at two small
    ladder shapes."""
    sim = _build_dense_sliding(superspan=True)
    sim.log_throughput = True
    sim.step_until_time(80.0)
    assert sim.dispatch_stats["superspans"] == 0
    assert sim.dispatch_stats["ladder_fallbacks"] > 0
    assert sim.dispatch_stats["window_chunks"] > 0


def test_tracer_span_cost_microbench():
    """Design bound: begin/end is well under a microsecond each on real
    hardware; the CI gate allows generous container noise but still
    catches an accidental allocation or string format on the record
    path."""
    tr = SpanTracer(capacity=1 << 12)
    spans = tr.handle()  # what an engine holds: its own aggregates too
    n = 20_000
    t_start = time.perf_counter_ns()
    for i in range(n):
        t0 = spans.begin(PH_WINDOW_CHUNK)  # enters the ktpu: TraceAnnotation
        spans.end(PH_WINDOW_CHUNK, t0, ident=i)
    per_span_us = (time.perf_counter_ns() - t_start) / n / 1e3
    assert per_span_us < 10.0, f"{per_span_us:.2f} µs per span"
    assert not tr._open_phase and not tr._open_ann  # every annotation closed
    rep = tr.report()
    # Aggregates exact after the ring wrapped, the recorder's and the
    # handle's alike; the kept rows are the newest, oldest first, each
    # with its id.
    assert rep["spans"]["window_chunk"]["count"] == n
    assert rep["span_events"] == {"recorded": n, "kept": 1 << 12}
    assert spans.report() == rep
    rows = tr.rows()
    np.testing.assert_array_equal(rows[:, 3], np.arange(n - (1 << 12), n))
    assert rep["spans"]["window_chunk"]["total_ms"] >= rows[:, 1].sum() / 1e6
    assert tr.dropped()["spans"] == n - (1 << 12)
    # A second handle on the same recorder starts from nothing.
    other = tr.handle()
    other.count("stage_prefetch_hit")
    assert other.report()["spans"] == {} and spans.report()["counters"] == {}
    assert tr.counters == other.counters == {"stage_prefetch_hit": 1}


def test_tracer_lane_swimlanes_and_query_phases(tmp_path):
    """Query-observatory tracer surface (PR 17): the queue-wait/service
    phases exist in the taxonomy, lane_event renders one pid-LANE_PID
    swimlane per lane with the occupying query id as the span name (plus
    process/thread metadata), the submit->drain flow pairs match, and
    report() discloses the lane-span ring's recorded/kept counts."""
    from kubernetriks_tpu.telemetry.tracer import (
        LANE_PID,
        PH_QUERY_QUEUE,
        PH_QUERY_SERVICE,
    )

    assert PHASE_NAMES[PH_QUERY_QUEUE] == "query_queue"
    assert PHASE_NAMES[PH_QUERY_SERVICE] == "query_service"
    tr = SpanTracer()
    t0 = time.perf_counter_ns()
    fid = tr.flow_start(PH_QUERY_QUEUE)
    tr.end(PH_QUERY_QUEUE, t0, dur=1_000, ident=7)
    tr.end(PH_QUERY_SERVICE, t0 + 1_000, dur=5_000, ident=7)
    tr.lane_event(2, 7, t0 + 1_000, 5_000)
    tr.lane_event(0, 8, t0 + 1_000, 4_000)
    tr.flow_end(PH_QUERY_QUEUE, fid)
    # A query still queued (any fleet's of the process) has no arrow yet.
    pending = tr.flow_start(PH_QUERY_QUEUE)
    doc = tr.chrome_trace()
    evs = doc["traceEvents"]
    lanes = [e for e in evs if e.get("pid") == LANE_PID and e["ph"] == "X"]
    assert {e["name"] for e in lanes} == {"q7", "q8"}
    assert {e["tid"] for e in lanes} == {0, 2}
    assert all(e["dur"] > 0 for e in lanes)
    meta = [
        e
        for e in evs
        if e.get("pid") == LANE_PID and e["ph"] == "M"
    ]
    names = {e["name"]: e["args"]["name"] for e in meta}
    assert names["process_name"] == "ktpu-lanes"
    thread_names = {
        e["args"]["name"] for e in meta if e["name"] == "thread_name"
    }
    assert thread_names == {"lane 0", "lane 2"}
    flows = [e for e in evs if e["ph"] in ("s", "f")]
    assert {e["id"] for e in flows if e["ph"] == "s"} == {
        e["id"] for e in flows if e["ph"] == "f"
    } == {fid}
    assert pending != fid
    rep = tr.report()
    assert rep["lane_spans"] == {"recorded": 2, "kept": 2}
    assert rep["spans"]["query_queue"]["count"] == 1
    assert rep["spans"]["query_service"]["count"] == 1
    # The written file passes the shared schema validator's span-name
    # rules (no C counter track here — a unit tracer has no device ring
    # extra_events — so only the span/flow/name assertions apply).
    path = tr.write_chrome_trace(str(tmp_path / "lanes.json"))
    with open(path) as fh:
        for ev in json.load(fh)["traceEvents"]:
            if ev["ph"] == "X" and ev["pid"] == LANE_PID:
                assert ev["name"].startswith("q")
    # The span rows carry the query id, in the Chrome events too.
    assert [e["args"]["id"] for e in evs if e.get("cat") == "host"] == [7, 7]
    # The process-wide recorder (there is no no-op stand-in any more)
    # takes the same surface: a lane event lands in its lane ring.
    was = recorder().report()["lane_spans"]["recorded"]
    recorder().lane_event(0, 0, 0, 0)
    assert recorder().report()["lane_spans"]["recorded"] == was + 1


def test_overhead_gate_smoke_scenario():
    """<3% wall-clock overhead, telemetry-on vs -off, on the smoke-scale
    scenario: both engines advance through the SAME sim regions in
    alternating timed spans (each pair hits identical windows), and the
    medians must stay inside the gate (small absolute slack absorbs
    container scheduling noise on sub-second spans). Engine configs match
    the module fixture's exactly, so the programs are jit-cache hits —
    the test times execution, not compilation."""
    on = _build_plain(telemetry=True, telemetry_ring=16)
    off = _build_plain()
    # Warm both: any residual compile + first slides out of the timed
    # region.
    on.step_until_time(120.0)
    off.step_until_time(120.0)
    pairs = []
    end = 120.0
    for _ in range(3):
        end += 100.0
        t0 = time.perf_counter()
        off.step_until_time(end)
        t_off = time.perf_counter() - t0
        t0 = time.perf_counter()
        on.step_until_time(end)
        t_on = time.perf_counter() - t0
        pairs.append((t_on, t_off))
    t_on_med = float(np.median([a for a, _ in pairs]))
    t_off_med = float(np.median([b for _, b in pairs]))
    assert t_on_med <= t_off_med * 1.03 + 0.10, (
        f"telemetry overhead gate: on={t_on_med:.3f}s off={t_off_med:.3f}s "
        f"(pairs={pairs})"
    )


def test_shared_render_path_covers_scalar_batched_and_telemetry(cheap_pair):
    """metrics/render.py is the ONE JSON/table path: the scalar printer's
    table, the batched summary and the telemetry report all render
    through it, and scalar/batched reports share the {"counters",
    "timings"} schema with identical timing keys."""
    from kubernetriks_tpu.metrics.collector import MetricsCollector
    from kubernetriks_tpu.metrics.printer import metrics_as_dict
    from kubernetriks_tpu.metrics.render import (
        render_metrics,
        render_telemetry,
    )

    on, _ = cheap_pair
    batched = on.metrics_summary()
    scalar = metrics_as_dict(MetricsCollector())

    assert set(scalar) == set(batched) == {"counters", "timings"}
    assert set(scalar["timings"]) == set(batched["timings"])
    for d in (scalar, batched):
        table = render_metrics(d, "table")
        assert "Metric" in table and "Pod queue time" in table and "|" in table
        parsed = json.loads(render_metrics(d, "json"))
        assert parsed["counters"] == json.loads(
            json.dumps(d["counters"], default=float)
        )
    rep_table = render_telemetry(on.telemetry_report(), "table")
    assert "window_chunk" in rep_table and "Ring windows kept" in rep_table
    json.loads(render_telemetry(on.telemetry_report(), "json"))


# --- the always-on process-wide recorder (PR 26) ---------------------------

# dispatch_stats of the two scenarios below as engines without the recorder
# (NULL_TRACER under telemetry=False) counted them: the recorder changes no
# dispatch and no sync. (Since PR 41 the superspan scenario's three numbers
# are 11 / 11 / 32 where they were 10 / 10 / 20: its 20 arrivals a cycle
# against max_pods_per_cycle 16 met the old per-cycle cap, and a cycle that
# drains starts those pods a cycle sooner, so the 64-slot window slides
# more often.)
PARENT_SUPERSPAN_STATS = {
    "window_chunks": 0, "fused_slides": 0, "slide_dispatches": 0,
    "slide_syncs": 11, "refill_prefetches": 0, "superspans": 11,
    "superspan_spans": 32, "stage_refills": 0, "feeder_slabs_produced": 0,
    "ladder_fallbacks": 0,
}
PARENT_FLEET_STATS = dict(
    PARENT_SUPERSPAN_STATS, window_chunks=6, slide_syncs=0, superspans=0,
    superspan_spans=0,
)
FLEET_HORIZONS = (120.0, 60.0, 90.0)


def _inside(child, parent):
    return parent[0] <= child[0] and child[0] + child[1] <= parent[0] + parent[1]


@pytest.fixture(scope="module")
def superspan_off_run(tmp_path_factory):
    """A superspan engine built with telemetry=False, stepped through
    ENDS with a jax.profiler capture round the last call."""
    import jax

    n0 = recorded()
    sim = _build_dense_sliding(superspan=True, superspan_k=4, superspan_chunk=4)
    for end in ENDS[:-1]:
        sim.step_until_time(end)
    trace_dir = str(tmp_path_factory.mktemp("xplane"))
    with jax.profiler.trace(trace_dir):
        sim.step_until_time(ENDS[-1])
    return sim, n0, trace_dir


def test_recorder_is_on_with_telemetry_off(superspan_off_run):
    """telemetry=False keeps the device ring out of the state pytree and
    the parent's dispatch counts, and still records the stream path's
    spans, each superspan and its progress wait under the ordinal of the
    dispatch, inside the step_until_time call that made them."""
    sim, n0, _ = superspan_off_run
    assert sim.state.telemetry is None and sim.observatory is None
    assert sim.dispatch_stats == PARENT_SUPERSPAN_STATS
    n = sim.dispatch_stats["superspans"]
    supers = rows_since(n0, "superspan")
    waits = rows_since(n0, "progress_wait")
    np.testing.assert_array_equal(supers[:, 3], np.arange(1, n + 1))
    np.testing.assert_array_equal(waits[:, 3], np.arange(1, n + 1))
    calls = rows_since(n0, "step_until_time")
    assert len(calls) == len(ENDS)
    for row in np.concatenate([supers, waits]):
        assert sum(_inside(row, call) for call in calls) == 1
    assert len(rows_since(n0, "engine_build")) == 1
    # The report is this engine's, the device sections are absent.
    rep = sim.telemetry_report()
    assert not rep["enabled"] and "ring" not in rep and "resources" not in rep
    assert rep["spans"]["superspan"]["count"] == n
    assert rep["spans"]["progress_wait"]["count"] == sim.dispatch_stats["slide_syncs"]


def test_profiler_capture_holds_program_spans(superspan_off_run):
    """A jax.profiler session started round an ordinary step_until_time
    (superspan engaged: no ladder fallback) holds the recorder's spans as
    `ktpu:<phase>` events on a host plane."""
    sim, _, trace_dir = superspan_off_run
    assert sim.dispatch_stats["ladder_fallbacks"] == 0
    names = host_annotations(trace_dir)
    assert {"ktpu:step_until_time", "ktpu:superspan", "ktpu:progress_wait"} <= names


def test_chrome_trace_needs_no_telemetry(superspan_off_run, tmp_path):
    """write_chrome_trace reads the shared recorder: a telemetry-off
    engine writes its host spans (no device-ring counter track)."""
    sim, _, _ = superspan_off_run
    with open(sim.write_chrome_trace(str(tmp_path / "off.json"))) as fh:
        events = json.load(fh)["traceEvents"]
    assert any(e["ph"] == "X" and e["name"] == "superspan" for e in events)
    assert not any(e["ph"] == "C" for e in events)


def _small_async_fleet(**kwargs):
    from kubernetriks_tpu.batched.fleet import ScenarioFleet

    config = default_test_simulation_config()
    cluster = UniformClusterTrace(8, cpu=64000, ram=128 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=1.0, horizon=200.0, seed=5, cpu=4000,
        ram=4 * 1024**3, duration_range=(20.0, 40.0),
    )
    return ScenarioFleet(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_lanes=2, horizon=120.0, max_pods_per_cycle=16, use_pallas=False,
        lane_async=True, span_windows=4, **kwargs,
    )


@pytest.fixture(scope="module")
def async_fleet_run():
    from kubernetriks_tpu.batched.fleet import Scenario

    n0 = recorded()
    fleet = _small_async_fleet()
    qids = [fleet.submit(Scenario(), h) for h in FLEET_HORIZONS]
    fleet.run_async()
    yield fleet, qids, rows_since(n0)
    fleet.close()


def test_fleet_rounds_are_recorded_with_their_round(async_fleet_run):
    """A lane-async fleet on a telemetry=False engine records pump >
    pump_admit / lane_dispatch / pump_drain > result_wait, every row under
    its round, with the parent's dispatch counts and no device ring."""
    fleet, _, rows = async_fleet_run
    assert fleet.engine.state.telemetry is None
    assert fleet.engine.dispatch_stats == PARENT_FLEET_STATS
    by = lambda name: rows[rows[:, 2] == PHASE_NAMES.index(name)]  # noqa: E731
    pumps = {int(r[3]): r for r in by("pump")}
    assert sorted(pumps) == list(range(fleet.pump_rounds)) == list(range(5))
    for child in ("pump_admit", "lane_dispatch", "pump_drain"):
        kids = by(child)
        assert len(kids) >= 2, child
        for row in kids:
            assert _inside(row, pumps[int(row[3])]), child
    assert len(by("lane_dispatch")) == fleet.engine.dispatch_stats["window_chunks"]
    drains = {int(r[3]): r for r in by("pump_drain")}
    waits = by("result_wait")
    assert len(waits) == len(drains)
    for row in waits:
        assert _inside(row, drains[int(row[3])])
    # A round's children cover it but for host arithmetic: self time is
    # what is left, never negative.
    assert (program_spans.self_ns(rows) >= 0).all()


def test_fleet_queries_are_recorded_with_their_qid(async_fleet_run):
    """query_queue (submit to admission) and query_service (admission to
    drain) carry the query id and add up to the fleet's own lifecycle."""
    fleet, qids, rows = async_fleet_run
    queue = {int(r[3]): r for r in rows[rows[:, 2] == PHASE_NAMES.index("query_queue")]}
    service = {int(r[3]): r for r in rows[rows[:, 2] == PHASE_NAMES.index("query_service")]}
    assert sorted(queue) == sorted(service) == sorted(qids)
    for qid in qids:
        assert queue[qid][0] + queue[qid][1] == service[qid][0]  # admitted
        assert queue[qid][1] >= 0 and service[qid][1] > 0


def test_recorder_moves_no_jit_cache(async_fleet_run):
    """The same stream again on the warm fleet compiles nothing: the
    recorder touches no traced value."""
    from kubernetriks_tpu.batched.fleet import Scenario, jit_cache_sizes

    fleet, _, _ = async_fleet_run
    first = {r.query: r.counters for r in fleet.poll()}
    sizes = jit_cache_sizes()
    again = [fleet.submit(Scenario(), h) for h in FLEET_HORIZONS]
    fleet.run_async()
    assert jit_cache_sizes() == sizes
    assert [r.counters for r in sorted(fleet.poll(), key=lambda r: r.query)] == [
        first[q] for q in sorted(first)
    ]
    assert len(again) == len(first)


def test_one_lane_occupancy_gauge():
    """The fleet's ledger and the observatory's lane_occupancy entry are
    one gauge: the entry reports the recorder's two counters, which the
    pump adds to where it adds to the ledger."""
    from kubernetriks_tpu.batched.fleet import Scenario

    fleet = _small_async_fleet(telemetry=True)
    assert "lane_occupancy" not in fleet.engine.observatory.occupancy()
    for h in FLEET_HORIZONS:
        fleet.submit(Scenario(), h)
    fleet.run_async()
    mine = fleet.lane_occupancy()
    theirs = fleet.engine.observatory.occupancy()["lane_occupancy"]
    assert theirs["lane_windows_busy"] == mine["lane_windows_busy"] == 30
    assert theirs["lane_windows_dispatched"] == mine["lane_windows_dispatched"] == 34
    assert theirs["share"] == round(mine["share"], 4) == round(30 / 34, 4)
    # Both zero together, and the recorder's counters stay time-resolved.
    fleet.reset_query_stats()
    assert fleet.lane_occupancy()["share"] == 1.0
    assert "lane_occupancy" not in fleet.engine.observatory.occupancy()
    samples = recorder().counter_samples("lane_windows_dispatched")
    assert (np.diff(samples[:, 0]) >= 0).all() and (np.diff(samples[:, 1]) > 0).all()
    fleet.close()


def test_self_time_of_a_hand_made_nest():
    """A span's self time is its duration less what its children cover;
    siblings and later spans do not touch it. (ONE implementation, beside
    its readers: benchmark/program_spans.py.)"""
    self_ns = program_spans.self_ns
    rows = np.array(
        [
            [0, 100, 0, 0],    # parent: children cover 20 + 30
            [10, 20, 1, 0],    # leaf
            [40, 30, 1, 0],    # child with a child of its own
            [45, 10, 2, 0],    # grandchild
            [200, 50, 0, 0],   # later, alone
            [100, 5, 3, 0],    # starts as the parent ends: a sibling
        ],
        np.int64,
    )
    np.testing.assert_array_equal(self_ns(rows), [50, 20, 20, 10, 50, 5])
    # Row order does not matter (the ring keeps rows by END time), and the
    # rows a recorder kept read the same.
    perm = np.array([3, 1, 2, 5, 0, 4])
    np.testing.assert_array_equal(self_ns(rows[perm]), np.array([50, 20, 20, 10, 50, 5])[perm])
    assert self_ns(rows[:0]).shape == (0,)
    tr = SpanTracer(capacity=16)
    for t0, dur, phase, ident in rows[perm].tolist():
        tr.end(phase, t0, dur=dur, ident=ident)
    by_phase = np.bincount(tr.rows()[:, 2], weights=self_ns(tr.rows()))
    np.testing.assert_array_equal(by_phase, [100, 40, 10, 5])
    assert tr.report()["spans"][PHASE_NAMES[0]]["total_ms"] == pytest.approx(150 / 1e6)


def test_report_is_the_engines_own_when_engines_interleave(cheap_pair):
    """Every engine writes to the one ring through a handle that keeps
    its own aggregates: two engines stepped turn by turn each report
    their own spans and time, and the two add up to what the shared
    recorder took meanwhile (tune/measure.py scores one engine per
    candidate in one process by this report's ms_per_window)."""
    was = recorder().report()["spans"]
    a = _build_plain(telemetry=True, telemetry_ring=16)
    b = _build_plain(telemetry=True, telemetry_ring=16)
    for end in ENDS:
        a.step_until_time(end)
        b.step_until_time(end)
    now = recorder().report()["spans"]
    reps = [a.telemetry_report(), b.telemetry_report()]
    for rep, sim in zip(reps, (a, b)):
        assert rep["spans"]["window_chunk"]["count"] == sim.dispatch_stats["window_chunks"]
        assert rep["spans"]["step_until_time"]["count"] == len(ENDS)
        assert rep["spans"]["engine_build"]["count"] == 1
        assert rep["per_window"]["windows"] == sim.next_window_idx
        assert rep["per_window"]["window_program_ms_total"] == pytest.approx(
            rep["spans"]["window_chunk"]["total_ms"]
        )
    for phase in ("window_chunk", "step_until_time", "engine_build"):
        grew = now[phase]["total_ms"] - was[phase]["total_ms"]
        assert sum(r["spans"][phase]["total_ms"] for r in reps) == pytest.approx(grew)
        assert now[phase]["count"] - was[phase]["count"] == sum(
            r["spans"][phase]["count"] for r in reps
        )
    # The engine that compiled the program (cheap_pair's) keeps that time.
    first = cheap_pair[0].telemetry_report()["per_window"]
    assert first["windows"] == reps[0]["per_window"]["windows"]
    assert first["ms_per_window"] > 0


def test_a_failed_build_leaves_no_span_open():
    """A constructor that raises still closes its `engine_build` span:
    no annotation stays open under the next engine's spans."""
    from kubernetriks_tpu.batched.fleet import ScenarioFleet

    rec = recorder()
    n0, depth = recorded(), len(rec._open_phase)
    with pytest.raises(ValueError, match="at least one lane"):
        ScenarioFleet(default_test_simulation_config(), [], [], n_lanes=0, horizon=10.0)
    assert len(rec._open_phase) == len(rec._open_ann) == depth
    assert len(rows_since(n0, "engine_build")) == 1


def test_compile_rows_carry_the_logs_seconds():
    """While a sentinel is installed, every 'Finished XLA compilation'
    line of jax's compile log becomes one `compile` row whose duration is
    the logged seconds and whose id finds the program's name."""
    import logging

    from kubernetriks_tpu.recompile import RecompileSentinel

    rec = recorder()
    n0, c0 = recorded(), rec.compiles_recorded
    log = logging.getLogger("jax._src.dispatch")
    line = "Finished XLA compilation of jit(_a_test_program) in 0.250000000 sec"
    log.warning(line)  # no sentinel installed: not recorded
    assert rec.compiles_recorded == c0
    with RecompileSentinel("warn") as sentinel:
        t_before = time.perf_counter_ns()
        log.warning(line)
        log.warning("Compiling jit(_a_test_program) with global shapes")  # another line
        assert sentinel.events == ["jit(_a_test_program)"]
    assert rec.compiles_recorded == c0 + 1
    (row,) = rows_since(n0, "compile")
    assert row[1] == 250_000_000 and row[3] == c0
    assert row[0] + row[1] >= t_before  # ends at the log line, starts 0.25 s before
    assert rec.compiles[-1] == ("jit(_a_test_program)", 0.25)


def test_counter_samples_give_a_delta_over_any_window():
    tr = SpanTracer(counter_capacity=8)
    tr.count("a", 3)
    t_mid = time.perf_counter_ns()
    tr.count("b")
    tr.count("a", 2)
    samples = tr.counter_samples("a")
    np.testing.assert_array_equal(samples[:, 1], [3, 5])
    assert samples[0, 0] <= t_mid <= samples[1, 0]
    assert tr.counter_samples("never").shape == (0, 2)
    for _ in range(10):
        tr.count("b")
    assert tr.dropped()["counter_samples"] == 5
    assert tr.counters == {"a": 5, "b": 11}


def test_device_ring_sharded_matches_unsharded_every_leaf():
    """The device ring on under a mesh: a ring row is per cluster, so it
    shards like every other leaf and the drained series, totals included,
    equals the unsharded run's; so does every other leaf of the state."""
    from kubernetriks_tpu.test_util import leaves_differing
    from tests.sharded_builds import bare_batch, mesh_of

    def run(**kwargs):
        sim = bare_batch(16, telemetry=True, fast_forward=False, **kwargs)
        sim.step_until_time(600.0)
        return sim

    unsharded, sharded = run(), run(mesh=mesh_of(8))
    assert leaves_differing(unsharded.state, sharded.state) == []
    assert sharded.state.telemetry is not None
    a, b = unsharded.telemetry_report()["ring"], sharded.telemetry_report()["ring"]
    assert a == b and a["windows_recorded"] > 0 and a["totals"]["decisions"] > 0


@pytest.mark.parametrize("chunk", [3, None])
def test_event_chunks_column_counts_the_event_loops_reads(chunk):
    """Ring on: a cluster's `event_chunks` in window W is ceil(due / E) for
    the events due in it (counted on the host from the trace), and the
    report's `event_chunks_per_window` the mean over windows of the
    clusters' maximum, which is the iterations the event loop ran (the slab
    reads the window paid). E = 3 makes most windows take several chunks;
    the default E takes at most one. Ring off: no leaf and no count (the
    loop's carry is the parent's), and the same final state."""
    import jax

    from kubernetriks_tpu.batched import step

    kwargs = {} if chunk is None else {"max_events_per_window": chunk}
    on = _build_plain(telemetry=True, **kwargs)
    off = _build_plain(**kwargs)
    for sim in (on, off):
        sim.step_until_time(ENDS[-1])
    E = on.max_events_per_window
    assert E == (chunk or E)
    wins, data = on.telemetry_window_series()
    got = data[:, :, RING_COLUMNS.index("event_chunks")]  # (windows, clusters)
    t = on._ev_time_np
    ev_win = np.where(np.isfinite(t), np.floor(t / on.config.scheduling_cycle_interval), -2)
    # consecutive stepping: window W applies exactly the events of window W - 1
    due = (ev_win[None, :, :] == (wins[:, None, None] - 1)).sum(axis=2)
    np.testing.assert_array_equal(got, -(-due // E))
    assert got.max() == (1 if chunk is None else -(-due.max() // chunk)) and got.max() >= 1
    per_window = on.telemetry_report()["ring"]["event_chunks_per_window"]
    assert per_window == pytest.approx(float((-(-due // E)).max(axis=1).mean()))
    assert compare_states(strip_telemetry(on.state), off.state) == []

    def chunks_of(sim):
        W = jax.numpy.ones((sim.n_clusters,), jax.numpy.int32)
        return jax.eval_shape(
            lambda state: step._apply_window_events(state, sim.slab, W, sim.consts, E)[2],
            sim.state,
        )

    assert chunks_of(off) is None
    assert chunks_of(on).shape == (on.n_clusters,)
