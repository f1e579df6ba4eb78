"""bench.py --smoke: the CPU-safe plumbing check for the tracked bench
lines (continuity shape, composed flagship, superspan machinery,
streaming feeder, endurance churn, north-star stand-in). Asserts every
line builds, RUNS its full machinery — the composed lines include real
window slides, HPA scale-ups and CA provisioning, the same in-bench
asserts the flagship line enforces on hardware; the superspan line
additionally asserts the SCANNED executor dispatched (so CI catches a
silent fallback to the ladder path), the streaming line asserts the
FEEDER ring staged the run (so CI catches a silent fallback to
whole-trace staging), and the endurance line asserts CA slot RECLAIM
fired with flat RSS/slab watermarks and zero recompiles (so CI catches
a reclaim regression before the slow endurance gate does) — and emits
parseable JSON with the headline fields. Composed lines time >= 5
repeated spans and carry the median + min/max spread. Values are not
performance numbers; tier-1 runs this under JAX_PLATFORMS=cpu (conftest
pins it)."""

import json
import os
import sys

import pytest


def _smoke_records(capsys, args):
    sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    import bench

    bench.main(args)
    lines = [
        ln for ln in capsys.readouterr().out.strip().splitlines() if ln.strip()
    ]
    records = [json.loads(ln) for ln in lines]
    for rec in records:
        if rec.get("unit") == "scenarios/s":
            # The scenario-fleet sweep line: its own unit and record
            # shape (what-if queries per second + the full sweep block).
            assert set(rec) == {"metric", "value", "unit", "sweep"}
            assert rec["value"] > 0
            continue
        if rec.get("unit") == "queries/s":
            # The open-loop lane-async line (DESIGN §13): queries per
            # second + the full open_loop block.
            assert set(rec) == {"metric", "value", "unit", "open_loop"}
            assert rec["value"] > 0
            continue
        if rec.get("unit") == "availability":
            # The host-chaos line (DESIGN §15): availability over the
            # injected phase + the full host_chaos block.
            assert set(rec) == {"metric", "value", "unit", "host_chaos"}
            assert 0.0 <= rec["value"] <= 1.0
            continue
        assert set(rec) - {"spans", "telemetry", "endurance"} == {
            "metric", "value", "unit", "vs_baseline",
        }
        assert rec["unit"] == "decisions/s"
        assert rec["value"] > 0
        # Smoke values are toy-shape numbers; the rounded-to-3-decimals
        # ratio can legitimately print as 0.0.
        assert rec["vs_baseline"] >= 0
    return records


def test_bench_smoke_emits_nine_parseable_lines(capsys, tmp_path, monkeypatch):
    # --trace rides along (the CI smoke job runs it this way): the
    # composed lines must carry the flight-recorder summary AND write a
    # Perfetto-loadable Chrome trace per traced line.
    monkeypatch.setenv("KTPU_TRACE_PATH", str(tmp_path / "ktpu_trace"))
    monkeypatch.setenv("KTPU_METRICS_PATH", str(tmp_path / "ktpu_metrics"))
    monkeypatch.setenv("KTPU_SWEEP_PATH", str(tmp_path / "ktpu_sweep"))
    records = _smoke_records(capsys, ["--smoke", "--trace"])
    assert len(records) == 9, records
    # Line order is part of the contract: continuity, composed, superspan
    # machinery, streaming feeder, endurance churn, compiled profile,
    # north-star, open-loop lane-async fleet, scenario
    # fleet (the sweep runs LAST: its cold-process baseline clears the
    # jit caches, which would cold-start anything after it).
    assert "composed" in records[1]["metric"]
    assert "superspan" in records[2]["metric"]
    assert "streaming" in records[3]["metric"]
    assert "endurance churn" in records[4]["metric"]
    # The compiled-profile line ran under the second (best_fit) scheduler
    # profile — its in-bench asserts fail loudly when the engine silently
    # falls back to the default pipeline, so its presence IS the gate.
    assert "best_fit profile" in records[5]["metric"]
    assert "north-star" in records[6]["metric"]
    assert "open-loop lane-async fleet" in records[7]["metric"]
    assert "scenario-vector fleet" in records[8]["metric"]
    # The ENDURANCE line (r14): run_endurance's in-bench gates (reclaim
    # actually fired, flat RSS/slab watermarks, zero recompiles after
    # warm-up, no reserve saturation verdict) already ran — the record's
    # endurance block discloses what was checked; pin the disclosure so a
    # gate that silently stops running fails here.
    endur = records[4]["endurance"]
    assert endur["allocations"] >= 3 * endur["reserve_slots"]
    assert endur["reclaimed"] >= endur["allocations"] - endur["reserve_slots"]
    assert endur["recompiles_after_warmup"] == 0
    # Reserve verdicts are the hard gate inside run_endurance; pipeline
    # verdicts (feeder stalls at toy shapes) are disclosed, not asserted.
    assert not any(
        k.endswith("_reserve_used") for k in endur["watchdog_fired"]
    )
    assert endur["rss_end_mb"] <= endur["rss_after_warm_mb"] * 1.5 + 256
    assert records[4]["spans"]["n"] >= 4
    assert records[4]["spans"]["min"] > 0
    # The scenario-fleet line: its in-bench asserts (zero recompiles
    # after warm-up, no lane cross-talk on the duplicate-scenario probes)
    # already ran inside run_sweep — the record's sweep block discloses
    # what was checked, and the JSON artifact landed for CI upload.
    sweep = records[8]["sweep"]
    assert sweep["scenarios"] == 8 and sweep["lanes"] == 4
    assert sweep["waves"] == 2
    assert sweep["recompiles_after_warmup"] == 0
    assert sweep["crosstalk_probes"]
    assert sweep["decisions_total"] > 0
    # Smoke keeps the jit caches warm (no cold-process baseline; the
    # speedup gate only arms on the full --sweep) and discloses it.
    assert sweep["baseline"]["cold_process_model"] is False
    sweep_doc = json.loads((tmp_path / "ktpu_sweep.json").read_text())
    assert sweep_doc == sweep
    # The OPEN-LOOP line (DESIGN §13): run_open_loop's in-bench asserts
    # (A/B bit-identity on every query between the wave-aligned and
    # lane-async fleets, zero recompiles across post-warm-up pump
    # rounds) already ran; pin the disclosure + the JSON artifact CI
    # uploads. The occupancy/speedup hard gates arm on the full --sweep
    # only — smoke pins the machinery, not toy-shape performance.
    ol = records[7]["open_loop"]
    assert ol["queries"] == 8 and ol["lanes"] == 4
    assert ol["ab_identity_checked"] == 8
    assert ol["recompiles_after_warmup"] == 0
    assert ol["recompile_sentinel"]["post_warmup_events"] == 0
    assert ol["async_queries_per_s"] > 0 and ol["wave_queries_per_s"] > 0
    assert 0 < ol["lane_occupancy"]["min"] <= ol["lane_occupancy"]["mean"] <= 1
    assert ol["latency_ms"]["p50_ms"] > 0
    ol_doc = json.loads((tmp_path / "ktpu_sweep_openloop.json").read_text())
    assert ol_doc == ol
    # Composed lines report the >= 5-span median with min/max spread; the
    # plain-shape lines keep the bare single-region value.
    for rec in records[1:4]:
        spans = rec["spans"]
        assert spans["n"] >= 5
        assert spans["min"] <= rec["value"] <= spans["max"]
        # r7 span-validity protocol: zero-decision (trace-exhausted) spans
        # are dropped and DISCLOSED, and every span that made the median
        # committed decisions — spans.min == 0 can no longer happen.
        assert spans["dropped"] >= 0
        assert spans["min"] > 0
    for rec in (records[0], records[5], records[6]):
        assert "spans" not in rec
    # Telemetry summary embedded in (exactly) the traced composed lines:
    # per-phase wall time, the observed-vs-expected sync budget, dispatch
    # stats with the ladder_fallbacks observable, device-ring totals.
    # The endurance line (records[4]) writes its trace/metrics artifacts
    # but keeps the flight-recorder summary out of the record — its
    # disclosure is the endurance block.
    for rec in (records[0], records[4], records[5], records[6]):
        assert "telemetry" not in rec
    for rec in records[1:4]:
        tel = rec["telemetry"]
        assert tel["spans_ms"]
        assert tel["sync_budget"]["observed_slide_syncs"] >= 0
        assert "ladder_fallbacks" in tel["dispatch_stats"]
        assert tel["ring_totals"]["decisions"] > 0
        # Per-window window-program cost (the lane-major / window-razor /
        # CA-de-scatter observable): present and positive on every traced
        # composed line, so layout regressions surface on CPU CI.
        pw = tel["per_window"]
        assert pw["windows"] > 0
        assert pw["ms_per_window"] > 0
    # The superspan line's trace shows the scanned executor: superspan
    # dispatches present, zero ladder chunks, sync budget exactly met.
    tel = records[2]["telemetry"]
    assert tel["dispatch_stats"]["superspans"] > 0
    assert tel["dispatch_stats"]["window_chunks"] == 0
    assert (
        tel["sync_budget"]["observed_slide_syncs"]
        == tel["sync_budget"]["steady_state_expected"]
    )
    # The streaming line's trace shows the feeder pipeline: slabs
    # produced AND installed, the whole-trace payload never materialized
    # (dispatch stats make a starved feeder observable: production vs
    # installs plus the stall split in the feeder section), sync budget
    # still exactly one progress readback per superspan.
    tel = records[3]["telemetry"]
    assert tel["dispatch_stats"]["superspans"] > 0
    assert tel["dispatch_stats"]["feeder_slabs_produced"] > 0
    assert tel["dispatch_stats"]["stage_refills"] > 0
    assert (
        tel["sync_budget"]["observed_slide_syncs"]
        == tel["sync_budget"]["steady_state_expected"]
    )
    feeder = tel["feeder"]
    # dispatch_stats is cumulative across feeder re-seeks (window growth);
    # the feeder section describes the LAST feeder generation.
    assert feeder["slabs_produced"] <= tel["dispatch_stats"]["feeder_slabs_produced"]
    assert feeder["ring_depth_high_water"] <= feeder["ring_capacity"]
    assert set(feeder["stalls"]) == {"feeder_not_ready", "upload_wait"}
    # Capacity-observatory resources section on every traced composed
    # line (the capacity half of the flight recorder): occupancy gauges
    # with reserve-capacity fractions plus RSS/slab watermarks — present
    # and sane, so a change that stops the observatory sampling fails on
    # CPU CI.
    for rec in records[1:4]:
        res = rec["telemetry"]["resources"]
        assert res["rss_mb"] > 0
        assert res["rss_high_water_mb"] >= res["rss_mb"] * 0.5
        occ = res["occupancy"]
        assert {"hpa_reserve_used", "ca_reserve_used", "pod_headroom"} <= set(occ)
        ca = occ["ca_reserve_used"]
        assert ca["capacity_min"] > 0
        assert 0 <= ca["used_max"] <= ca["high_water"] <= ca["capacity_min"]
        assert res["slabs"]["telemetry_ring_bytes"] > 0
        assert "watchdog_fired" in res
    # The streaming line's slab accounting shows the bounded feeder ring
    # and NO whole-trace device payload (the memory bound, in bytes).
    res = records[3]["telemetry"]["resources"]
    assert res["slabs"]["device_slide_bytes"] == 0
    assert res["slabs"].get("feeder_ring_capacity_bytes", 0) > 0
    for label in (
        "smoke_composed", "smoke_superspan", "smoke_stream", "smoke_endurance",
    ):
        path = tmp_path / f"ktpu_trace_{label}.json"
        assert path.exists(), f"missing Chrome trace {path}"
        doc = json.loads(path.read_text())
        assert doc["traceEvents"], "empty Chrome trace"
        # The observatory's time-series export landed next to the trace:
        # parseable JSONL drain records + the Prometheus textfile.
        jsonl = tmp_path / f"ktpu_metrics_{label}.jsonl"
        assert jsonl.exists(), f"missing metrics JSONL {jsonl}"
        lines = [json.loads(ln) for ln in jsonl.read_text().splitlines()]
        assert lines and all("occupancy" in ln for ln in lines)
        assert lines[-1]["resources"]["rss_bytes"] > 0
        prom = tmp_path / f"ktpu_metrics_{label}.prom"
        assert prom.exists(), f"missing Prometheus textfile {prom}"
        prom_text = prom.read_text()
        assert "ktpu_occupancy{" in prom_text
        assert "ktpu_memory_bytes{" in prom_text


@pytest.mark.slow
def test_bench_smoke_faults_adds_chaos_line(capsys, tmp_path, monkeypatch):
    """--faults inserts a fault-enabled composed smoke line (the chaos
    engine's dispatch/throughput tracker) before the final sweep line.
    --trace rides along so the traced composed lines are jit-cache hits
    from the previous test (same programs); the chaos line itself is
    untraced either way. Slow lane (tier-1 wall-clock budget): the
    nine-line test covers every line contract including the sweep; this
    variant only adds the chaos line's presence on top of chaos-path
    coverage tier-1 already carries (test_superspan / test_streaming /
    test_soak fault engines, test_chaos)."""
    monkeypatch.setenv("KTPU_TRACE_PATH", str(tmp_path / "ktpu_trace"))
    monkeypatch.setenv("KTPU_METRICS_PATH", str(tmp_path / "ktpu_metrics"))
    monkeypatch.setenv("KTPU_SWEEP_PATH", str(tmp_path / "ktpu_sweep"))
    records = _smoke_records(capsys, ["--smoke", "--faults", "--trace"])
    assert len(records) == 10, records
    assert "chaos" in records[7]["metric"]
    assert records[7]["value"] > 0
    assert records[7]["spans"]["n"] >= 5
    assert "telemetry" not in records[7]
    assert "open-loop lane-async fleet" in records[8]["metric"]
    assert "scenario-vector fleet" in records[9]["metric"]


@pytest.mark.slow
def test_bench_smoke_host_chaos_adds_availability_line(
    capsys, tmp_path, monkeypatch
):
    """--host-chaos inserts the fault-tolerant-serving line (DESIGN §15)
    AFTER the open-loop line (shared warm jit caches) and BEFORE the
    sweep (which must stay LAST: its baseline clears the jit caches).
    run_host_chaos's in-bench gates already ran — quiet-layer A/B
    bit-identity + dispatch_stats equality, stream-once typed-error
    delivery, availability >= 90% under the pinned-seed injector, every
    lane faulted, quarantine fired AND re-admitted, zero post-warm-up
    recompiles; pin the disclosure + the JSON artifact CI uploads. Slow
    lane: the nine-line test covers the default contract (no flag = no
    line); fault-path unit coverage lives in test_fleet_faults.py."""
    monkeypatch.setenv("KTPU_SWEEP_PATH", str(tmp_path / "ktpu_sweep"))
    records = _smoke_records(capsys, ["--smoke", "--host-chaos"])
    assert len(records) == 10, records
    assert "open-loop lane-async fleet" in records[7]["metric"]
    assert "host-chaos" in records[8]["metric"]
    assert "scenario-vector fleet" in records[9]["metric"]
    hc = records[8]["host_chaos"]
    assert hc["availability"] >= 0.90
    assert hc["lanes"] == 4 and hc["victim_lanes"] == [0, 1, 2, 3]
    assert hc["quarantine_events"] >= 1 and hc["readmissions"] >= 1
    assert sum(hc["failed_by_kind"].values()) == hc["failed"]
    assert hc["stream_once_audited"] == hc["submitted"]
    assert hc["quiet_ab_identity_checked"] > 0
    assert hc["quiet_dispatch_stats_equal"] is True
    assert hc["recompiles_after_warmup"] == 0
    assert hc["recompile_sentinel"]["post_warmup_events"] == 0
    hc_doc = json.loads(
        (tmp_path / "ktpu_sweep_hostchaos.json").read_text()
    )
    assert hc_doc == hc
