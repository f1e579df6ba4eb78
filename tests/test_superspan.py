"""Device-resident superspan executor (step.run_superspan).

The superspan path runs up to K consecutive slide-spans entirely on device —
window chunks, shift computation, quantization and slide application inside
ONE while_loop, refill columns drawn from a device-resident staging slab —
and must be BIT-IDENTICAL to the ladder path it replaces:

1. Composed flagship run (HPA + CA + sliding pod window), superspan ON vs
   the unfused two-dispatch-slide ladder: every state leaf exact, metrics
   exact, same slide trajectory — fault-free AND with fault_injection
   enabled (the commit-time threefry draws are slot-keyed and
   slide-invariant, so the on-device slides must not perturb them). The
   fault variant runs the non-default "best_fit" compiled scheduler
   profile on ladder+fused AND superspan executors — the chaos-on
   profile bit-identity gate (batched/pipeline.py).
2. The bounded RefillStage path (whole-trace payload over budget): staging
   installs, the double-buffered successor, and the SUPERSPAN_STAGE
   mid-flight exhaustion exit all preserve bit-identity.
3. The SUPERSPAN_GROW exit: a dense stretch with no terminal leading pod
   must grow the window in place, matching the full-resident run.
4. precompile_chunks warms the ONE superspan program instead of the ladder.
"""

import numpy as np
import pytest

import kubernetriks_tpu.batched.engine as engine_mod
from kubernetriks_tpu.batched.state import compare_states, strip_telemetry
from kubernetriks_tpu.test_util import default_test_simulation_config

from test_pod_window_growth import _build as _build_growth
from test_pod_window_growth import _long_running_workload
from test_window_donation_dispatch import _build_composed

FAULT_SUFFIX = """
fault_injection:
  enabled: true
  node:
    mttf: 2500.0
    mttr: 120.0
  pod:
    fail_prob: 0.12
    backoff_base: 10.0
    backoff_cap: 300.0
    restart_limit: 3
"""


def _run(sim, ends=(150.0, 300.0, 450.0)):
    for end in ends:
        sim.step_until_time(end)
    return sim


def _assert_superspan_matches_ladder(ss, ladder):
    # The superspan path really ran (and never silently fell back to the
    # ladder), and the run exercised slides — otherwise parity is vacuous.
    assert ss.dispatch_stats["superspans"] > 0
    assert ss.dispatch_stats["window_chunks"] == 0
    assert ss._pod_base > 0
    assert ladder.dispatch_stats["superspans"] == 0

    assert ss._pod_base == ladder._pod_base
    assert ss.next_window_idx == ladder.next_window_idx
    # strip_telemetry: a flight-recorder-armed ss engine (the fault test
    # below) carries the device ring, the ONE leaf allowed to differ.
    assert compare_states(strip_telemetry(ss.state), ladder.state) == []
    assert ss.metrics_summary() == ladder.metrics_summary()
    if ss.autoscale_statics is not None:
        # The carried windowed name ranks land back in the statics.
        np.testing.assert_array_equal(
            np.asarray(ss.autoscale_statics.pod_name_rank),
            np.asarray(ladder.autoscale_statics.pod_name_rank),
        )


@pytest.mark.slow
def test_superspan_composed_bit_identical():
    """Flagship composition: superspan ON (donated, whole-trace payload) ==
    the plain two-dispatch-slide ladder, bit for bit. Slow lane (tier-1
    wall-clock budget): the chaos-on variant below is the superset gate —
    same superspan-vs-ladder bit-identity assert over the same composed
    scenario with MORE channels live (fault slab events, commit-time
    draws, telemetry ring, non-default profile) — so tier-1 keeps that
    one; this fault-free isolate remains for diagnosis when the superset
    gate trips."""
    ss = _run(
        _build_composed(superspan=True, superspan_k=4, superspan_chunk=4)
    )
    assert ss._superspan_ok()
    ladder = _run(_build_composed(donate=False, fuse_slide=False))
    _assert_superspan_matches_ladder(ss, ladder)
    # Steady-state sync economy: one progress readback per superspan
    # dispatch, nothing else.
    assert ss.dispatch_stats["slide_syncs"] == ss.dispatch_stats["superspans"]


def test_superspan_composed_bit_identical_under_faults(tmp_path):
    """Same flagship parity with the chaos engine on: node crash chains ride
    the slab, pod-attempt threefry draws happen at commit inside the scanned
    windows — the on-device slides must leave every draw slot-keyed exactly
    as the ladder path sees it.

    BOTH engines run the non-default "best_fit" compiled scheduler profile
    (batched/pipeline.py): this is the chaos-on bit-identity gate for a
    non-default profile ACROSS EXECUTORS — the subject is the superspan
    executor, and the comparator dispatches plain ladder chunks PLUS the
    fused chunk+slide megastep (fuse_slide=True: the fused program is the
    last ladder chunk of every slide span), so ladder, fused and superspan
    all execute the same compiled profile and must agree bit for bit.
    Riding the existing fault engines keeps this at zero extra engines
    (the profile variant replaces the programs this test compiled anyway,
    the PR-8 telemetry pattern).

    The ss engine ALSO runs with the flight recorder armed (PR 8): the
    parity compare against the telemetry-OFF comparator is then the
    composed HPA+CA+superspan+chaos telemetry bit-identity gate. The
    composed-scale ring/report/budget gates ride here too;
    tests/test_telemetry.py covers the mechanics on cheap engines."""
    ss = _run(
        _build_composed(
            config_suffix=FAULT_SUFFIX,
            superspan=True,
            superspan_k=4,
            superspan_chunk=4,
            telemetry=True,
            telemetry_ring=32,  # < executed windows: drains + wrap exercised
            scheduler_profile="best_fit",
        )
    )
    assert ss.fault_params is not None
    assert ss.profile.name == "best_fit"
    ladder = _run(
        _build_composed(
            config_suffix=FAULT_SUFFIX,
            donate=False,
            fuse_slide=True,
            scheduler_profile="best_fit",
        )
    )
    # The comparator really exercised BOTH non-superspan executors: plain
    # ladder chunks and the fused chunk+slide megastep.
    assert ladder.dispatch_stats["window_chunks"] > 0
    assert ladder.dispatch_stats["fused_slides"] > 0
    counters = ss.metrics_summary()["counters"]
    assert counters["pod_interruptions"] + counters["pods_failed"] > 0, (
        "fault run produced no faults; parity under faults is vacuous"
    )
    _assert_superspan_matches_ladder(ss, ladder)
    # Threading the profile static added no host syncs: the superspan
    # engine's dispatch accounting still meets the steady-state budget
    # (asserted == below) and the comparator's chunk accounting is the
    # fused-ladder shape, exactly as under the default profile.
    assert ss.dispatch_stats["ladder_fallbacks"] == 0

    # --- composed-scale flight-recorder gates (PR 8) ---------------------
    from kubernetriks_tpu.telemetry.ring import RING_COLUMNS

    # No new syncs: the steady-state budget (1 progress readback per
    # superspan, zero ladder chunks) is untouched by telemetry.
    assert ss.dispatch_stats["slide_syncs"] == ss.dispatch_stats["superspans"]
    assert ss.dispatch_stats["ladder_fallbacks"] == 0
    # Ring lossless despite wrapping (capacity 32 < executed windows):
    # every executed window has exactly one record, and the per-window
    # decision deltas sum to the run's total decision counter.
    executed = ss.next_window_idx
    assert executed > 32
    wins, data = ss.telemetry_window_series()
    np.testing.assert_array_equal(wins, np.arange(executed, dtype=np.int32))
    assert (
        int(data[:, :, RING_COLUMNS.index("decisions")].sum())
        == counters["scheduling_decisions"]
    )
    # The composed scenario's activity is visible in the ring columns.
    for col in ("hpa_pod_actions", "ca_node_actions", "fault_events"):
        assert int(data[:, :, RING_COLUMNS.index(col)].sum()) > 0, col
    rep = ss.telemetry_report()
    assert rep["spans"]["superspan"]["count"] == ss.dispatch_stats["superspans"]
    assert rep["spans"]["progress_wait"]["count"] == ss.dispatch_stats["slide_syncs"]
    assert (
        rep["sync_budget"]["observed_slide_syncs"]
        == rep["sync_budget"]["steady_state_expected"]
    )
    assert rep["ring"]["windows_kept"] == executed
    # The emitted Chrome trace carries the async progress readbacks as
    # matched flow pairs (the overlap arrows a Perfetto view shows).
    from test_telemetry import validate_chrome_trace

    path = ss.write_chrome_trace(str(tmp_path / "trace.json"))
    validate_chrome_trace(path, expect_flows=True)


@pytest.mark.slow
def test_superspan_bounded_stage_and_exhaustion_exit(monkeypatch):
    """Over-budget traces stage refill columns through bounded RefillStage
    slabs. A minimal-width stage (W + W/2) exhausts after a single max
    slide, forcing SUPERSPAN_STAGE exits and restages mid-run — the end
    state must still match the ladder, and the engine must never spin on an
    exhausted buffer (the regression this test pins: _stage_covers accepts
    a stage with zero slide headroom left). Slow lane (tier-1 wall-clock
    budget): restage-under-exhaustion coverage stays tier-1 through
    test_superspan_capacity_edge_restages_instead_of_growing (the exact
    zero-headroom edge) and test_streaming's run-ahead-restage / K=1-ring
    / demand-mode gates over the same stage machinery; this ladder-parity
    variant remains for diagnosis when those trip."""
    monkeypatch.setattr(engine_mod, "_DEVICE_SLIDE_BUDGET_BYTES", 0)
    ss = _build_composed(
        superspan=True,
        superspan_k=8,
        superspan_chunk=4,
        superspan_stage_cols=96,  # W=64: minimum width, 32 columns headroom
        fuse_slide=False,
    )
    assert ss._device_slide is None, "budget monkeypatch did not take"
    _run(ss)
    monkeypatch.setattr(engine_mod, "_DEVICE_SLIDE_BUDGET_BYTES", 2 << 30)
    ladder = _run(_build_composed(donate=False, fuse_slide=False))
    _assert_superspan_matches_ladder(ss, ladder)
    # The initial install plus at least one mid-run restage happened.
    assert ss.dispatch_stats["stage_refills"] >= 2


def test_superspan_grow_exit_matches_resident():
    """SUPERSPAN_GROW: long-running pods leave no terminal leading slot, so
    the scanned loop reports shift == 0 and the engine grows the window in
    place — same counters and terminal phases as the full-resident run."""
    workload = _long_running_workload(n_pods=120, duration=600.0)
    ss = _build_growth(
        workload,
        pod_window=64,
        superspan=True,
        superspan_k=4,
        superspan_chunk=4,
        fast_forward=False,
    )
    ss.step_until_time(1200.0)
    assert ss.pod_window == 120, "window never grew"
    assert ss.dispatch_stats["superspans"] > 0
    ref = _build_growth(workload, fast_forward=False)
    ref.step_until_time(1200.0)
    assert (
        ss.metrics_summary()["counters"] == ref.metrics_summary()["counters"]
    )
    P_real = np.asarray(ss.state.pods.phase).shape[1]
    np.testing.assert_array_equal(
        np.asarray(ref.state.pods.phase)[:, :P_real],
        np.asarray(ss.state.pods.phase),
    )


@pytest.mark.slow
def test_precompile_warms_superspan_program():
    """A superspan engine warms exactly ONE program shape (the scanned loop
    serves every span/target); the warm dispatch must not perturb state or
    host mirrors. Slow lane (tier-1 wall-clock budget): warm-up plumbing,
    not simulation semantics — a precompile regression that let the
    superspan fall back to the ladder fails tier-1 loudly anyway via
    test_bench_smoke's superspan line (in-bench scanned-executor assert)
    and the dispatch-count gate in test_window_donation_dispatch."""
    ss = _build_composed(superspan=True, superspan_k=4, superspan_chunk=4)
    before = (ss.next_window_idx, ss._pod_base)
    snap = {
        k: np.asarray(v).copy()
        for k, v in (("phase", ss.state.pods.phase), ("time", ss.state.time))
    }
    assert ss.precompile_chunks() == 1
    assert (ss.next_window_idx, ss._pod_base) == before
    np.testing.assert_array_equal(np.asarray(ss.state.pods.phase), snap["phase"])
    np.testing.assert_array_equal(np.asarray(ss.state.time), snap["time"])
    # And the warmed program is the one the loop then uses: no ladder chunks.
    _run(ss)
    assert ss.dispatch_stats["window_chunks"] == 0
    assert ss.dispatch_stats["superspans"] > 0


def _exact_exhaustion_workload(W=64):
    """Engineered for the capacity-unreadable staging edge: pods 0..W/2-1
    terminate before the first slide, pods W/2..(3W/2)-1 run long enough to
    be live across it, and the final W/2 pods create after a long gap. The
    first slide is then EXACTLY the max quantum W/2 — landing a minimal
    (W + W/2)-wide stage's capacity column exactly at its edge with a live
    front pod and the true capacity far away. A blocked slide there must
    exit SUPERSPAN_STAGE (restage, re-read the real capacity), never
    SUPERSPAN_GROW: the ladder path never grows on this trace."""
    half = W // 2
    pods = [(1.0 + i, i, 20.0 if i < half else 100.0) for i in range(W + half)]
    pods += [(2001.0 + j, W + half + j, 20.0) for j in range(half)]
    from kubernetriks_tpu.trace.generic import GenericWorkloadTrace

    return GenericWorkloadTrace.from_yaml(
        "events:"
        + "".join(
            f"""
- timestamp: {ts}
  event_type:
    !CreatePod
      pod:
        metadata:
          name: pod_{i:04d}
        spec:
          resources:
            requests: {{cpu: 10, ram: 10485760}}
            limits: {{cpu: 10, ram: 10485760}}
          running_duration: {dur}
"""
            for ts, i, dur in pods
        )
    ).convert_to_simulator_events()


def test_superspan_capacity_edge_restages_instead_of_growing(monkeypatch):
    """Regression: a blocked slide whose capacity column lies beyond the
    stage (col == L after a max slide consumed all headroom) must exit
    SUPERSPAN_STAGE, not SUPERSPAN_GROW — growing there diverges from the
    ladder (which reads the TRUE capacity and just keeps running)."""
    W = 64
    workload = _exact_exhaustion_workload(W)
    monkeypatch.setattr(engine_mod, "_DEVICE_SLIDE_BUDGET_BYTES", 0)
    ss = _build_growth(
        workload,
        pod_window=W,
        superspan=True,
        superspan_k=8,
        superspan_chunk=4,
        superspan_stage_cols=W + W // 2,  # minimum width: zero slack
        fast_forward=False,
    )
    assert ss._device_slide is None, "budget monkeypatch did not take"
    ss.step_until_time(2200.0)
    # The edge fired (initial install + at least one mid-run restage) and
    # was answered with a restage, not a spurious growth.
    assert ss.dispatch_stats["stage_refills"] >= 2
    assert ss.pod_window == W, "capacity-unreadable slide grew the window"
    monkeypatch.setattr(engine_mod, "_DEVICE_SLIDE_BUDGET_BYTES", 2 << 30)
    ladder = _build_growth(
        workload, pod_window=W, fast_forward=False, fuse_slide=False
    )
    ladder.step_until_time(2200.0)
    assert ladder.pod_window == W
    _assert_superspan_matches_ladder(ss, ladder)


def test_superspan_sliding_stream_sharded_matches_unsharded_every_leaf():
    """A sliding stream through the superspan executor on a mesh of 4, over
    clusters whose pod windows would slide by different amounts: pod_base,
    the capacity read and the slide's shift are pmin'ned over the shards, so
    the loop's trip count and every exit code are the same on each, and the
    final state (pod faults on, HPA + CA acting) equals the unsharded run's
    in every leaf, with the same slide trajectory."""
    from kubernetriks_tpu.test_util import leaves_differing
    from tests.sharded_builds import POD_FAULTS, autoscaled_batch, mesh_of

    def run(**kwargs):
        sim = autoscaled_batch(8, POD_FAULTS, pod_window=64, superspan=True, **kwargs)
        return _run(sim, ends=(500.0, 1000.0, 1500.0))

    unsharded, sharded = run(), run(mesh=mesh_of(4))
    assert sharded.dispatch_stats["superspans"] > 0
    assert sharded.dispatch_stats["window_chunks"] == 0
    assert sharded._pod_base == unsharded._pod_base > 0
    assert sharded.dispatch_stats == unsharded.dispatch_stats
    counters = sharded.metrics_summary()["counters"]
    for key in ("total_scaled_up_pods", "total_scaled_up_nodes", "pod_restarts"):
        assert counters[key] > 0, (key, counters)
    assert leaves_differing(unsharded.state, sharded.state) == []
    np.testing.assert_array_equal(
        np.asarray(sharded.autoscale_statics.pod_name_rank),
        np.asarray(unsharded.autoscale_statics.pod_name_rank),
    )
