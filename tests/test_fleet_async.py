"""Lane-asynchronous fleet gates (batched/fleet.py submit/pump/poll +
the per-lane window clocks of DESIGN §13).

1. A/B IDENTITY: the same heterogeneous-horizon query stream through a
   wave-aligned fleet and a lane-async fleet returns bit-identical
   per-query results — with chaos ON and more queries than lanes, so
   lanes finish early and re-seed mid-flight while neighbours keep
   stepping.
2. LANE PERMUTATION: submitting the same multiset in a different order
   lands queries on different lanes at different global windows — the
   per-query results still bit-match (a lane's trajectory is a pure
   function of its scenario + horizon, never its lane index or clock
   offset; per-lane fault seeds keep that true under chaos).
3. SCALAR ORACLES: each heterogeneous-horizon query's HPA replica count
   equals an independent scalar-oracle run of that scenario stepped to
   that query's OWN horizon (the test_fleet oracle protocol, made
   horizon-heterogeneous).
4. CONTINUOUS ENGINE MECHANICS: poll() streams completions exactly once;
   re-running a stream is recompile-free (cache counts + armed
   sentinel); the occupancy ledger and latency percentiles account every
   query; the trace mux masks per-lane row spans and never re-offers a
   flying lane.
5. QUERY OBSERVATORY (DESIGN §14): poll() of a never-submitted qid is a
   loud KeyError carrying the known-qid inventory; reset_query_stats()
   zeroes the bounded latency histograms without discarding results
   (poll-after-reset still streams each completion exactly once); every
   polled query's lifecycle stages are host-clock monotone.
"""

import os

import numpy as np
import pytest

from kubernetriks_tpu.batched.fleet import (
    Scenario,
    ScenarioFleet,
    jit_cache_sizes,
)
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generic import (
    GenericClusterTrace,
    GenericWorkloadTrace,
)

from test_fleet import FAULT_SUFFIX, _apply_scenario_to_config, _composed_traces
from test_random_hpa_equivalence import (
    CLUSTER_TRACE as HPA_CLUSTER_TRACE,
    make_workload as make_hpa_workload,
)
from test_window_donation_dispatch import COMPOSED_CONFIG_SUFFIX

# Scenario 0 == scenario 3 (in-stream duplicate at a different horizon
# slot); five queries over three lanes force a mid-flight reseed; the
# 150 s horizon finishes its lane ~3x earlier than its neighbours.
SCENS = [
    (Scenario(fault_seed=11, hpa_scan_interval=30.0), 450.0),
    (Scenario(fault_seed=22, ca_threshold=0.7), 250.0),
    (Scenario(fault_seed=33, hpa_tolerance=0.25), 350.0),
    (Scenario(fault_seed=11, hpa_scan_interval=30.0), 450.0),  # dup of 0
    (Scenario(fault_seed=44), 150.0),
]


def _build(lane_async, config, cluster_events, workload):
    return ScenarioFleet(
        config,
        cluster_events,
        workload,
        n_lanes=3,
        horizon=450.0,
        max_pods_per_cycle=16,
        use_pallas=False,
        ca_slot_multiplier=4,
        lane_async=lane_async,
    )


@pytest.fixture(scope="module")
def async_ab_runs():
    """One wave-aligned and two lane-async fleets (the second fed the
    permuted stream) over the composed+chaos scenario — the shared
    engines every gate below reads. KTPU_EXPLAIN_RECOMPILES=1 arms the
    recompile sentinel on the two lane-async fleets, so every
    post-warm-up pump round already runs under an expect_none guard.
    The WAVE reference stays unarmed: it compiles one program per
    distinct span length by design, and this stream's second wave
    introduces span lengths the first never ran."""
    config = default_test_simulation_config(
        COMPOSED_CONFIG_SUFFIX + FAULT_SUFFIX
    )
    cluster_events, workload = _composed_traces()
    wave = _build(False, config, cluster_events, workload)
    for scen, hor in SCENS:
        wave.submit(scen, hor)
    wave_res = wave.run()

    os.environ["KTPU_EXPLAIN_RECOMPILES"] = "1"
    try:
        asy = _build(True, config, cluster_events, workload)
        qids = [asy.submit(s, h) for s, h in SCENS]
        asy.run_async()

        perm = [4, 2, 3, 0, 1]
        asy_p = _build(True, config, cluster_events, workload)
        qids_p = [asy_p.submit(*SCENS[i]) for i in perm]
        asy_p.run_async()

        yield wave, wave_res, asy, qids, asy_p, qids_p, perm
        wave.close()
        asy.close()
        asy_p.close()
    finally:
        os.environ.pop("KTPU_EXPLAIN_RECOMPILES", None)


def _same_result(a, b):
    return (
        a.counters == b.counters
        and a.hpa_replicas == b.hpa_replicas
        and a.ca_nodes == b.ca_nodes
    )


def test_async_bit_matches_wave(async_ab_runs):
    """The A/B gate: every query's counters / replica / node readouts are
    bit-identical between the wave-aligned and lane-async executions,
    with the chaos machinery demonstrably engaged."""
    wave, wave_res, asy, qids, _, _, _ = async_ab_runs
    total_faults = 0
    for i, qid in enumerate(qids):
        ra, rw = asy.results[qid], wave_res[i]
        assert _same_result(ra, rw), (
            f"query {i} ({SCENS[i]}) diverges between wave and async:\n"
            f"{rw.counters}\n{ra.counters}"
        )
        total_faults += (
            ra.counters["pod_restarts"] + ra.counters["node_crashes"]
        )
    assert total_faults > 0, "chaos fleet produced no faults (vacuous gate)"


def test_async_lane_permutation_bit_identical(async_ab_runs):
    """Permuted submission order = different lanes, different clock
    offsets, different reseed timing — identical per-query results. The
    in-stream duplicate (scenario 0 == 3) also bit-matches within one
    fleet across its two placements."""
    _, _, asy, qids, asy_p, qids_p, perm = async_ab_runs
    for j, i in enumerate(perm):
        ra, rp = asy.results[qids[i]], asy_p.results[qids_p[j]]
        assert _same_result(ra, rp), (
            f"scenario {i} differs between lane {ra.lane} (in-order) and "
            f"lane {rp.lane} (permuted)"
        )
    r0, r3 = asy.results[qids[0]], asy.results[qids[3]]
    assert _same_result(r0, r3)


def test_async_poll_streams_each_result_once(async_ab_runs):
    """poll() is the streaming read side: after run_async drained the
    whole stream, one poll returns every result exactly once (completion
    order) and the next poll returns nothing."""
    _, _, asy, qids, _, _, _ = async_ab_runs
    polled = asy.poll()
    assert sorted(r.query for r in polled) == sorted(qids)
    assert asy.poll() == []


def test_async_rerun_is_recompile_free(async_ab_runs):
    """The compile-once contract across reseeds: re-submitting the whole
    stream to the warm fleet moves no jit-cache count (and the armed
    sentinel would raise on any hidden compile), and reproduces the
    first run's results exactly."""
    _, _, asy, qids, _, _, _ = async_ab_runs
    assert asy._sentinel is not None, (
        "KTPU_EXPLAIN_RECOMPILES=1 did not arm the fleet sentinel"
    )
    first = {i: asy.results[qid] for i, qid in enumerate(qids)}
    sizes0 = jit_cache_sizes()
    rerun_qids = [asy.submit(s, h) for s, h in SCENS]
    asy.run_async()
    sizes1 = jit_cache_sizes()
    assert sizes0 == sizes1, {
        k: (sizes0[k], sizes1[k]) for k in sizes0 if sizes0[k] != sizes1[k]
    }
    for i, qid in enumerate(rerun_qids):
        assert _same_result(asy.results[qid], first[i]), f"rerun query {i}"
    asy.poll()  # drain the completion queue for later gates


def test_async_ledger_and_latency_account_every_query(async_ab_runs):
    """The occupancy ledger saw busy lane-windows, every completed query
    has a latency sample, and reset_query_stats() returns both to their
    pre-run state."""
    _, _, _, _, asy_p, qids_p, _ = async_ab_runs
    occ = asy_p.lane_occupancy()
    assert 0.0 < occ["min"] <= occ["mean"] <= 1.0
    assert occ["lane_windows_busy"] > 0
    lat = asy_p.query_latency_percentiles()
    assert lat["count"] == len(qids_p)
    assert 0.0 < lat["p50_ms"] <= lat["p95_ms"] <= lat["p99_ms"]
    asy_p.reset_query_stats()
    assert asy_p.query_latency_percentiles() == {"count": 0}
    assert asy_p.lane_occupancy()["mean"] == 1.0  # pristine ledger


def test_async_poll_unknown_qid_raises_with_inventory(async_ab_runs):
    """poll(qid) on a never-submitted query id is a LOUD KeyError that
    names the qid and inventories what the fleet has actually seen —
    never a silent empty list (a typo'd qid would otherwise read as
    'still pending' forever)."""
    _, _, asy, _, _, _, _ = async_ab_runs
    with pytest.raises(KeyError, match=r"poll\(9999\).*never submitted"):
        asy.poll(9999)
    with pytest.raises(KeyError, match="never submitted"):
        asy.poll(-1)
    try:
        asy.poll(9999)
    except KeyError as err:
        msg = str(err)
        assert "submitted (qids 0.." in msg  # the known-qid inventory
    # A known-but-pending qid is NOT an error: it returns [] (qid 0 was
    # submitted and already polled, so it's known and not completed).
    assert asy.poll(0) == []


def test_async_poll_after_reset_streams_results(async_ab_runs):
    """Poll-after-reset semantics: reset_query_stats() clears the latency
    HISTOGRAMS (count back to 0) but never discards RESULTS — a query
    completed before the reset is still polled exactly once after it."""
    _, _, _, _, asy_p, qids_p, _ = async_ab_runs
    # The ledger gate above already reset asy_p's stats; its results were
    # never polled.
    assert asy_p.query_latency_percentiles() == {"count": 0}
    first = asy_p.poll(qids_p[0])
    assert len(first) == 1 and first[0].query == qids_p[0]
    assert asy_p.poll(qids_p[0]) == []  # streamed once, even post-reset
    rest = asy_p.poll()
    assert sorted(r.query for r in rest) == sorted(qids_p[1:])
    # Polling completions from BEFORE the reset does not repopulate the
    # histograms: recording happens at drain time, not poll time.
    assert asy_p.query_latency_percentiles() == {"count": 0}


def test_async_query_lifecycle_stages_are_monotone(async_ab_runs):
    """Every polled query's lifecycle record carries the five host-clock
    stages of DESIGN §14 in order (submitted <= admitted <=
    first-dispatch <= drained <= polled) and a real lane assignment."""
    _, _, asy, qids, _, _, _ = async_ab_runs
    for qid in qids:
        rec = asy.query_lifecycle(qid)
        assert rec["lane"] >= 0
        assert "flow_id" in rec  # 0 here: the fixture runs untraced
        assert (
            rec["submitted_ns"]
            <= rec["admitted_ns"]
            <= rec["first_dispatch_ns"]
            <= rec["drained_ns"]
            <= rec["polled_ns"]
        ), rec
    with pytest.raises(KeyError, match="no lifecycle record"):
        asy.query_lifecycle(31337)


def test_async_matches_scalar_oracles_at_own_horizons():
    """Per-query scalar-oracle equivalence under heterogeneous horizons:
    each lane-async query's final HPA replica count equals an
    independent scalar run of that scenario stepped to that query's own
    horizon — four queries over three lanes, so one oracle checks a
    RE-SEEDED lane (the test_fleet HPA oracle protocol; tolerance-only
    scenarios, where scalar and batched sampling provably agree)."""
    queries = [
        (Scenario(), 950.0),
        (Scenario(hpa_tolerance=0.02), 470.0),
        (Scenario(hpa_tolerance=0.4), 710.0),
        (Scenario(hpa_tolerance=0.02), 230.0),
    ]
    workload = make_hpa_workload(29)
    base = default_test_simulation_config()
    base.horizontal_pod_autoscaler.enabled = True
    fleet = ScenarioFleet(
        base,
        GenericClusterTrace.from_yaml(
            HPA_CLUSTER_TRACE
        ).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload).convert_to_simulator_events(),
        n_lanes=3,
        horizon=950.0,
        use_pallas=False,
        lane_async=True,
    )
    qids = [fleet.submit(s, h) for s, h in queries]
    fleet.run_async()
    diverged = set()
    for i, (scen, hor) in enumerate(queries):
        cfg = default_test_simulation_config()
        cfg.horizontal_pod_autoscaler.enabled = True
        sim = KubernetriksSimulation(_apply_scenario_to_config(cfg, scen))
        sim.initialize(
            GenericClusterTrace.from_yaml(HPA_CLUSTER_TRACE),
            GenericWorkloadTrace.from_yaml(workload),
        )
        sim.step_until_time(hor)
        groups = sim.horizontal_pod_autoscaler.pod_groups
        oracle = (
            len(groups["pod_group_1"].created_pods)
            if "pod_group_1" in groups
            else 0
        )
        got = fleet.results[qids[i]].hpa_replicas["pod_group_1"]
        assert got == oracle, (
            f"query {i} ({scen}, horizon {hor}): async fleet reports "
            f"{got} replicas, scalar oracle {oracle}"
        )
        diverged.add((oracle, hor))
    assert len(diverged) > 1  # the heterogeneity was non-vacuous
    fleet.close()


def test_trace_mux_masks_and_never_reoffers():
    """The lane trace multiplexer: a masked row span changes results
    (non-vacuous), equal masks bit-match across lane placements
    (including a 1-lane fleet — placement invariance), and offering a
    FLYING lane raises (never-re-offer invariant)."""
    config = default_test_simulation_config(
        COMPOSED_CONFIG_SUFFIX + FAULT_SUFFIX
    )
    cluster_events, workload = _composed_traces()
    fleet = _build(True, config, cluster_events, workload)
    E = fleet.engine._lane_mux.n_rows
    q_full = fleet.submit(Scenario(fault_seed=11), 300.0)
    q_mask = fleet.submit(
        Scenario(fault_seed=11), 300.0, trace_rows=(0, E // 2)
    )
    q_full2 = fleet.submit(Scenario(fault_seed=11), 300.0)
    # Lands on a RE-USED lane: the mux must retire the old span first.
    q_mask2 = fleet.submit(
        Scenario(fault_seed=11), 300.0, trace_rows=(0, E // 2)
    )
    fleet.run_async()
    res = fleet.results
    assert res[q_full].counters == res[q_full2].counters
    assert res[q_mask].counters == res[q_mask2].counters
    assert res[q_full].counters != res[q_mask].counters, "mask did not bite"

    solo = ScenarioFleet(
        config,
        cluster_events,
        workload,
        n_lanes=1,
        horizon=450.0,
        max_pods_per_cycle=16,
        use_pallas=False,
        ca_slot_multiplier=4,
        lane_async=True,
    )
    s1 = solo.submit(Scenario(fault_seed=11), 300.0, trace_rows=(0, E // 2))
    solo.run_async()
    assert solo.results[s1].counters == res[q_mask].counters
    solo.close()

    flying = _build(True, config, cluster_events, workload)
    flying.submit(Scenario(), 300.0)
    flying.pump()  # lane 0 is now in flight
    with pytest.raises(RuntimeError, match="fly|flight|active"):
        flying.engine.set_lane_trace(0, 0, E // 2)
    flying.close()


def test_async_every_qid_streams_exactly_one_terminal_outcome(
    async_ab_runs,
):
    """The stream-once contract covers FAILURES too (the poll()
    hang-forever fix): a mixed stream — one query doomed by an
    already-expired deadline, one healthy — delivers exactly one
    terminal outcome per qid through poll(), discriminated by the shared
    `.ok`/`.kind` protocol, and a dead query never leaves its client
    polling forever."""
    from kubernetriks_tpu.batched.faults import (
        DeadlineExceededError,
        QueryError,
    )

    _, _, asy, qids, _, _, _ = async_ab_runs
    reference = asy.results[qids[0]]
    asy.poll()  # drain completions earlier gates may not have polled
    q_dead = asy.submit(*SCENS[0], deadline_s=1e-9)  # expired on arrival
    q_live = asy.submit(*SCENS[0])
    asy.run_async()
    outcomes = asy.poll()
    assert sorted(o.query for o in outcomes) == sorted([q_dead, q_live])
    by_qid = {o.query: o for o in outcomes}
    dead, live = by_qid[q_dead], by_qid[q_live]
    assert isinstance(dead, DeadlineExceededError)
    assert isinstance(dead, QueryError)  # a real Exception subclass
    assert (dead.ok, dead.kind) == (False, "deadline_exceeded")
    assert dead.lane == -1, "deadline failure must never occupy a lane"
    assert dead.late_s >= 0.0 and "deadline exceeded" in dead.message
    assert (live.ok, live.kind) == (True, "result")
    assert _same_result(live, reference)
    # Streamed exactly once: the broadcast poll and the per-qid poll are
    # both empty now, for the error exactly like for the result.
    assert asy.poll() == []
    assert asy.poll(q_dead) == [] and asy.poll(q_live) == []
    assert asy.failed_queries.get("deadline_exceeded", 0) >= 1


# --- the pump round's transport (PR 33): one packed readback, one packed
# admission ------------------------------------------------------------------

GOLDEN = os.path.join(os.path.dirname(__file__), "data", "fleet_pump_golden.json")
TRANSPORT_LANES = 8
TRANSPORT_SPAN = 4
RANGED = 160 // 2  # half the composed trace's rows (the golden records 160)

# Three horizons, scenarios with and without overrides (a seed with the
# high bit set, a disabled HPA, a CA quota of zero), a trace range on some.
MIXED = [
    (Scenario(), 90.0, None),
    (Scenario(fault_seed=11, hpa_scan_interval=30.0), 250.0, None),
    (Scenario(ca_threshold=0.7), 150.0, (0, RANGED)),
    (Scenario(fault_seed=22, hpa_tolerance=0.25), 90.0, (10, None)),
    (Scenario(hpa_enabled=False), 150.0, None),
    (
        Scenario(
            fault_seed=33, ca_max_node_count=0, as_to_ca_network_delay=0.9
        ),
        250.0,
        None,
    ),
    (Scenario(), 250.0, (0, RANGED)),
    (Scenario(fault_seed=2**31 + 5, ca_scan_interval=20.0), 90.0, None),
]
RESULT_FIELDS = (
    "counters",
    "hpa_replicas",
    "ca_nodes",
    "hpa_reserve_clamped",
    "ca_reserve_starved",
)


def _full_ranges(fleet):
    """Every lane back on the whole trace (a ranged query leaves its range
    installed until the lane's next admission changes it), so that the
    rounds counted below carry no range change of an earlier test's."""
    horizon = (TRANSPORT_SPAN - 1) * fleet.config.scheduling_cycle_interval
    for _ in range(TRANSPORT_LANES):
        fleet.submit(Scenario(), horizon)
    fleet.run_async()
    fleet.poll()


def _transfers(fleet):
    counters = fleet.engine.tracer.counters
    return (
        counters.get("pump_transfers_down", 0),
        counters.get("pump_transfers_up", 0),
    )


@pytest.fixture(scope="module")
def transport_runs(async_ab_runs):
    """An 8-lane lane-async fleet under the armed sentinel, fed the MIXED
    stream (the golden's order), and the module's wave fleet fed the
    queries of it that carry no trace range (a wave fleet has no trace
    multiplexer)."""
    import json

    wave = async_ab_runs[0]
    config = default_test_simulation_config(
        COMPOSED_CONFIG_SUFFIX + FAULT_SUFFIX
    )
    cluster_events, workload = _composed_traces()
    os.environ["KTPU_EXPLAIN_RECOMPILES"] = "1"
    try:
        fleet = ScenarioFleet(
            config,
            cluster_events,
            workload,
            n_lanes=TRANSPORT_LANES,
            horizon=450.0,
            max_pods_per_cycle=16,
            use_pallas=False,
            ca_slot_multiplier=4,
            lane_async=True,
            span_windows=TRANSPORT_SPAN,
        )
        assert fleet._sentinel is not None
        assert fleet.engine._lane_mux.n_rows == 2 * RANGED
        qids = [fleet.submit(s, h, trace_rows=r) for s, h, r in MIXED]
        fleet.run_async()
        fleet.poll()
        wave_qids = {
            i: wave.submit(s, h)
            for i, (s, h, r) in enumerate(MIXED)
            if r is None
        }
        wave.run()
        with open(GOLDEN) as fh:
            golden = json.load(fh)["results"]
        yield fleet, qids, wave, wave_qids, golden
        fleet.close()
    finally:
        os.environ.pop("KTPU_EXPLAIN_RECOMPILES", None)


@pytest.mark.parametrize("i", range(len(MIXED)))
def test_pump_result_equals_wave_and_parent_golden(transport_runs, i):
    """Same integers through the packed readback and the packed
    admission: every FleetResult field of the pump path equals the golden
    the PARENT's per-leaf transport returned for the same query (JSON:
    plain ints, so a numpy scalar in a result fails here too), and, where
    the query carries no trace range, the wave path's."""
    fleet, qids, wave, wave_qids, golden = transport_runs
    scen, horizon, _ = MIXED[i]
    got = fleet.results[qids[i]]
    assert got.ok and got.scenario == scen and got.horizon == horizon
    for name in RESULT_FIELDS:
        assert getattr(got, name) == golden[i][name], (i, name)
    assert all(type(v) is int for v in got.counters.values())
    assert all(type(v) is int for v in got.ca_nodes)
    assert all(type(v) is int for v in got.hpa_replicas.values())
    if i in wave_qids:
        ref = wave.results[wave_qids[i]]
        for name in RESULT_FIELDS:
            assert getattr(got, name) == getattr(ref, name), (i, name)


@pytest.mark.parametrize(
    "drains,admits", [(1, 1), (7, 7), (8, 8), (1, 7), (7, 1)]
)
def test_round_transfers_do_not_depend_on_the_lanes(
    transport_runs, drains, admits
):
    """One transfer down and two up (the admission's buffer, the
    dispatch's window indices) in a round that drains `drains` lanes and
    admits `admits`, whatever the two numbers: the transport is a mask,
    never a loop over lanes."""
    fleet = transport_runs[0]
    _full_ranges(fleet)
    interval = fleet.config.scheduling_cycle_interval
    one_round = (TRANSPORT_SPAN - 1) * interval  # span windows exactly
    two_rounds = (2 * TRANSPORT_SPAN - 1) * interval
    if drains + admits <= TRANSPORT_LANES:
        for _ in range(drains):
            fleet.submit(Scenario(fault_seed=5), two_rounds)
        assert fleet.pump() == 0  # admitted and stepped, none finished
        for _ in range(admits):
            fleet.submit(Scenario(fault_seed=6), two_rounds)
    else:  # the same lanes, admitted and drained by one round
        assert drains == admits
        for _ in range(admits):
            fleet.submit(Scenario(fault_seed=5), one_round)
    before = _transfers(fleet)
    assert fleet.pump() == drains
    after = _transfers(fleet)
    assert (after[0] - before[0], after[1] - before[1]) == (1, 2)
    fleet.run_async()
    assert not any(q for q in fleet.poll() if not q.ok)


def test_changed_trace_range_is_one_more_put(transport_runs):
    """A lane whose workload range changes at admission costs one put of
    its own, on the same counter; an unchanged range costs none."""
    fleet = transport_runs[0]
    _full_ranges(fleet)
    horizon = (TRANSPORT_SPAN - 1) * fleet.config.scheduling_cycle_interval
    ups = []
    for rows in ((0, RANGED), (0, RANGED), None):
        fleet.submit(Scenario(), horizon, trace_rows=rows)
        before = _transfers(fleet)
        assert fleet.pump() == 1
        ups.append(_transfers(fleet)[1] - before[1])
        fleet.poll()
    # lane 0 each time: range installed, range kept, full range restored
    assert ups == [3, 2, 3]


@pytest.mark.parametrize("ranged", [False, True])
def test_fifty_rounds_compile_nothing(transport_runs, ranged):
    """Compile-once: under the armed sentinel (a compile inside a round
    raises), fifty more rounds of the mixed stream leave every jit entry
    of the dispatch loop, the packed admission and the packed readback
    among them, at its warm-up count."""
    fleet = transport_runs[0]
    sizes0 = jit_cache_sizes()
    assert sizes0["admit_lanes"] >= 1 and sizes0["pack_lane_rows"] >= 1
    rounds0 = fleet.pump_rounds
    k = 0
    while fleet.pump_rounds - rounds0 < 50:
        if fleet.pending < TRANSPORT_LANES:
            scen, horizon, rows = MIXED[k % len(MIXED)]
            fleet.submit(scen, horizon, trace_rows=rows if ranged else None)
            k += 1
        fleet.pump()
    while fleet.pending or fleet._active:
        fleet.pump()
    assert jit_cache_sizes() == sizes0
    assert all(q.ok for q in fleet.poll())


@pytest.mark.parametrize(
    "counter", ["hpa_reserve_clamped", "ca_reserve_starved"]
)
def test_strict_divergence_still_raises_from_the_packed_row(
    transport_runs, counter
):
    """The loud readout reads the packed row: a lane whose divergence
    counter is non-zero at its drain raises, naming the lane and the
    counter; the lane's next admission selects the pristine state back
    in, and the same query then returns the reference's integers."""
    import jax.numpy as jnp

    fleet, qids = transport_runs[:2]
    scen, horizon, _ = MIXED[0]
    fleet.submit(scen, horizon)
    assert fleet.pump() == 0
    (lane,) = fleet._active
    eng = fleet.engine
    poked = getattr(eng.state.metrics, counter) + (
        jnp.arange(TRANSPORT_LANES) == lane
    ).astype(jnp.int32) * 3
    eng.state = eng.state._replace(
        metrics=eng.state.metrics._replace(**{counter: poked})
    )
    with pytest.raises(RuntimeError, match=rf"lane {lane}\).*{counter}=3"):
        fleet.run_async()
    assert lane not in fleet._active
    again = fleet.submit(scen, horizon)
    fleet.run_async()
    got = fleet.results[again]
    assert got.lane == lane and got.ok
    assert _same_result(got, fleet.results[qids[0]])
    assert (got.hpa_reserve_clamped, got.ca_reserve_starved) == (0, 0)
    fleet.poll()


def test_crash_reset_lane_is_readmitted_through_the_packed_admission(
    transport_runs,
):
    """A dispatch fault crash-resets its lane through the public
    lane_reset + set_lane_plan; the next query lands on that lane through
    admit_lanes and returns what a lane that never faulted returns."""
    from kubernetriks_tpu.batched.faults import LaneFaultError

    fleet, qids = transport_runs[:2]
    scen, horizon, _ = MIXED[1]

    class FaultLaneZeroOnce:
        seed = -1
        fired = False

        def stall_s(self):
            return 0.0

        def dispatch_fault(self, active):
            if not self.fired and 0 in active:
                self.fired = True
                return 0
            return None

        def report(self):
            return {}

    dead = fleet.submit(scen, horizon)
    fleet.arm_host_chaos(FaultLaneZeroOnce())
    fleet.pump()
    fleet.arm_host_chaos(None)
    assert isinstance(fleet.results[dead], LaneFaultError)
    assert "InjectedFault" in fleet.results[dead].cause
    again = fleet.submit(scen, horizon)
    fleet.run_async()
    got = fleet.results[again]
    assert got.ok and got.lane == 0
    assert _same_result(got, fleet.results[qids[1]])
    fleet.poll()
