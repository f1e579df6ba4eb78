"""Fault-domain gates for the serving fleet (batched/faults.py + the
fleet.py fault isolation of DESIGN §15).

1. TYPED OUTCOMES: the QueryError taxonomy carries the FleetResult
   readout protocol (`.ok` / `.kind` / `.query` / `.lane`), every class
   is a real Exception, and poll() streams errors under the same
   stream-once contract as results.
2. HOST CHAOS: the counter-seeded injector replays the exact same fault
   schedule per seed, the least-faulted victim rule covers every lane by
   construction, and `KTPU_HOST_CHAOS` parsing is loud on bad specs.
3. ISOLATION + QUARANTINE (module fixture, one scripted end-to-end run):
   a dispatch fault kills ONLY the victim lane's query — neighbors and
   every later query on the crash-reset lane bit-match a fault-free
   reference fleet; the faulted lane quarantines, backs off, probes and
   re-admits; the whole fault path moves no jit-cache count.
4. HOST BOUNDARIES: loud submit() validation naming the field, bounded
   admission (reject streams RejectedError with a retry-after hint;
   block pumps inline), queued-past-deadline failure without occupying a
   lane, graceful close() (drain in-flight, fail queued, refuse new).
5. STREAM-ONCE AUDIT: across the fixture's whole life — quiet, chaos,
   deadline, backpressure, shutdown — every submitted qid streamed
   exactly one terminal outcome through poll().
"""

import pytest

from kubernetriks_tpu.batched.faults import (
    DeadlineExceededError,
    FeederError,
    HostChaos,
    InjectedFault,
    LaneFaultError,
    QueryError,
    RejectedError,
    ShutdownError,
)
from kubernetriks_tpu.batched.fleet import (
    FleetResult,
    Scenario,
    ScenarioFleet,
    jit_cache_sizes,
)
from kubernetriks_tpu.test_util import default_test_simulation_config

from test_fleet import FAULT_SUFFIX, _composed_traces
from test_fleet_async import SCENS
from test_window_donation_dispatch import COMPOSED_CONFIG_SUFFIX


# --- the QueryError taxonomy (pure protocol, no engine) ----------------------


def test_query_outcome_protocol():
    """Results and errors share one discrimination protocol: `.ok` and a
    stable string `.kind` — a poll loop never needs isinstance ladders,
    and every error is a real Exception (raisable where no qid exists)."""
    assert FleetResult.ok is True and FleetResult.kind == "result"
    taxonomy = {
        RejectedError: "rejected",
        DeadlineExceededError: "deadline_exceeded",
        LaneFaultError: "lane_fault",
        FeederError: "feeder",
        ShutdownError: "shutdown",
    }
    for cls, kind in taxonomy.items():
        err = cls(7, "boom", lane=2)
        assert isinstance(err, QueryError) and isinstance(err, Exception)
        assert err.ok is False and err.kind == kind
        assert (err.query, err.lane, err.message) == (7, 2, "boom")
    # Kind-specific payloads.
    rej = RejectedError(1, "full", retry_after_s=0.25)
    assert rej.retry_after_s == 0.25
    lane = LaneFaultError(2, "died", cause=ValueError("xla"))
    assert isinstance(lane.cause, str) and "xla" in lane.cause  # repr'd
    feed = FeederError(3, "producer died", slab_lo=128, restarts=2)
    assert (feed.slab_lo, feed.restarts) == (128, 2)
    with pytest.raises(ShutdownError):
        raise ShutdownError(-1, "no qid to stream under")


# --- HostChaos: determinism, victim rule, flag parsing -----------------------


def test_host_chaos_flag_parsing_is_loud():
    for off in (None, "", "0", "false", "no", "off", "OFF"):
        assert HostChaos.from_flag(off) is None
    on = HostChaos.from_flag("1")
    assert (on.seed, on.dispatch_rate) == (7, 0.04)
    assert (on.feeder_rate, on.stall_rate, on.stall_ms) == (0.05, 0.03, 2.0)
    spec = HostChaos.from_flag("seed=3, dispatch=0.5, stall_ms=1.5")
    assert (spec.seed, spec.dispatch_rate, spec.stall_ms) == (3, 0.5, 1.5)
    assert spec.feeder_rate == 0.05  # unspecified keys keep the defaults
    with pytest.raises(ValueError, match="unknown key 'bogus'"):
        HostChaos.from_flag("bogus=1")
    with pytest.raises(ValueError, match="key=value"):
        HostChaos.from_flag("just-noise")


def test_host_chaos_schedule_is_a_pure_function_of_the_seed():
    def schedule(seed):
        chaos = HostChaos(seed=seed, dispatch_rate=0.3, stall_rate=0.3)
        return [
            (chaos.dispatch_fault([0, 1, 2]), chaos.stall_s())
            for _ in range(40)
        ]

    assert schedule(7) == schedule(7)  # replayable
    assert schedule(7) != schedule(8)  # and actually seeded
    hits = [v for v, _ in schedule(7) if v is not None]
    assert hits, "rate 0.3 over 40 draws produced no faults (vacuous)"


def test_host_chaos_victim_rule_covers_every_lane():
    """The least-faulted rule (ties to the lowest index): coverage is by
    construction, even when the active set shrinks mid-run — the shrunk
    set's survivor still gets faulted, and a re-grown set resumes at its
    least-faulted member."""
    chaos = HostChaos(seed=1, dispatch_rate=1.0)
    assert [chaos.dispatch_fault([0, 1, 2]) for _ in range(3)] == [0, 1, 2]
    assert chaos.dispatch_fault([0, 1, 2]) == 0  # wraps to least-faulted
    shrunk = HostChaos(seed=1, dispatch_rate=1.0)
    assert shrunk.dispatch_fault([0, 1]) == 0
    assert shrunk.dispatch_fault([1]) == 1
    assert shrunk.dispatch_fault([1]) == 1
    assert shrunk.dispatch_fault([0, 1, 2]) == 2  # never-faulted lane
    assert shrunk.dispatch_fault([]) is None  # nothing active, no fault
    assert shrunk.events["dispatch_faults"] == 4


def test_host_chaos_stall_and_feeder_channels():
    chaos = HostChaos(seed=2, stall_rate=1.0, stall_ms=5.0)
    assert chaos.stall_s() == pytest.approx(0.005)
    assert HostChaos(seed=2).stall_s() == 0.0  # rate 0: no draw, no stall
    killer = HostChaos(seed=2, feeder_rate=1.0)
    assert killer.feeder_kill() is True
    assert HostChaos(seed=2).feeder_kill() is False
    rep = killer.report()
    assert rep["seed"] == 2 and rep["events"]["feeder_kills"] == 1
    assert set(rep["rates"]) == {"dispatch", "feeder", "stall"}


# --- the scripted end-to-end fault run (module fixture) ----------------------


class ScriptedInjector:
    """Duck-typed HostChaos stand-in that faults EXACTLY the scripted
    lanes, in order, whenever the head of the script is active — the
    surgical control the isolation gates need (the probabilistic
    injector is covered above and, on a fleet, by
    test_seeded_host_chaos_keeps_the_fleet_available)."""

    def __init__(self, script):
        self.script = list(script)
        self.seed = -1  # InjectedFault's message interpolates it
        self.faults = 0

    def stall_s(self):
        return 0.0

    def feeder_kill(self):
        return False

    def dispatch_fault(self, active_lanes):
        if self.script and self.script[0] in {int(v) for v in active_lanes}:
            self.faults += 1
            return self.script.pop(0)
        return None

    def report(self):
        return {
            "seed": self.seed,
            "rates": {},
            "events": {"dispatch_faults": self.faults},
        }


@pytest.fixture(scope="module")
def fault_run():
    """One reference fleet (fault-free) + one chaos fleet driven through
    every fault domain in sequence: quiet A/B, scripted lane faults with
    quarantine/probe/re-admission, an expired deadline, bounded
    admission (reject + block), and a graceful close with work queued.
    Every poll() outcome is tallied for the stream-once audit."""
    config = default_test_simulation_config(
        COMPOSED_CONFIG_SUFFIX + FAULT_SUFFIX
    )
    cluster_events, workload = _composed_traces()

    def build(**kw):
        return ScenarioFleet(
            config,
            cluster_events,
            workload,
            n_lanes=3,
            horizon=450.0,
            max_pods_per_cycle=16,
            use_pallas=False,
            ca_slot_multiplier=4,
            lane_async=True,
            **kw,
        )

    art = {}
    ref = build()
    ref_qids = [ref.submit(s, h) for s, h in SCENS]
    ref.run_async()
    ref.poll()
    art["ref_results"] = [ref.results[q] for q in ref_qids]

    fl = build(quarantine_faults=1, quarantine_window=64, quarantine_backoff=2)
    outcome_counts = {}

    def drain_poll():
        polled = fl.poll()
        for o in polled:
            outcome_counts[o.query] = outcome_counts.get(o.query, 0) + 1
        return polled

    # Phase 1 — QUIET: aggressive quarantine thresholds configured, no
    # injector armed. Must bit-match the plain reference fleet.
    quiet_qids = [fl.submit(s, h) for s, h in SCENS]
    fl.run_async()
    drain_poll()
    art["quiet_results"] = [fl.results[q] for q in quiet_qids]
    art["quiet_stats"] = dict(fl.engine.dispatch_stats)
    art["ref_stats"] = dict(ref.engine.dispatch_stats)
    art["quiet_report"] = fl.fault_report()

    # Phase 2 — CHAOS: script one fault on lane 0, then one on lane 1.
    # quarantine_faults=1 means each fault fires a quarantine; the
    # 2-round backoff expires mid-stream, so both lanes probe and
    # re-admit before the queue dries.
    sizes_before = jit_cache_sizes()
    injector = ScriptedInjector([0, 1])
    fl.arm_host_chaos(injector)
    chaos_qids = [fl.submit(s, h) for s, h in SCENS + SCENS]
    states_seen = set()
    while fl.pending or fl._active:
        fl.pump()
        states_seen.update(fl.lane_states())
    art["chaos_states_seen"] = states_seen
    art["chaos_outcomes"] = drain_poll()
    art["chaos_qids"] = chaos_qids
    art["chaos_results"] = [fl.results[q] for q in chaos_qids]
    art["chaos_report"] = fl.fault_report()
    art["jit_cache_moved"] = {
        k: (sizes_before[k], v)
        for k, v in jit_cache_sizes().items()
        if sizes_before.get(k) != v
    }
    fl.arm_host_chaos(None)

    # Phase 3 — DEADLINE: expired-on-arrival query fails at the next
    # pump boundary without ever occupying a lane.
    art["deadline_qid"] = fl.submit(SCENS[0][0], 150.0, deadline_s=1e-9)
    fl.run_async()
    art["deadline_outcomes"] = drain_poll()

    # Phase 4 — BOUNDED ADMISSION: reject streams a typed refusal with a
    # retry-after hint; block pumps inline until a slot frees.
    fl.max_queue, fl.queue_policy = 1, "reject"
    art["accepted_qid"] = fl.submit(*SCENS[0])
    art["rejected_qid"] = fl.submit(*SCENS[1])
    art["rejected_outcomes"] = drain_poll()  # streamed before any pump
    fl.queue_policy = "block"
    art["blocked_qids"] = [fl.submit(*SCENS[i]) for i in range(3)]
    art["queue_depth_after_block"] = fl.pending
    fl.run_async()
    drain_poll()
    fl.max_queue, fl.queue_policy = None, "reject"

    # Phase 5 — GRACEFUL CLOSE: 5 queries over 3 lanes, one pump (all
    # lanes in flight, 2 queued), then close(drain=True).
    shut_qids = [fl.submit(s, h) for s, h in SCENS]
    fl.pump()
    art["in_flight_at_close"] = sorted(
        q for q, _, _ in fl._active.values()
    )
    fl.close()
    art["shut_qids"] = shut_qids
    art["shutdown_outcomes"] = drain_poll()
    art["outcome_counts"] = outcome_counts
    art["n_submitted"] = fl._next_query
    art["final_report"] = fl.fault_report()

    yield ref, fl, art
    ref.close()


def test_quiet_robustness_layer_is_free(fault_run):
    """Quarantine thresholds configured + injector unarmed = the exact
    pre-fault-domain fleet: bit-identical per-query results and equal
    engine dispatch_stats against the plain reference."""
    _, _, art = fault_run
    for i, (rq, rr) in enumerate(
        zip(art["quiet_results"], art["ref_results"])
    ):
        assert rq.ok and rr.ok
        assert (
            rq.counters == rr.counters
            and rq.hpa_replicas == rr.hpa_replicas
            and rq.ca_nodes == rr.ca_nodes
        ), f"quiet query {i} diverges from the plain reference fleet"
    assert art["quiet_stats"] == art["ref_stats"]
    rep = art["quiet_report"]
    assert rep["chaos"] is None and rep["failed"] == {}
    assert rep["availability"] == 1.0


def test_lane_fault_is_isolated_to_the_victim_query(fault_run):
    """Poison isolation: exactly the two scripted queries die (typed
    LaneFaultError naming the lane and cause), every OTHER chaos-phase
    query — including later queries re-seeded onto the crash-reset
    lanes — bit-matches the fault-free reference."""
    _, _, art = fault_run
    fails = [r for r in art["chaos_results"] if not r.ok]
    assert len(fails) == 2
    assert sorted(f.lane for f in fails) == [0, 1]
    for f in fails:
        assert isinstance(f, LaneFaultError) and f.kind == "lane_fault"
        assert "InjectedFault" in f.cause and "crash-reset" in f.message
        assert f.scenario is not None and f.horizon is not None
    for i, r in enumerate(art["chaos_results"]):
        if not r.ok:
            continue
        ref_r = art["ref_results"][i % len(SCENS)]
        assert (
            r.counters == ref_r.counters
            and r.hpa_replicas == ref_r.hpa_replicas
            and r.ca_nodes == ref_r.ca_nodes
        ), f"chaos-phase query {i} diverged after a NEIGHBOR lane fault"
    rep = art["chaos_report"]
    assert rep["failed"] == {"lane_fault": 2}
    assert rep["chaos"]["events"]["dispatch_faults"] == 2


def test_quarantine_fires_probes_and_readmits(fault_run):
    """The quarantine lifecycle: both faulted lanes leave the admission
    rotation (the states were observable mid-run), probe after the
    backoff, complete their probe query and re-admit — ending idle with
    no quarantine residue."""
    _, fl, art = fault_run
    assert {"quarantined", "probe", "active"} <= art["chaos_states_seen"]
    rep = art["chaos_report"]
    assert rep["quarantine_events"] == 2
    assert rep["readmissions"] == 2
    assert rep["lane_states"] == ["idle"] * 3
    assert fl._quarantine == {}  # no residue after re-admission


def test_fault_path_moves_no_jit_cache_count(fault_run):
    """Crash recovery is pure data ops: lane_reset + a zeroed plan reuse
    the admission path's compiled programs — the whole chaos phase moves
    no jit-cache count."""
    _, _, art = fault_run
    assert art["jit_cache_moved"] == {}, (
        "the fault/quarantine path RECOMPILED jit entries: "
        f"{art['jit_cache_moved']}"
    )


def test_deadline_fails_queued_query_without_a_lane(fault_run):
    _, _, art = fault_run
    (out,) = art["deadline_outcomes"]
    assert out.query == art["deadline_qid"]
    assert isinstance(out, DeadlineExceededError)
    assert out.lane == -1 and out.late_s >= 0.0
    assert "without" in out.message and "lane" in out.message


def test_bounded_admission_reject_and_block(fault_run):
    """policy='reject': the refused qid streams a RejectedError (with a
    retry-after hint once service times exist) BEFORE any pump —
    admission refusal is immediate. policy='block': submit() pumps
    inline until a slot frees, so the queue never exceeds the bound and
    everything completes."""
    _, fl, art = fault_run
    outs = {o.query: o for o in art["rejected_outcomes"]}
    rej = outs[art["rejected_qid"]]
    assert isinstance(rej, RejectedError) and rej.kind == "rejected"
    assert "queue full" in rej.message and "'reject'" in rej.message
    assert rej.retry_after_s is not None and rej.retry_after_s > 0.0
    assert art["accepted_qid"] not in outs  # accepted, not yet complete
    assert art["queue_depth_after_block"] <= 1
    for qid in [art["accepted_qid"]] + art["blocked_qids"]:
        assert fl.results[qid].ok, f"backpressured query {qid} failed"


def test_graceful_close_drains_in_flight_and_fails_queued(fault_run):
    """close(drain=True): the three in-flight queries finish with real
    results; the two still-queued fail with typed ShutdownErrors; new
    submits raise ShutdownError; poll() keeps working on host state."""
    _, fl, art = fault_run
    outs = {o.query: o for o in art["shutdown_outcomes"]}
    shut = art["shut_qids"]
    for qid in art["in_flight_at_close"]:
        assert outs[qid].ok, f"in-flight query {qid} was not drained"
    queued = [q for q in shut if q not in art["in_flight_at_close"]]
    assert len(queued) == 2
    for qid in queued:
        assert isinstance(outs[qid], ShutdownError)
        assert "queued at close()" in outs[qid].message
    with pytest.raises(ShutdownError, match="after close"):
        fl.submit(*SCENS[0])
    assert fl.poll() == []  # the stream stays functional after close


def test_every_submitted_qid_streamed_exactly_one_outcome(fault_run):
    """The stream-once audit across the fixture's WHOLE life — quiet,
    chaos, deadline, backpressure, shutdown: every qid ever submitted
    delivered exactly one terminal outcome through poll(), result and
    typed error alike (no hangs, no duplicates)."""
    _, _, art = fault_run
    counts = art["outcome_counts"]
    bad = {
        q: counts.get(q, 0)
        for q in range(art["n_submitted"])
        if counts.get(q, 0) != 1
    }
    assert not bad, f"qids without exactly one streamed outcome: {bad}"
    rep = art["final_report"]
    assert rep["submitted"] == art["n_submitted"]
    assert rep["completed"] + sum(rep["failed"].values()) == rep["submitted"]


# --- loud submit() validation (uses the open reference fleet) ----------------


def test_submit_validation_names_the_field(fault_run):
    """Malformed queries are caller bugs, rejected BEFORE admission with
    a ValueError naming the field and the legal range — never in-flight
    poison at a reseed boundary."""
    ref, _, _ = fault_run
    with pytest.raises(ValueError, match=r"unknown scenario key.*'warp'"):
        ref.submit({"warp": 9.0}, 100.0)
    with pytest.raises(ValueError, match=r"scenario\['ca_threshold'\].*SCALAR"):
        ref.submit({"ca_threshold": [0.5, 0.6]}, 100.0)
    with pytest.raises(ValueError, match=r"scenario\['hpa_tolerance'\].*>= 0"):
        ref.submit({"hpa_tolerance": -0.25}, 100.0)
    with pytest.raises(ValueError, match="Scenario or a mapping"):
        ref.submit(42, 100.0)
    for bad_h in (0, -5.0, float("nan"), "soon"):
        with pytest.raises(ValueError, match="horizon must be a finite"):
            ref.submit(Scenario(), bad_h)
    with pytest.raises(ValueError, match="deadline_s must be a finite"):
        ref.submit(Scenario(), 100.0, deadline_s=0.0)
    with pytest.raises(ValueError, match="trace_rows"):
        ref.submit(Scenario(), 100.0, trace_rows=(4, 2))
    # Nothing above was admitted: the queue is still empty.
    assert ref.pending == 0


# --- the seeded injector against a real fleet --------------------------------


def test_seeded_host_chaos_keeps_the_fleet_available(fault_run):
    """`HostChaos` itself (pinned seed, so the same schedule every run), armed
    on the warm reference fleet: the unit of failure is a query, never the
    fleet. Every round finishes, at least nine queries in ten come back with
    a result, every failure is a typed error streamed exactly once, the
    least-faulted victim rule reaches every lane, and the injected phase
    compiles nothing."""
    ref, _, _ = fault_run
    ref.poll()
    sizes_before = jit_cache_sizes()
    ref.arm_host_chaos(HostChaos(seed=7, dispatch_rate=0.05, stall_rate=0.05, stall_ms=1.0))
    qids, streamed = [], {}
    for _ in range(8):
        qids += [ref.submit(s, h) for s, h in SCENS]
        ref.run_async()
        for outcome in ref.poll():
            streamed[outcome.query] = streamed.get(outcome.query, 0) + 1
    report = ref.fault_report()
    ref.arm_host_chaos(None)
    assert [streamed.get(q, 0) for q in qids] == [1] * len(qids)
    fails = [ref.results[q] for q in qids if not ref.results[q].ok]
    assert fails and all(isinstance(f, LaneFaultError) for f in fails)
    assert report["chaos"]["events"]["dispatch_faults"] == len(fails)
    assert 1.0 - len(fails) / len(qids) >= 0.90, (len(fails), len(qids))
    assert sorted({f.lane for f in fails}) == [0, 1, 2]
    assert jit_cache_sizes() == sizes_before

