"""Chaos engine (kubernetriks_tpu/chaos.py): counter-PRNG parity, fault
compiler semantics, and the headline acceptance property — scalar-vs-batched
equivalence on fault-enabled random traces with identical fault metrics
(downtime, interruptions, restarts, permanently-failed), bit-identical
batched state across donation on/off and fast-forward on/off, and
seed-determinism (same seed -> bit-identical, different seed -> different).
"""

import jax
import numpy as np
import pytest

from kubernetriks_tpu import chaos
from kubernetriks_tpu.batched import step
from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.state import (
    PHASE_FAILED,
    PHASE_SUCCEEDED,
    PHASE_UNSCHEDULABLE,
    compare_states,
    tree_copy,
)
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.core.events import CreateNodeRequest, RemoveNodeRequest
from kubernetriks_tpu.core.types import Node, PodConditionType
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.test_util import default_test_simulation_config
from test_random_equivalence import END_TIME, generate_traces

FAULT_YAML = """
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

GROUP_FAULT_YAML = """
fault_injection:
  enabled: true
  node:
    mttf: 4000.0
    mttr: 150.0
  pod:
    fail_prob: 0.08
    restart_limit: 2
  failure_groups:
  - members: [node_000, node_001, node_002, node_003]
    mttf: 3000.0
    mttr: 200.0
"""

# Backoff shorter than the failure-chain delay (0.21s with the default test
# delays): every retry is floored at the chain arrival — the regime where a
# naive fail+backoff requeue would beat the failure notification to the
# queue and desync the paths by a whole scheduling cycle.
SHORT_BACKOFF_YAML = """
fault_injection:
  enabled: true
  node:
    mttf: 2500.0
    mttr: 120.0
  pod:
    fail_prob: 0.12
    backoff_base: 0.05
    backoff_cap: 0.1
    restart_limit: 3
"""


# --- counter PRNG ------------------------------------------------------------


def test_threefry_numpy_jnp_parity():
    """The scalar oracle (numpy) and the device draw (jnp) must produce
    bit-identical uniforms for the same counters."""
    import jax.numpy as jnp

    cluster = np.arange(64, dtype=np.uint32) % 7
    slot = np.arange(64, dtype=np.uint32) * 13
    attempt = np.arange(64, dtype=np.uint32) % 5
    a0, a1 = chaos.pod_attempt_uniforms(42, cluster, slot, attempt, xp=np)
    b0, b1 = chaos.pod_attempt_uniforms(
        42,
        jnp.asarray(cluster),
        jnp.asarray(slot),
        jnp.asarray(attempt),
        xp=jnp,
    )
    np.testing.assert_array_equal(a0, np.asarray(b0))
    np.testing.assert_array_equal(a1, np.asarray(b1))
    # Uniforms live in [0, 1) and are not degenerate.
    assert a0.min() >= 0.0 and a0.max() < 1.0
    assert len(np.unique(a0)) > 32


def test_counter_prng_is_order_independent():
    """A draw depends only on its counter tuple — evaluating in any order or
    batch shape yields the same value (the property that lets both paths
    draw lazily without a synchronized stream)."""
    single = chaos.pod_attempt_uniforms(
        7, np.uint32(3), np.uint32(17), np.uint32(2)
    )
    batch = chaos.pod_attempt_uniforms(
        7,
        np.asarray([0, 3, 9], np.uint32),
        np.asarray([17, 17, 17], np.uint32),
        np.asarray([2, 2, 2], np.uint32),
    )
    assert float(single[0]) == float(batch[0][1])
    assert float(single[1]) == float(batch[1][1])


# --- fault compiler ----------------------------------------------------------


def _fault_cfg(yaml_suffix=FAULT_YAML):
    return SimulationConfig.from_yaml(
        "sim_name: t\nseed: 5\n" + yaml_suffix
    ).fault_injection


def test_inject_node_faults_chain_rules():
    GiB = 1024**3
    events = [
        (0.0, CreateNodeRequest(node=Node.new("n_a", 8000, 16 * GiB))),
        (5.0, CreateNodeRequest(node=Node.new("n_b", 8000, 16 * GiB))),
        (900.0, RemoveNodeRequest(node_name="n_b")),
    ]
    cfg = _fault_cfg()
    out = chaos.inject_node_faults(events, cfg, 5, 0, 20000.0, 10.0)
    injected = out[len(events):]
    assert injected, "mttf=2500 over a 20000s horizon must produce crashes"
    # Events come in (crash, recover) pairs, time-sorted, with ttr >= the
    # scheduling interval (window-separation clamp).
    crashes = [e for _, e in injected if isinstance(e, RemoveNodeRequest)]
    recovers = [e for _, e in injected if isinstance(e, CreateNodeRequest)]
    assert len(crashes) == len(recovers)
    assert all(e.crashed for e in crashes)
    assert all(e.recovered for e in recovers)
    assert all(e.downtime_s >= 10.0 for e in crashes)
    times = [ts for ts, _ in injected]
    assert times == sorted(times)
    # Every n_b fault pair fits strictly inside its lifetime [5, 900).
    by_node = [
        (ts, e.node_name) for ts, e in injected if isinstance(e, RemoveNodeRequest)
    ]
    for ts, name in by_node:
        if name == "n_b":
            assert 5.0 < ts < 900.0
    # Determinism: same inputs -> identical schedule; different cluster
    # index -> different schedule.
    again = chaos.inject_node_faults(events, cfg, 5, 0, 20000.0, 10.0)
    assert [(ts, type(e).__name__, getattr(e, "node_name", "")) for ts, e in out] == [
        (ts, type(e).__name__, getattr(e, "node_name", "")) for ts, e in again
    ]
    other = chaos.inject_node_faults(events, cfg, 5, 1, 20000.0, 10.0)
    assert [ts for ts, _ in out] != [ts for ts, _ in other]


def test_inject_correlated_group_faults():
    GiB = 1024**3
    events = [
        (0.0, CreateNodeRequest(node=Node.new(f"node_{i:03d}", 8000, 16 * GiB)))
        for i in range(6)
    ]
    cfg = _fault_cfg(GROUP_FAULT_YAML)
    cfg.node.mttf = 0.0  # isolate the group channel
    out = chaos.inject_node_faults(events, cfg, 5, 0, 30000.0, 10.0)
    injected = [(ts, e) for ts, e in out[len(events):]]
    crash_times = {}
    for ts, e in injected:
        if isinstance(e, RemoveNodeRequest):
            crash_times.setdefault(ts, set()).add(e.node_name)
    assert crash_times, "group mttf=3000 over 30000s must fire"
    # Blast radius: every group crash takes ALL four members down together.
    for ts, members in crash_times.items():
        assert members == {"node_000", "node_001", "node_002", "node_003"}, (
            ts,
            members,
        )


def test_overlapping_node_and_group_channels_never_double_crash():
    """The per-node and group chains are sampled independently; a group
    crash landing while a member is already down (or within one interval of
    its transitions) is dropped — never a second remove for a down node
    (which would KeyError at trace compile) or two same-slot transitions in
    one batched window."""
    GiB = 1024**3
    events = [
        (0.0, CreateNodeRequest(node=Node.new(f"n_{i}", 8000 + i * 1000, 16 * GiB)))
        for i in range(3)
    ]
    cfg = _fault_cfg(GROUP_FAULT_YAML)
    cfg.node.mttf, cfg.node.mttr = 500.0, 200.0
    cfg.failure_groups[0].members = ["n_0", "n_1"]
    cfg.failure_groups[0].mttf, cfg.failure_groups[0].mttr = 400.0, 300.0
    interval = 10.0
    for seed in range(6):  # dense chains: overlaps occur at several seeds
        out = chaos.inject_node_faults(events, cfg, seed, 0, 5000.0, interval)
        down = {}
        spans = {}
        for ts, e in out[len(events):]:
            if isinstance(e, RemoveNodeRequest):
                assert e.node_name not in down, (seed, ts, e.node_name)
                down[e.node_name] = ts
            else:
                name = e.node.metadata.name
                spans.setdefault(name, []).append((down.pop(name), ts))
        for name, ss in spans.items():
            ss.sort()
            for (_, end), (start, _) in zip(ss, ss[1:]):
                assert start >= end + interval, (seed, name, end, start)


# --- scalar vs batched equivalence under faults ------------------------------


def _run_scalar(config, seed):
    cluster_trace, workload_trace = generate_traces(seed)
    scalar = KubernetriksSimulation(config)
    scalar.initialize(cluster_trace, workload_trace)
    scalar.step_until_time(END_TIME)
    return scalar


def _build_batched(config, seed, **kwargs):
    cluster_trace, workload_trace = generate_traces(seed)
    return build_batched_from_traces(
        config,
        cluster_trace.convert_to_simulator_events(),
        workload_trace.convert_to_simulator_events(),
        n_clusters=1,
        **kwargs,
    )


@pytest.fixture
def rank_slots(request, monkeypatch):
    """The compacted queue rank's width for one test (step.RANK_COMPACT_SLOTS;
    None: as it is). The width is read when a window program is traced, so the
    programs traced under another are dropped on both sides of the test."""
    if request.param is not None:
        jax.clear_caches()
        monkeypatch.setattr(step, "RANK_COMPACT_SLOTS", request.param)
    yield request.param
    if request.param is not None:
        jax.clear_caches()


@pytest.mark.parametrize(
    "seed,fault_yaml,rank_slots",
    [
        (101, FAULT_YAML, None),
        (202, GROUP_FAULT_YAML, None),
        (101, SHORT_BACKOFF_YAML, None),
        # A failure group's crash re-queues more pods than a rank of ONE
        # slot holds: the order comes from the sort of the whole pod axis,
        # the fallback, end to end.
        (202, GROUP_FAULT_YAML, 1),
    ],
    indirect=["rank_slots"],
)
def test_fault_enabled_cross_path_equivalence(seed, fault_yaml, rank_slots):
    """The acceptance property: on a fault-enabled random trace the scalar
    and batched paths agree on every terminal counter INCLUDING the fault
    metrics, and pod-for-pod on terminal states."""
    config = default_test_simulation_config(fault_yaml)

    scalar = _run_scalar(config, seed)
    batched = _build_batched(config, seed)
    batched.step_until_time(END_TIME)

    # Which branch ordered the re-queued pods: a window in which a cluster
    # re-queued more than the slots took the sort, and only such a window.
    ranked = int(np.asarray(batched.state.metrics.resched_rank_windows).sum())
    by_sort = int(np.asarray(batched.state.metrics.resched_rank_sorted).sum())
    assert ranked > 0
    assert (by_sort > 0) == (rank_slots is not None), (ranked, by_sort)

    sm = scalar.metrics_collector.accumulated_metrics
    bm = batched.metrics_summary()["counters"]
    assert bm["pods_succeeded"] == sm.pods_succeeded
    assert bm["pods_removed"] == sm.pods_removed
    assert bm["terminated_pods"] == sm.internal.terminated_pods
    # Fault metrics: counters exact, downtime to float tolerance (f32
    # accumulation on device vs f64 on host).
    assert bm["node_crashes"] == sm.node_crashes
    assert bm["node_recoveries"] == sm.node_recoveries
    assert bm["pod_interruptions"] == sm.pod_interruptions
    assert bm["pod_restarts"] == sm.pod_restarts
    assert bm["pods_failed"] == sm.pods_failed
    assert bm["node_downtime_s"] == pytest.approx(sm.node_downtime_s, rel=1e-5)
    # The scenario actually exercises the chaos engine.
    assert sm.node_crashes > 0
    assert sm.pod_restarts > 0
    assert sm.pods_succeeded > 50

    view = batched.pod_view(0)
    succeeded = scalar.persistent_storage.succeeded_pods
    failed = scalar.persistent_storage.failed_pods
    cache = scalar.persistent_storage.unscheduled_pods_cache
    for name, b in view.items():
        if b["phase"] == PHASE_SUCCEEDED:
            pod = succeeded.get(name)
            assert pod is not None, (name, seed)
            assert b["node"] == pod.status.assigned_node, (name, seed)
            scalar_start = pod.get_condition(
                PodConditionType.POD_RUNNING
            ).last_transition_time
            assert b["start_time"] == pytest.approx(scalar_start, abs=5e-6), (
                name,
                seed,
            )
        elif b["phase"] == PHASE_FAILED:
            assert name in failed, (name, seed)
        elif b["phase"] == PHASE_UNSCHEDULABLE:
            assert name in cache, (name, seed)


def test_fault_batched_bitwise_across_donation_and_fast_forward():
    """Donation on/off and fast-forward on/off must produce bit-identical
    final states and fault metrics under faults (the composed-path
    invariants extend to the chaos subsystem)."""
    config = default_test_simulation_config(FAULT_YAML)
    variants = [
        _build_batched(config, 101, donate=False, fast_forward=False),
        _build_batched(config, 101, donate=True, fast_forward=False),
        _build_batched(config, 101, donate=False, fast_forward=True),
    ]
    for sim in variants:
        sim.step_until_time(END_TIME)
    ref = variants[0]
    assert int(np.asarray(ref.state.metrics.node_crashes).sum()) > 0
    for other in variants[1:]:
        bad = compare_states(ref.state, other.state)
        assert bad == [], bad


def test_fault_seed_determinism():
    """Two identically-seeded fault runs are bit-identical; changing only
    the fault seed changes the trajectory."""
    config = default_test_simulation_config(FAULT_YAML)
    a = _build_batched(config, 101)
    b = _build_batched(config, 101)
    a.step_until_time(END_TIME)
    b.step_until_time(END_TIME)
    assert compare_states(a.state, b.state) == []

    config2 = default_test_simulation_config(
        FAULT_YAML.replace("enabled: true", "enabled: true\n  seed: 999")
    )
    c = _build_batched(config2, 101)
    c.step_until_time(END_TIME)
    assert compare_states(a.state, c.state) != []


def test_faults_off_state_is_pristine():
    """With fault_injection absent the fault fields stay inert zeros and
    the engine threads fault_params=None (identical compiled programs)."""
    config = default_test_simulation_config()
    sim = _build_batched(config, 101)
    assert sim.fault_params is None
    sim.step_until_time(END_TIME)
    m = sim.metrics_summary()["counters"]
    assert m["node_crashes"] == 0
    assert m["pod_restarts"] == 0
    assert m["pods_failed"] == 0
    assert m["node_downtime_s"] == 0.0
    assert not np.asarray(sim.state.pods.will_fail).any()
    assert not np.asarray(sim.state.pods.restarts).any()


def test_debug_finite_guard_names_offending_field():
    """KTPU_DEBUG_FINITE guard mode: a clean fault run passes the sweep; an
    injected NaN fails naming the field."""
    config = default_test_simulation_config(FAULT_YAML)
    sim = _build_batched(config, 101)
    sim._debug_finite = True
    sim.step_until_time(2000.0)  # sweeps after every dispatched chunk

    import jax.numpy as jnp

    est = sim.state.metrics.queue_time
    sim.state = sim.state._replace(
        metrics=sim.state.metrics._replace(
            queue_time=est._replace(total=est.total.at[0].set(jnp.nan))
        )
    )
    with pytest.raises(FloatingPointError, match="queue_time"):
        sim._check_finite()


@pytest.mark.parametrize("distribution", ["exponential", "fixed"])
def test_batched_chain_compilation_matches_loop(distribution):
    """inject_node_faults samples its crash/recover chains through the
    VECTORIZED _chains_batched (one threefry block per incarnation index for
    every lifetime at once); every chain must be bit-identical — same
    float64 values, same pair order — to the sequential per-lifetime _chain
    loop it replaced, across finite/infinite lifetimes, horizon cutoffs and
    the interval clamp."""
    rng = np.random.default_rng(42)
    produced = False
    for trial in range(8):
        U = int(rng.integers(1, 30))
        uids = list(range(U))
        t0s = [float(rng.uniform(0.0, 400.0)) for _ in range(U)]
        # Mix never-removed (inf) and trace-removed lifetimes, including
        # some too short to ever crash.
        ends = [
            float(np.inf)
            if rng.random() < 0.3
            else t0 + float(rng.uniform(5.0, 2500.0))
            for t0 in t0s
        ]
        horizon = float(rng.uniform(50.0, 3000.0))
        # Small mttf/mttr exercise the one-interval clamp lanes.
        mttf = float(rng.uniform(2.0, 800.0))
        mttr = float(rng.uniform(1.0, 200.0))
        seed = int(rng.integers(0, 10_000))
        cluster = int(rng.integers(0, 16))
        batched = chaos._chains_batched(
            seed, chaos.STREAM_NODE, cluster, uids, t0s, ends,
            horizon, mttf, mttr, distribution, 10.0,
        )
        loop = [
            chaos._chain(
                seed, chaos.STREAM_NODE, cluster, uid, t0s[i], ends[i],
                horizon, mttf, mttr, distribution, 10.0,
            )
            for i, uid in enumerate(uids)
        ]
        assert batched == loop, trial
        produced = produced or any(len(c) for c in batched)
    # The scenarios above must actually produce chains somewhere, or the
    # parity claim is vacuous.
    assert produced


def test_batched_chain_compilation_empty_inputs():
    assert chaos._chains_batched(
        1, chaos.STREAM_NODE, 0, [], [], [], 100.0, 10.0, 5.0,
        "exponential", 10.0,
    ) == []


# --- recovery on identical nodes ----------------------------------------------

EQUAL_NODES_FAULT_YAML = """
fault_injection:
  enabled: true
  node:
    mttf: 3000.0
    mttr: 60.0
  failure_groups:
  - members: [node_000, node_001, node_002, node_003]
    mttf: 1500.0
    mttr: 90.0
"""


def _equal_capacity_events(seed, n_nodes=12, n_pods=160):
    """IDENTICAL nodes under zero-padded names, small pods: nearly every
    placement is an exact score tie between empty nodes, broken by the last
    name in sorted order on the scalar path and by the highest slot on the
    batched one."""
    rng = np.random.default_rng(seed)
    cluster = [
        (0.0, CreateNodeRequest(node=Node.new(f"node_{i:03d}", 16000, 32 * 1024**3)))
        for i in range(n_nodes)
    ]
    from kubernetriks_tpu.core.events import CreatePodRequest
    from kubernetriks_tpu.core.types import Pod

    times = np.sort(np.round(rng.uniform(1.0, 1400.0, n_pods), 3))
    workload = [
        (
            float(t),
            CreatePodRequest(
                pod=Pod.new(f"pod_{i:04d}", 2000, 4 * 1024**3, float(np.round(rng.uniform(30.0, 200.0), 3)))
            ),
        )
        for i, t in enumerate(times)
    ]
    return cluster, workload


@pytest.mark.parametrize("seed", [11, 12])
def test_recovery_on_equal_capacity_nodes_keeps_the_scalar_tie_break(seed):
    """The case the fault suites dodged with heterogeneous capacities: a
    recovered node returns to ITS OWN slot (trace_compile), so slot order
    stays sorted-name order through crash and recovery and equal-capacity
    nodes tie-break as the scalar walk does, pod for pod; the node axis is
    the trace's node count whatever the schedule."""
    from kubernetriks_tpu.trace.interface import Trace

    class _Events(Trace):
        def __init__(self, events):
            self._events = events

        def convert_to_simulator_events(self):
            return self._events

        def event_count(self):
            return len(self._events)

    config = default_test_simulation_config(EQUAL_NODES_FAULT_YAML)
    cluster, workload = _equal_capacity_events(seed)
    scalar = KubernetriksSimulation(config)
    scalar.initialize(_Events(cluster), _Events(workload))
    scalar.step_until_time(END_TIME)
    batched = build_batched_from_traces(config, cluster, workload, n_clusters=1)
    assert batched.n_nodes == 12
    batched.step_until_time(END_TIME)

    sm = scalar.metrics_collector.accumulated_metrics
    bm = batched.metrics_summary()["counters"]
    assert sm.node_crashes >= 8 and sm.node_recoveries >= 8 and sm.pod_interruptions > 0
    for name in ("node_crashes", "node_recoveries", "pod_interruptions"):
        assert bm[name] == getattr(sm, name), name
    assert bm["node_downtime_s"] == pytest.approx(sm.node_downtime_s, rel=1e-5)
    assert bm["pods_succeeded"] == sm.pods_succeeded == len(workload)
    succeeded = scalar.persistent_storage.succeeded_pods
    for name, b in batched.pod_view(0).items():
        pod = succeeded[name]
        assert b["phase"] == PHASE_SUCCEEDED and b["node"] == pod.status.assigned_node, (name, seed)
        start = pod.get_condition(PodConditionType.POD_RUNNING).last_transition_time
        assert b["start_time"] == pytest.approx(start, abs=5e-6), (name, seed)
