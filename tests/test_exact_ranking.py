"""Node ranking where requests are heterogeneous (PR 28): node slots count as
names sort, and the default profile ranks by an exact fixed-point key where
float32 scores cannot tell two nodes apart that the scalar path's float64
can."""

import numpy as np
import pytest

import jax.numpy as jnp

from kubernetriks_tpu.batched import pipeline
from kubernetriks_tpu.batched.pipeline import DEFAULT_PROFILE
from kubernetriks_tpu.batched.state import EV_CREATE_NODE, EV_REMOVE_NODE
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.core.events import CreateNodeRequest, CreatePodRequest, RemoveNodeRequest
from kubernetriks_tpu.core.types import Node, Pod

GIB = 1024**3


def _float64_best(alive, cpu, ram, rc, rr):
    """The scalar scheduler's choice: float64 LeastAllocated, last max wins."""
    fit = alive & (rc <= cpu) & (rr <= ram) & (cpu > 0) & (ram > 0)
    c, r = cpu.astype(np.float64), ram.astype(np.float64)
    with np.errstate(divide="ignore", invalid="ignore"):
        score = ((c - rc) * 100.0 / c + (r - rr) * 100.0 / r) / 2.0
    score = np.where(fit, score, -np.inf)
    return len(score) - 1 - int(np.argmax(score[::-1]))


def _exact_best(alive, cpu, ram, rc, rr, bits=14):
    fit = jnp.asarray(alive & (rc <= cpu) & (rr <= ram))
    cpu, ram = jnp.asarray(cpu, jnp.int32)[None, :], jnp.asarray(ram, jnp.int32)[None, :]
    hi, lo = pipeline.exact_least_allocated_key(
        fit[None, :], cpu, ram, jnp.int32(rc).reshape(1, 1), jnp.int32(rr).reshape(1, 1), bits
    )
    iota = jnp.arange(cpu.shape[1], dtype=jnp.int32)[None, :]
    return int(pipeline.exact_best_node(hi, lo, True, iota, axis=1)[0, 0])


def test_node_slots_count_as_names_sort():
    names = [f"alibaba_node_{i}" for i in range(1, 13)]  # _10 sorts before _2
    cluster = [(0.0, CreateNodeRequest(node=Node.new(n, 1000 * (i + 1), GIB))) for i, n in enumerate(names)]
    cluster.append((50.0, RemoveNodeRequest(node_name="alibaba_node_3")))
    workload = [(5.0, CreatePodRequest(pod=Pod.new("p", 100, 1024**2, 10.0)))]
    compiled = compile_cluster_trace(cluster, workload)
    assert compiled.node_names == sorted(names)
    by_name = dict(zip(compiled.node_names, compiled.node_cap_cpu))
    assert all(by_name[n] == 1000 * (i + 1) for i, n in enumerate(names))
    creates = compiled.ev_slot[compiled.ev_kind == EV_CREATE_NODE]
    assert sorted(creates) == list(range(12)) and compiled.node_names[creates[1]] == "alibaba_node_2"
    removed = compiled.ev_slot[compiled.ev_kind == EV_REMOVE_NODE]
    assert [compiled.node_names[s] for s in removed] == ["alibaba_node_3"]
    # names that sort as they were created keep their slots: the same object comes back
    padded = [(0.0, CreateNodeRequest(node=Node.new(f"n_{i:02d}", 1000, GIB))) for i in range(12)]
    assert compile_cluster_trace(padded, workload).node_names == [f"n_{i:02d}" for i in range(12)]


def _float32_best(alive, cpu, ram, rc, rr):
    _, score = pipeline.profile_fit_score(
        DEFAULT_PROFILE, jnp.asarray(alive), jnp.asarray(cpu, jnp.int32), jnp.asarray(ram, jnp.int32),
        jnp.int32(rc), jnp.int32(rr),
    )
    score = np.asarray(score)
    return len(score) - 1 - int(np.argmax(score[::-1]))


def test_the_exact_key_ranks_as_float64_does():
    rng = np.random.default_rng(28)
    n = 1313
    cap_cpu, cap_ram = 64000, 88 * 1024
    for trial in range(40):
        used_cpu = rng.integers(0, 40000, n) // 10 * 10
        used_ram = rng.integers(0, 60000, n)
        cpu, ram = cap_cpu - used_cpu, cap_ram - used_ram
        if trial % 4 == 0:
            cpu[rng.integers(0, n, 5)] = 0  # nothing allocatable: never chosen
            cpu[-3:], ram[-3:] = cpu[7], ram[7]  # equal nodes tie: the highest slot wins
        alive = rng.random(n) > 0.02
        rc, rr = int(rng.integers(50, 6400)) * 10, int(rng.integers(64, 4096))
        assert _exact_best(alive, cpu, ram, rc, rr) == _float64_best(alive, cpu, ram, rc, rr)


@pytest.mark.parametrize(
    "cpu,ram,rc,rr",
    [
        # float64 puts node 0 ahead by 1.1e-7 and 2.5e-6 of a score near 96; float32 rounds both
        # nodes to one number and its last-max-wins takes node 1
        ((36650, 42660), (90008, 79245), 1160, 2955),
        ((45070, 43970), (80031, 89883), 4170, 1690),
    ],
)
def test_a_near_tie_float32_cannot_see(cpu, ram, rc, rr):
    cpu, ram, alive = np.array(cpu), np.array(ram), np.array([True, True])
    assert _float64_best(alive, cpu, ram, rc, rr) == 0
    assert _float32_best(alive, cpu, ram, rc, rr) == 1
    for bits in (14, 12, 10):
        assert _exact_best(alive, cpu, ram, rc, rr, bits) == 0


def test_which_builds_rank_exactly():
    bits = pipeline.exact_score_bits
    lockstep_pods = [(np.array([4000, 4000, 0]), np.array([8192, 8192, 0]))]
    lockstep_nodes = [(np.full(10, 64000), np.full(10, 131072))]
    assert bits(DEFAULT_PROFILE, lockstep_pods, lockstep_nodes) == 0
    two_sizes = [(np.array([16000, 8000]), np.array([32768, 16384]))]  # the autoscaled deployment's
    assert bits(DEFAULT_PROFILE, two_sizes, lockstep_nodes) == 0
    replay_pods = [(np.array([500, 4000, 64000]), np.array([64, 1000, 4095]))]
    replay_nodes = [(np.full(1313, 64000), np.full(1313, 90112))]
    assert bits(DEFAULT_PROFILE, replay_pods, replay_nodes) == 14
    assert bits(DEFAULT_PROFILE, replay_pods, [(np.full(3, 512000), np.full(3, 90112))]) == 12
    assert bits(DEFAULT_PROFILE, [(np.zeros(3, int), np.zeros(3, int))], replay_nodes) == 0  # nothing is asked
    assert bits(DEFAULT_PROFILE, [], []) == 0


@pytest.mark.parametrize("name", ["default", "topology_spread", "node_pools"])
def test_the_key_goes_by_the_scores_not_the_filters(name, caplog):
    """A profile whose scores are LeastAllocatedResources at weight 1 gets the
    exact key over mixed capacities whatever its filters, and says nothing;
    the key ranks the nodes the chain lets through."""
    pools_pods = [(np.array([500, 4000, 8000]), np.array([1024, 49152, 98304]))]
    pools_nodes = [(np.array([64000, 64000, 96000, 32000]), np.array([131072, 262144, 196608, 65536]))]
    profile = pipeline.compile_profile(name)
    with caplog.at_level("WARNING", logger=pipeline.__name__):
        assert pipeline.exact_score_bits(profile, pools_pods, pools_nodes) == 12
    assert not caplog.records


def test_a_build_that_needs_the_exact_key_and_cannot_have_it_says_so(caplog):
    bits = pipeline.exact_score_bits
    replay_pods = [(np.array([500, 4000, 64000]), np.array([64, 1000, 4095]))]
    replay_nodes = [(np.full(1313, 64000), np.full(1313, 90112))]
    lockstep_pods = [(np.array([4000, 4000, 0]), np.array([8192, 8192, 0]))]
    lockstep_nodes = [(np.full(10, 64000), np.full(10, 131072))]
    packed = pipeline.compile_profile("best_fit")
    with caplog.at_level("WARNING", logger=pipeline.__name__):
        assert bits(DEFAULT_PROFILE, replay_pods, replay_nodes) == 14
        assert bits(packed, lockstep_pods, lockstep_nodes) == 0  # float32 decides as float64 there
        assert not caplog.records
        assert bits(packed, replay_pods, replay_nodes) == 0  # only the default profile has the key
        assert "profile 'best_fit' has no exact key" in caplog.records[-1].getMessage()
        huge = [(np.full(3, 2**22), np.full(3, 90112))]  # 2**22 millicores leave 8 bits a digit
        assert bits(DEFAULT_PROFILE, replay_pods, huge) == 0
        assert "leaves 8 bits a digit" in caplog.records[-1].getMessage() and len(caplog.records) == 2


def test_float32_ranks_a_lockstep_trace_as_float64_up_to_the_gates_width():
    """`_LOCKSTEP_MAX_UNITS`: requests of k units on nodes with A free units
    score 100 - 100 k / A. Up to the gate's A the float32 score the kernels
    compute orders every pair of neighbouring A as float64 does, for every k;
    the gate is not slack by much: float32 first ties two neighbours under four
    times its width."""
    score = pipeline.DEVICE_SCORE_PLUGINS[pipeline.LEAST_ALLOCATED]
    unit_cpu, unit_ram = 250, 512

    def neighbours_ordered(width, ks):
        """Whether score(A + 1) > score(A) in float32 for every A in [k, width) of every k."""
        free = np.arange(1, width + 1, dtype=np.int32)[None, :]
        k = np.asarray(ks, np.int32)[:, None]
        got = np.asarray(score(jnp.asarray(free * unit_cpu), jnp.asarray(free * unit_ram),
                               jnp.asarray(k * unit_cpu), jnp.asarray(k * unit_ram)))
        assert got.dtype == np.float32 and got.shape == (len(ks), width)
        return bool((np.diff(got, axis=1) > 0)[free[:, :-1] >= k].all())

    width = pipeline._LOCKSTEP_MAX_UNITS
    assert neighbours_ordered(width, range(1, width))
    assert not neighbours_ordered(4 * width, [1])
    # the gate itself: one unit more than the width is no longer taken on trust
    pods = [(np.array([unit_cpu]), np.array([unit_ram]))]
    nodes = lambda units: [(np.array([units * unit_cpu]), np.array([units * unit_ram]))]  # noqa: E731
    assert pipeline.exact_score_bits(DEFAULT_PROFILE, pods, nodes(width)) == 0
    assert pipeline.exact_score_bits(DEFAULT_PROFILE, pods, nodes(width + 1)) > 0


@pytest.mark.parametrize("bits", [0, 14])
def test_the_candidate_kernel_ranks_as_the_scalar_scheduler(bits):
    """fused_schedule_cycle (interpreted) over a heterogeneous batch: with the
    exact key every decision is float64's; allocatables are updated as it goes."""
    from kubernetriks_tpu.ops.scheduler_kernel import fused_schedule_cycle

    rng = np.random.default_rng(5)
    C, N, K = 2, 40, 6
    alive = rng.random((C, N)) > 0.1
    cpu = (64000 - rng.integers(0, 30000, (C, N)) // 10 * 10).astype(np.int32)
    ram = (90112 - rng.integers(0, 50000, (C, N))).astype(np.int32)
    valid = np.ones((C, K), bool)
    rc = (rng.integers(50, 800, (C, K)) * 10).astype(np.int32)
    rr = rng.integers(64, 4096, (C, K)).astype(np.int32)
    assign, _, best, cpu_out, ram_out = fused_schedule_cycle(
        jnp.asarray(alive), jnp.asarray(cpu), jnp.asarray(ram), jnp.asarray(valid), jnp.asarray(rc),
        jnp.asarray(rr), interpret=True, profile=DEFAULT_PROFILE._replace(exact_bits=bits),
    )
    assert np.asarray(assign).all()
    if not bits:
        return  # float32 ranking is the historical kernel; it only has to run
    for c in range(C):
        cpu_c, ram_c = cpu[c].copy(), ram[c].copy()
        for k in range(K):
            want = _float64_best(alive[c], cpu_c, ram_c, int(rc[c, k]), int(rr[c, k]))
            assert int(best[c, k]) == want
            cpu_c[want] -= rc[c, k]
            ram_c[want] -= rr[c, k]
        assert (np.asarray(cpu_out)[c] == cpu_c).all() and (np.asarray(ram_out)[c] == ram_c).all()


def test_alibaba_replay_lands_every_pod_on_the_scalar_paths_node(tmp_path):
    """The normal path over Alibaba-format files (bare machine ids, 0.5-64
    core pods) against the program's own scalar simulator, pod for pod; and
    what the recorder says of the ingestion."""
    from kubernetriks_tpu.cli import build_batched_simulation
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
    from kubernetriks_tpu.telemetry.tracer import PHASE_NAMES, recorder
    from kubernetriks_tpu.trace.alibaba import AlibabaClusterTraceV2017, AlibabaWorkloadTraceV2017
    from kubernetriks_tpu.trace.synthetic_alibaba import write_synthetic_trace_dir

    machines, tasks, instances = write_synthetic_trace_dir(
        str(tmp_path), n_machines=60, n_tasks=400, horizon=2500.0, seed=28
    )
    delays = "".join(
        f"{edge}_network_delay: 0.0\n"
        for edge in ("as_to_ps", "ps_to_sched", "sched_to_as", "as_to_node", "as_to_ca", "as_to_hpa")
    )
    config = SimulationConfig.from_yaml(
        "sim_name: t\nseed: 1\nscheduling_cycle_interval: 10.0\n" + delays
        + "trace_config:\n  alibaba_cluster_trace_v2017:\n"
        f"    machine_events_trace_path: {machines}\n    batch_task_trace_path: {tasks}\n"
        f"    batch_instance_trace_path: {instances}\n"
    )
    rec = recorder()
    before = dict(rec.counters)
    spans_before = len(rec.rows())
    batched = build_batched_simulation(config, n_clusters=1)
    rows = rec.rows()[spans_before:]
    assert (rows[:, 2] == PHASE_NAMES.index("trace_ingest")).sum() == 1
    pods = batched.n_real_pods
    assert rec.counters["trace_ingest_rows"] - before.get("trace_ingest_rows", 0) == pods
    assert rec.counters["trace_ingest_rows_dropped"] == before.get("trace_ingest_rows_dropped", 0)
    assert batched.kernel_formulation()["ranking"] == "exact"
    assert batched.node_names[0] == sorted(batched.node_names[0]) and "alibaba_node_10" in batched.node_names[0]

    end = 2500.0 * 0.8 + 60 + 2400 + 100
    batched.step_until_time(end)
    scalar = KubernetriksSimulation(config)
    scalar.initialize(
        AlibabaClusterTraceV2017.from_file(machines), AlibabaWorkloadTraceV2017.from_files(instances, tasks)
    )
    scalar.step_until_time(end)
    theirs = {name: pod.status.assigned_node for name, pod in scalar.persistent_storage.succeeded_pods.items()}
    ours = {name: row["node"] for name, row in batched.pod_view(0).items()}
    assert len(theirs) == pods > 700 and ours == theirs
