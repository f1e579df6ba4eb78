"""Node ranking where requests are heterogeneous (PR 28): node slots count as
names sort, and the default profile ranks by an exact fixed-point key where
float32 scores cannot tell two nodes apart that the scalar path's float64
can."""

import json
import os

import numpy as np
import pytest

import jax
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


POOL_SHAPES = [(64000, 128 * 1024), (64000, 256 * 1024), (96000, 192 * 1024), (32000, 64 * 1024)]  # millicores, MiB
POOL_REQUESTS = [(500, 1024), (1000, 4096), (2000, 4096), (4000, 8192), (8000, 32768), (16000, 32768),
                 (4000, 49152), (8000, 98304), (8000, 16384)]  # benchmark/traffic/montecarlo-pools.json
REPLAY_SHAPE = (64000, 88 * 1024)


def _assert_long_division(digits, num, den, bits):
    """`digits` are what _quotient_digits promises, worked out in integers
    (int64 holds them)."""
    rem = num.astype(np.int64)
    for place, digit in enumerate(digits):
        want, rem = np.divmod(rem << bits, den)
        assert np.array_equal(np.asarray(digit), want), (bits, place)
    assert len(digits) == pipeline._EXACT_DIGITS


def _division_pairs(bits):
    """(num, den) with 0 <= num <= den < 2**(31 - bits): random ones, the
    edges, and what the pools cell and the replay divide (a request by what
    is left of a machine after j other requests)."""
    rng = np.random.default_rng(bits)
    top = 2 ** (31 - bits) - 1
    den = np.concatenate([
        rng.integers(1, top + 1, 120_000),
        (2.0 ** rng.uniform(0, 31 - bits, 60_000)).astype(np.int64).clip(1, top),
        np.repeat([1, 2, 3, top - 1, top], 2_000),
    ])
    num = rng.integers(0, den + 1)
    num[:: 7] = den[:: 7]  # a pod that fills its node
    num[1 :: 7] = 0
    num[2 :: 7] = den[2 :: 7] - 1
    machines = POOL_SHAPES + [REPLAY_SHAPE]
    nums, dens = [num], [den]
    for cap in sorted({c for shape in machines for c in shape}):
        for held in sorted({r for req in POOL_REQUESTS for r in req} | {10, 64, 1160, 2955, 4095}):
            left = cap - held * np.arange(0, cap // held + 1)
            left = left[(left > 0) & (left <= top)]
            for asked in (held, 500, 1024, 4170, 1690, cap):
                keep = left[left >= asked]
                nums.append(np.full(keep.shape, asked))
                dens.append(keep)
    return np.concatenate(nums), np.concatenate(dens)


@pytest.mark.parametrize("bits", [10, 12, 14])
def test_quotient_digits_are_the_long_divisions(bits):
    num, den = _division_pairs(bits)
    got = pipeline._quotient_digits(jnp.asarray(num, jnp.int32), jnp.asarray(den, jnp.int32), bits)
    _assert_long_division(got, num, den, bits)
    assert int(np.asarray(got[0]).max()) == 1 << bits  # num == den was among them


@pytest.mark.parametrize("ulps", [-32, -5, -1, 1, 5, 32])
@pytest.mark.parametrize("bits", [10, 12, 14])
def test_the_digits_hold_with_a_reciprocal_some_ulp_off(bits, ulps):
    """The one-sided correction does not lean on how a backend's division
    rounds: the digit loop handed a reciprocal up to 32 ulp either way."""
    num, den = _division_pairs(bits)
    inv = np.float32(pipeline._ESTIMATE_BIAS * 2.0**bits) / den.astype(np.float32)
    inv = (inv.view(np.int32) + np.int32(ulps)).view(np.float32)
    got = pipeline._digits_by_reciprocal(
        jnp.asarray(num, jnp.int32), jnp.asarray(den, jnp.int32), jnp.asarray(inv), bits
    )
    _assert_long_division(got, num, den, bits)


def test_the_key_divides_once_a_denominator():
    """What the kernels trace of the key at the megakernel's node tile: one
    float32 division a denominator and no floor."""
    plane = jax.ShapeDtypeStruct((1024, 128), jnp.int32)
    row = jax.ShapeDtypeStruct((1, 128), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda fit, cpu, ram, rc, rr: pipeline.exact_least_allocated_key(fit, cpu, ram, rc, rr, 12))(
        jax.ShapeDtypeStruct((1024, 128), jnp.bool_), plane, plane, row, row
    )
    names = [eqn.primitive.name for eqn in jaxpr.jaxpr.eqns]
    assert names.count("div") == 2 and "floor" not in names


def _frozen_key_cases():
    with open(os.path.join(os.path.dirname(__file__), "data", "exact_key_parent.json")) as fh:
        return json.load(fh)["cases"]


@pytest.mark.parametrize("case", _frozen_key_cases(), ids=lambda case: f"bits{case['bits']}")
def test_the_key_is_word_for_word_what_it_was(case):
    """(hi, lo) against tests/data/exact_key_parent.json, which PR 47's tree
    wrote with a float32 division a digit and a two-sided correction; and
    both against the key in Python integers."""
    bits = case["bits"]
    alive = np.array(case["alive"], bool)
    cpu, ram = np.array(case["cpu"]), np.array(case["ram"])
    key = jax.jit(pipeline.exact_least_allocated_key, static_argnums=5)
    for (rc, rr), hi_was, lo_was in zip(case["requests"], case["hi"], case["lo"]):
        fit = alive & (rc <= cpu) & (rr <= ram)
        args = (
            jnp.asarray(fit)[None, :], jnp.asarray(cpu, jnp.int32)[None, :], jnp.asarray(ram, jnp.int32)[None, :],
            jnp.int32(rc).reshape(1, 1), jnp.int32(rr).reshape(1, 1), bits,
        )
        for hi, lo in (pipeline.exact_least_allocated_key(*args), key(*args)):
            assert np.asarray(hi)[0].tolist() == hi_was and np.asarray(lo)[0].tolist() == lo_was
        ok = fit & (cpu > 0) & (ram > 0)
        total = [(rc << 3 * bits) // int(c) + (rr << 3 * bits) // int(r) for c, r in zip(cpu[ok], ram[ok])]
        assert [t >> bits for t in total] == np.array(hi_was)[ok].tolist()
        assert [t & ((1 << bits) - 1) for t in total] == np.array(lo_was)[ok].tolist()
        assert (np.array(hi_was)[~ok] == 2**31 - 1).all() and (np.array(lo_was)[~ok] == 2**31 - 1).all()


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
