"""The megakernel's launch chooses its lanes by depth (step._launch_by_depth,
PR 45): a cluster whose queue is deeper than one pass sits the first launch
out and is drained by a second launch over the batch's deep clusters brought
together into one lane tile. Held here:

(a) the state after a run of windows equals, LEAF FOR LEAF, the state of the
    same run with one launch of the wrapper a cycle (the test's own: it
    stands in for step._launch_by_depth, the program has no switch), for no
    deep cluster, one (alone it stays: its tile's steps would only move),
    several over three tiles, exactly a tile's worth, one more than that
    (nothing moves), two bursts in one cycle, both node layouts, the spread
    filter, and passes of 4 and 64; the toy bursts are tens of steps deep, so
    the runs price the selection at nothing (step.CYCLE_COMPACT_PAYS = 0:
    whatever saves a step moves);
(b) cycle_deep / cycle_compacted read the counts the traces were built to,
    and step._lanes_to_move at its own price moves a backlog's bursts and
    leaves alone a lone burst, a few lanes just past a pass, and more deep
    clusters than a tile holds;
(c) where the second launch sits in the lowered program: inside the taken
    arm of a `cond` and nowhere else (the other arm is the single launch),
    and in no program of one tile; under
    a mesh each shard takes its own branch and the program holds no
    collective;
(d) the lane selection alone carries every int32, float32 (nan, inf, -0.0)
    and bool bit for bit, there and back;
(e) the operands the launch is handed are whole lane tiles: the wrapper's own
    pad of the cluster axis moves nothing.
"""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched import step
from kubernetriks_tpu.batched.engine import BatchedSimulation
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.ops import scheduler_kernel
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from spread_traces import ZONE_KEY
from test_cycle_drain import leaves_differing
from test_pending_free import config_with, node_event, pod_event
from tests.sharded_builds import mesh_of

C = 300  # three lane tiles
R = scheduler_kernel._LANE
END = 70.0
N_NODES = 12


def cluster_events(labelled):
    events = [node_event(0.0, f"node_{i:03d}", cpu=16000, ram_gib=32) for i in range(N_NODES)]
    if labelled:
        for i, event in enumerate(events):
            event["event_type"]["node"]["metadata"]["labels"] = {ZONE_KEY: f"zone-{i % 3}"}
    return events


def workload_events(kind, seed, burst, labelled):
    """Two pods a cycle in the background (never deeper than a pass of 4),
    and by `kind`: nothing more ("shallow"), `burst` pods at one instant in
    the cycle of window 3 ("early") or 5 ("late"), or two bursts of half
    that in one cycle ("double"). Every kind is padded to the same number of
    pods with arrivals after the run's end, so that every case of a pass
    size shares one program."""
    rng = np.random.default_rng(seed)
    times = [10.0 * i + off for i in range(6) for off in (3.0, 7.0)]
    at = {"shallow": [], "early": [(25.0, burst)], "late": [(45.0, burst)],
          "double": [(23.0, burst // 2), (27.0, burst - burst // 2)]}[kind]
    for t, n in at:
        times += [t] * n
    times += [END + 100.0] * (12 + burst - len(times))
    workload = []
    for i, t in enumerate(sorted(times)):
        event = pod_event(t, f"pod_{i:05d}", np.round(rng.uniform(20.0, 60.0), 3), 4000, 8)
        w = int(rng.integers(3))
        if labelled and w < 2:
            pod = event["event_type"]["pod"]
            pod["metadata"]["labels"] = {"color": f"c{w}"}
            pod["spec"]["topology_spread_constraints"] = [
                {
                    "max_skew": 1,
                    "topology_key": ZONE_KEY,
                    "when_unsatisfiable": "DoNotSchedule",
                    "label_selector": {"match_labels": {"color": f"c{w}"}},
                }
            ]
        workload.append(event)
    return workload


def spread_over_tiles(n):
    """n clusters of the batch, over all three tiles."""
    return [int(c) for c in np.linspace(1, C - 2, n).round()]


# name: (deep clusters {cluster: kind}, K, burst, lane_major, spread filter,
# the deep clusters no second launch drains). "early" and "double" fall in
# one cycle, "late" in another; a cluster deep ALONE in its cycle stays (its
# tile's steps would only move to the second launch), and so does everyone
# where a tile cannot hold them.
# Three programs in all (a compile each, twice): lane-major nodes at a pass
# of 4, and row-major nodes at a pass of 64 with and without the filter.
CASES = {
    "none": ({}, 4, 16, True, False, ()),
    "one-alone-stays": ({137: "early"}, 4, 16, True, False, (137,)),
    "several-over-three-tiles": (
        {3: "early", 127: "late", 128: "early", 131: "double", 200: "late", 256: "early", 299: "late"},
        4, 16, True, False, (),
    ),
    "exactly-a-tile": ({c: "early" for c in spread_over_tiles(R)}, 4, 16, True, False, ()),
    "one-more-than-a-tile": (
        {c: "early" for c in spread_over_tiles(R + 1)}, 4, 16, True, False, spread_over_tiles(R + 1),
    ),
    "two-bursts-in-one-cycle": ({77: "double", 210: "double"}, 4, 16, True, False, ()),
    "row-major-nodes": ({3: "early", 131: "double", 299: "late"}, 64, 80, False, False, (299,)),
    "spread-filter": ({3: "early", 131: "double", 299: "late", 140: "late"}, 64, 80, False, True, ()),
    "pass-of-64": ({5: "early", 129: "late", 130: "double", 290: "early"}, 64, 80, False, False, (129,)),
}


def single_launch(
    launch, nodes, eligible, pod_planes, pod_time, spread, n_eligible, K, lane_major, affinity=None, kube=None
):
    """The comparison: the wrapper called once on the whole batch."""
    return launch(nodes, eligible, pod_planes, pod_time, spread, affinity, kube), jnp.zeros(
        eligible.shape[:1], jnp.bool_
    )


def run(case, **kwargs):
    deep, K, burst, lane_major, labelled, _ = CASES[case]
    config = config_with("zero", "scheduler_profile: topology_spread\n" if labelled else "")
    nodes = GenericClusterTrace(events=cluster_events(labelled)).convert_to_simulator_events()
    compiled = {
        kind: compile_cluster_trace(
            nodes,
            GenericWorkloadTrace(
                events=workload_events(kind, seed, burst, labelled)
            ).convert_to_simulator_events(),
            config,
        )
        for seed, kind in enumerate(["shallow", *sorted(set(deep.values()))])
    }
    sim = BatchedSimulation(
        config,
        [compiled[deep.get(c, "shallow")] for c in range(C)],
        use_pallas=True,
        pallas_interpret=True,
        max_pods_per_cycle=K,
        lane_major=lane_major,
        **kwargs,
    )
    assert sim.kernel_formulation()["cycle"] == "megakernel"
    sim.step_until_time(END)
    return sim


@contextlib.contextmanager
def traced_with(**patched):
    """Globals of `step` replaced while programs are traced. The window
    programs read them when they are TRACED, so jax's caches are dropped
    before and after."""
    jax.clear_caches()
    with pytest.MonkeyPatch.context() as patch:
        for name, value in patched.items():
            patch.setattr(step, name, value)
        yield
    jax.clear_caches()


@pytest.fixture(scope="module")
def runs():
    """Every case run as the program runs it, the selection priced at nothing,
    then again with the single launch in _launch_by_depth's place."""
    with traced_with(CYCLE_COMPACT_PAYS=0):
        split = {case: run(case) for case in CASES}
    with traced_with(_launch_by_depth=single_launch):
        single = {case: run(case) for case in CASES}
    return split, single


@pytest.mark.parametrize("case", list(CASES))
def test_split_launch_leaves_the_single_launchs_state(runs, case):
    split, single = (sims[case] for sims in runs)
    deep, K, burst, _, labelled, stays = CASES[case]
    assert (split.state.spread is not None) == labelled
    assert leaves_differing(split.state, single.state, skip=("cycle_compacted",)) == []
    # The traffic did what the case says: each deep cluster drained its
    # burst, several passes deep, in ONE cycle, and nobody else was deep.
    m = jax.tree.map(np.asarray, split.state.metrics)
    expected = np.zeros(C, np.int32)
    expected[list(deep)] = 1
    np.testing.assert_array_equal(m.cycle_deep, expected)
    if deep:
        assert int(m.cycle_deepest[list(deep)].min()) >= burst > K
    expected[list(stays)] = 0
    np.testing.assert_array_equal(m.cycle_compacted, expected)
    assert not np.asarray(single.state.metrics.cycle_compacted).any()
    split.metrics_summary()
    counted = split.telemetry_report()["counters"]
    assert counted["cycle_deep"] == len(deep)
    assert counted["cycle_compacted"] == len(deep) - len(stays)


def depths(deep, shallow=20, n=1250, seed=45):
    """A batch's queue depths: Poisson(`shallow`) everywhere but `deep`
    {cluster: depth}."""
    n_eligible = np.random.default_rng(seed).poisson(shallow, n).astype(np.int32)
    n_eligible[list(deep)] = list(deep.values())
    return n_eligible


@pytest.mark.parametrize(
    "n_eligible, moves",
    [
        # sched1k-backlog.bursts: some thirty thousand-pod bursts over ten tiles.
        (depths({int(c): 1000 for c in np.linspace(5, 1240, 29)}), True),
        (depths({int(c): 1000 for c in np.linspace(5, 1240, 12)} | {640: 2000}), True),
        # One burst alone: its tile's thousand steps would only move.
        (depths({700: 1000}), False),
        # sched1k-faults: a rack's re-queued pods on a cycle's arrivals, in
        # a cluster or three.
        (depths({100: 88}), False),
        (depths({100: 88, 600: 71, 1100: 95}), False),
        # More deep clusters than a tile holds.
        (depths({c: 1000 for c in range(0, 1250, 9)}), False),
        (depths({}), False),
        # Two tiles of a pass of 256 (the stream), one burst a tile.
        (depths({3: 900, 200: 700}, shallow=50, n=256), True),
    ],
    ids=["bursts", "bursts-and-a-double", "lone-burst", "rack", "three-racks", "over-a-tile",
         "shallow", "two-tiles"],
)
def test_lanes_move_where_the_move_pays(n_eligible, moves):
    K = 256 if len(n_eligible) == 256 else 64
    moved = np.asarray(step._lanes_to_move(jnp.asarray(n_eligible), K, R))
    np.testing.assert_array_equal(moved, (n_eligible > K) & moves)


def test_each_chip_takes_its_own_branch_and_no_collective(runs):
    """Two devices, 150 clusters each, two tiles a shard. Under the shard_map
    the predicate, the selection and the put-back are a shard's own: in the
    first cycle the first shard holds three deep clusters over its two tiles
    and moves them while the second holds one alone and leaves it, and the
    other way round in the second cycle (two and one). The state is the
    one-device run's leaf for leaf but for that counter, and the compiled
    program holds no collective."""
    from test_batched_sharding import _COLLECTIVE, _compiled_window_program

    case = "several-over-three-tiles"
    with traced_with(CYCLE_COMPACT_PAYS=0):
        sharded = run(case, mesh=mesh_of(2))
        assert sharded.kernel_formulation()["shards"] == 2
        assert leaves_differing(sharded.state, runs[0][case].state, skip=("cycle_compacted",)) == []
        compacted = np.asarray(sharded.state.metrics.cycle_compacted)
        assert sorted(np.nonzero(compacted)[0]) == [3, 128, 131, 200, 299]
        assert _COLLECTIVE.findall(_compiled_window_program(sharded, "run_windows")) == []


def lowered_cycle(n_clusters):
    """The scheduling cycle alone, lowered for `n_clusters` clusters."""
    config = config_with("zero")
    nodes = GenericClusterTrace(events=cluster_events(False)).convert_to_simulator_events()
    compiled = compile_cluster_trace(
        nodes,
        GenericWorkloadTrace(
            events=workload_events("early", 0, 16, False)
        ).convert_to_simulator_events(),
        config,
    )
    sim = BatchedSimulation(
        config, [compiled] * n_clusters, use_pallas=True, pallas_interpret=True, max_pods_per_cycle=4
    )
    sim.use_pallas_select = sim.use_megakernel = True
    assert sim.kernel_formulation()["cycle"] == "megakernel"
    W = jnp.zeros((n_clusters,), jnp.int32)
    return jax.make_jaxpr(
        lambda state, W: step._run_scheduling_cycle(
            state, W, sim.consts, 4, True, True, use_pallas_select=True,
            use_megakernel=True, lane_major=sim.lane_major,
        )[0]
    )(sim.state, W)


def launches(jaxpr, inside_cond=False):
    """(clusters of the launch, is it inside a `cond` branch) of every call of
    the megakernel's wrapper under `jaxpr`, and the branch index it sits in."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit") and eqn.params["name"] == "fused_select_cycle_commit":
            found.append((eqn.invars[3].aval.shape[0], inside_cond))
            continue
        if eqn.primitive.name == "cond":
            for index, branch in enumerate(eqn.params["branches"]):
                found += launches(branch.jaxpr, inside_cond=index)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += launches(sub, inside_cond)
    return found


def test_second_launch_sits_in_the_taken_branch_alone():
    # Three tiles (300 clusters padded to 384 lanes): the `cond` sits round
    # the kernel's own operands, so its arm 0 (nobody deep) holds the single
    # launch over the whole batch and nothing else, and arm 1 the launch over
    # the clusters that stayed and the one tile of those that moved. No
    # launch outside the branch, none of one tile outside arm 1.
    assert launches(lowered_cycle(C).jaxpr) == [(384, 0), (384, 1), (R, 1)]


@pytest.mark.parametrize("n_clusters", [2, R])
def test_one_tile_traces_the_single_launch(n_clusters):
    jaxpr = lowered_cycle(n_clusters)
    assert launches(jaxpr.jaxpr) == [(n_clusters, False)]


def lane_pads(jaxpr):
    """The `pad` equations under `jaxpr` that widen an operand's LAST axis."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pad" and eqn.params["padding_config"][-1] != (0, 0, 0):
            found.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += lane_pads(sub)
    return found


def wrapper_calls(jaxpr):
    """Every call of the megakernel's wrapper under `jaxpr`, as its jaxpr."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name in ("jit", "pjit") and eqn.params["name"] == "fused_select_cycle_commit":
            found.append(eqn.params["jaxpr"].jaxpr)
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += wrapper_calls(sub)
    return found


def test_the_wrapper_is_handed_whole_lane_tiles():
    """_launch_by_depth pads the cluster axis to the wrapper's own tiles
    (ops/scheduler_kernel._LANE) before the `cond`, so that inside an arm the
    wrapper's pad of that axis moves nothing: no launch of the three widens a
    last axis. The single launch of a batch that is NOT whole tiles shows
    that the reader sees such a pad where there is one."""
    calls = wrapper_calls(lowered_cycle(C).jaxpr)
    assert len(calls) == 3 and [len(lane_pads(call)) for call in calls] == [0, 0, 0]
    (alone,) = wrapper_calls(lowered_cycle(2).jaxpr)
    assert lane_pads(alone)


@pytest.mark.parametrize("shape", [(40, 300), (300,), (3, 8, 300)], ids=["plane", "vector", "table"])
def test_lanes_travel_bit_for_bit(shape):
    """step._take_lanes / _put_lanes, cluster axis last: every int32, every
    float32 (nan, both infinities, -0.0, denormals as bits) and bool arrives
    as it left, empty slots read zero, unmoved clusters keep their own."""
    rng = np.random.default_rng(45)
    n = shape[-1]
    moved = np.zeros(n, bool)
    moved[rng.choice(n, 29, replace=False)] = True
    lanes = np.nonzero(moved)[0]
    index = jnp.asarray(np.concatenate([lanes, np.full(R - len(lanes), n)]).astype(np.int32))
    slot = jnp.asarray((np.cumsum(moved) - 1).astype(np.int32))
    words = rng.integers(-(2**31), 2**31, shape, dtype=np.int64).astype(np.int32)
    words.flat[:4] = [-1, 0, np.iinfo(np.int32).min, np.iinfo(np.int32).max]
    floats = words.view(np.float32).copy()
    floats.flat[4:8] = [np.nan, np.inf, -np.inf, -0.0]

    def bits(x):
        return np.ascontiguousarray(x).view(np.uint8)

    for plane in (words, floats, rng.random(shape) < 0.5):
        taken = np.asarray(step._take_lanes(jnp.asarray(plane), index))
        assert taken.dtype == plane.dtype and taken.shape == shape[:-1] + (R,)
        np.testing.assert_array_equal(bits(taken[..., : len(lanes)]), bits(plane[..., lanes]))
        assert not bits(taken[..., len(lanes) :]).any()
        # Put back over a plane of other values: the moved clusters' lanes
        # return, every other keeps its own.
        other = plane[..., ::-1].copy()
        back = np.asarray(
            step._put_lanes(jnp.asarray(other), jnp.asarray(taken), slot, jnp.asarray(moved))
        )
        np.testing.assert_array_equal(bits(back), bits(np.where(moved, plane, other)))
