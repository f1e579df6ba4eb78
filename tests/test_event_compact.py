"""The event chunk loop finishes a burst in a lane tile of its own
(step._apply_window_events_work, PR 49): in a batch of more than one lane tile
of the event kernel, the clusters with a burst of slab events due take their
passes in ONE tile, before the batch's loop and not in it. Held here:

(a) the state after a run of windows equals, LEAF FOR LEAF, the state of the
    same run with every pass in the batch's loop (the test's own predicate,
    which moves nobody, stands in for step._event_lanes_to_move), for no
    cluster deep, one alone (it moves: what is saved
    is the other tiles' passes), a burst in every tile, exactly a tile's
    worth, one more than that (nobody moves), two bursts in two windows of
    one cluster, a build under node faults with the crash plane and a rack
    of crashes and recoveries in the burst's window, a build under the
    reference's network delays with the conditional move, row-major and
    lane-major, and the device ring on, its `event_chunks` column unchanged;
    the predicate at its own price throughout (a burst of 100 on a chunk of
    32 over three tiles pays);
(b) events_deep / events_compacted read the counts the traces were built to
    (a cluster's own windows with more due than a chunk, and those of them
    that ran in the tile: where more clusters are deep than a tile holds,
    every one is deep and none is compacted), a build that applies its
    events by scatters moves the same clusters and holds the same state,
    and step._event_lanes_to_move moves a job's creations, alone too, and
    leaves alone a rack's crashes on a window's arrivals, more deep clusters
    than a tile holds, and a shallow batch;
(c) the tile's loop sits in the branch a window with a cluster to move takes
    and nowhere else (the razor's `cond` made a `switch`; the other branches
    are the single loop's program), no program of one tile has that branch
    or the two counters' leaves; under a mesh each shard takes its own branch
    and the program holds no collective.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched import step
from kubernetriks_tpu.batched.engine import BatchedSimulation
from kubernetriks_tpu.batched.state import TraceSlab, compare_states
from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
from kubernetriks_tpu.core.events import CreateNodeRequest, RemoveNodeRequest
from kubernetriks_tpu.ops import scheduler_kernel
from kubernetriks_tpu.telemetry.ring import RING_COLUMNS
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace
from test_cycle_compact import traced_with
from test_cycle_drain import leaves_differing
from test_pending_free import config_with, node_event, pod_event
from tests.sharded_builds import mesh_of

C = 300  # three lane tiles
R = scheduler_kernel._LANE
E = 32
BURST = 100  # more than E + EVENT_COMPACT_PAYS * 3 / 2 = 80: the move pays
SMALL = 50  # two passes, and under 80: it does not
END = 70.0
N_NODES = 12
RACK = 4


def cluster_events(crashes):
    """A dozen nodes at t = 0; with `crashes`, a rack of RACK nodes lost at
    23-24 s, in the window of the "early" burst, and back at 41-42 s, in the
    window of the "late" one."""
    events = GenericClusterTrace(
        events=[node_event(0.0, f"node_{i:03d}", cpu=16000, ram_gib=32) for i in range(N_NODES)]
    ).convert_to_simulator_events()
    if crashes:
        nodes = {event.node.metadata.name: event.node for _, event in events}
        for i in range(RACK):
            name = f"node_{N_NODES - 1 - i:03d}"
            events.append(
                (23.0 + 0.25 * i, RemoveNodeRequest(node_name=name, crashed=True, downtime_s=18.0))
            )
            events.append((41.0 + 0.25 * i, CreateNodeRequest(node=nodes[name], recovered=True)))
        events.sort(key=lambda pair: pair[0])
    return events


def workload_events(kind, seed):
    """Two pods a cycle in the background, and by `kind`: nothing more
    ("shallow"), BURST pods at one instant of window 2 ("early") or window 4
    ("late"), or both ("twice"), or SMALL pods at one instant of window 2
    ("small": past a chunk, under the move's price). Every kind is padded to
    the same number of pods with arrivals after the run's end, so that all
    share one program."""
    rng = np.random.default_rng(seed)
    times = [10.0 * i + off for i in range(6) for off in (3.0, 7.0)]
    for t in {"early": [25.0], "late": [45.0], "twice": [25.0, 45.0]}.get(kind, []):
        times += [t] * BURST
    if kind == "small":
        times += [25.0] * SMALL
    times += [END + 100.0] * (12 + 2 * BURST - len(times))
    return [
        pod_event(t, f"pod_{i:05d}", np.round(rng.uniform(20.0, 60.0), 3), 4000, 8)
        for i, t in enumerate(sorted(times))
    ]


def spread_over_tiles(n):
    return [int(c) for c in np.linspace(1, C - 2, n).round()]


@dataclasses.dataclass(frozen=True)
class Case:
    deep: dict  # {cluster: kind}
    build: str = "plain"
    stays: bool = False  # more deep clusters than a tile holds: nobody moves


# Five programs in all (a compile each, twice): BUILDS.
CASES = {
    "none": Case({}),
    "one-alone": Case({137: "early"}),
    "a-burst-in-every-tile": Case(
        {3: "early", 127: "late", 128: "early", 131: "twice", 200: "late", 256: "early", 299: "late"}
    ),
    "exactly-a-tile": Case({c: "early" for c in spread_over_tiles(R)}),
    "one-more-than-a-tile": Case({c: "early" for c in spread_over_tiles(R + 1)}, stays=True),
    "a-few-past-the-chunk": Case({60: "small", 61: "late", 200: "early", 201: "small"}),
    "node-faults": Case({5: "early", 129: "late", 130: "twice", 290: "early"}, build="faults"),
    "netdelay-conditional-move": Case({3: "early", 131: "twice", 299: "late"}, build="netdelay"),
    "netdelay-row-major": Case({3: "early", 131: "twice", 299: "late"}, build="netdelay-rows"),
    "ring-on": Case({77: "twice", 210: "early"}, build="ring"),
}
CONDITIONAL_MOVE = "enable_unscheduled_pods_conditional_move: true\n"
# build: (delays, config suffix, crashes in the cluster trace, engine kwargs)
# The window razor on, as the chip has it (the tile's branch is the razor's
# `switch`'s third), but in one build, where a `cond` chooses the soup.
BUILDS = {
    "plain": ("zero", "", False, dict(lane_major=True, window_razor=True)),
    "faults": ("zero", "", True, dict(lane_major=True, window_razor=True)),
    "netdelay": ("reference", CONDITIONAL_MOVE, False, dict(lane_major=True, window_razor=True)),
    "netdelay-rows": ("reference", CONDITIONAL_MOVE, False, dict(lane_major=False, window_razor=False)),
    "ring": ("zero", "", False, dict(lane_major=True, window_razor=True, telemetry=True)),
}


def run(case, kernels=True, **kwargs):
    deep, build = CASES[case].deep, CASES[case].build
    delays, suffix, crashes, engine = BUILDS[build]
    config = config_with(delays, suffix)
    nodes = cluster_events(crashes)
    compiled = {
        kind: compile_cluster_trace(
            nodes,
            GenericWorkloadTrace(events=workload_events(kind, seed)).convert_to_simulator_events(),
            config,
        )
        for seed, kind in enumerate(["shallow", *sorted(set(deep.values()))])
    }
    sim = BatchedSimulation(
        config,
        [compiled[deep.get(c, "shallow")] for c in range(C)],
        use_pallas=kernels,
        pallas_interpret=kernels,
        max_pods_per_cycle=64,
        max_events_per_window=E,
        **engine,
        **kwargs,
    )
    formulation = sim.kernel_formulation()
    assert formulation["events"] == ("kernel" if kernels else "scatter")
    assert sim.max_events_per_window == E
    assert sim.conditional_move == bool(suffix)
    assert (sim.fault_params is not None and sim.fault_params.node_faults) == crashes
    sim.step_until_time(END)
    return sim


def nobody_moves(slab, cursor, W, chunk, lanes):
    """The comparison: every pass in the batch's loop."""
    return jnp.zeros(cursor.shape, jnp.bool_)


@pytest.fixture(scope="module")
def runs():
    """Every case run as the program runs it, then again with every pass in
    the batch's loop."""
    jax.clear_caches()
    compact = {case: run(case) for case in CASES}
    with traced_with(_event_lanes_to_move=nobody_moves):
        single = {case: run(case) for case in CASES}
    return compact, single


def burst_windows(deep, moved_only=False):
    """(C,) how many windows of the run hold more of the cluster's events
    than a chunk, or (moved_only) more than the move's price."""
    n = np.zeros(C, np.int32)
    for c, kind in deep.items():
        n[c] = {"twice": 2, "small": 0 if moved_only else 1}.get(kind, 1)
    return n


@pytest.mark.parametrize("case", list(CASES))
def test_the_tile_leaves_the_single_loops_state(runs, case):
    compact, single = (sims[case] for sims in runs)
    assert leaves_differing(compact.state, single.state, skip=("events_compacted",)) == []
    # The traffic did what the case says: a burst is more than a chunk and
    # the background never is, so the deep windows are the bursts' and
    # nobody else's, each finished in the tile but the small ones, which took
    # their second pass in the batch's loop; where the tile could not hold the
    # window's deep clusters every one was deep and nobody moved.
    # The twelve nodes' creations at t = 0 are under a chunk.
    m = jax.tree.map(np.asarray, compact.state.metrics)
    deep = burst_windows(CASES[case].deep)
    moved = burst_windows(CASES[case].deep, moved_only=True) * (not CASES[case].stays)
    np.testing.assert_array_equal(m.events_deep, deep)
    np.testing.assert_array_equal(m.events_compacted, moved)
    assert not np.asarray(single.state.metrics.events_compacted).any()
    assert int(m.pods_succeeded.sum() + m.scheduling_decisions.sum()) > 0
    compact.metrics_summary()
    counted = compact.telemetry_report()["counters"]
    assert (counted["events_deep"], counted["events_compacted"]) == (deep.sum(), moved.sum())


@pytest.mark.parametrize("case", ["a-few-past-the-chunk", "netdelay-row-major"])
def test_the_scatter_path_moves_what_the_kernels_move(runs, case):
    """The counters are the state's, and the benchmark holds the plain
    formulation's whole state leaf for leaf to the kernels' (autoscaled.stream,
    two tiles): a build that applies its events by scatters finishes the
    same clusters in a sub-batch of the same width (its loops in
    test_the_tiles_loop_sits_in_the_moving_windows_branch_alone), so
    events_compacted counts what ran in either build."""
    plain = run(case, kernels=False)
    # compare_states: every simulation leaf and integer counter exactly, the
    # float32 estimator sums to an ulp (differently fused programs).
    # (cycle_compacted is the megakernel's own, which the plain build lacks.)
    differing = compare_states(plain.state, runs[0][case].state)
    assert [leaf for leaf in differing if leaf != ".metrics.cycle_compacted"] == []
    assert np.asarray(plain.state.metrics.events_compacted).sum() > 0


def test_a_rack_crashes_and_returns_in_the_bursts_windows(runs):
    """The faults build's crash plane travels through the tile: the rack's
    four crashes share the early burst's window and its recoveries the late
    one's, in every cluster, and the fault counters are the single loop's."""
    compact, single = (sims["node-faults"] for sims in runs)
    for name in ("node_crashes", "node_recoveries", "node_downtime_s", "pod_interruptions"):
        got = np.asarray(getattr(compact.state.metrics, name))
        np.testing.assert_array_equal(got, np.asarray(getattr(single.state.metrics, name)))
    assert (np.asarray(compact.state.metrics.node_crashes) == RACK).all()
    assert (np.asarray(compact.state.metrics.node_recoveries) == RACK).all()
    assert np.asarray(compact.state.metrics.pod_interruptions).sum() > 0


def test_the_rings_event_chunks_are_the_single_loops(runs):
    """Ring on: a cluster's `event_chunks` is the passes ITS events needed,
    ceil(due / E), wherever it took them: four for a burst of 100 and the
    window's arrivals on a chunk of 32, in the tile as in the batch's loop."""
    compact, single = (sims["ring-on"] for sims in runs)
    column = RING_COLUMNS.index("event_chunks")
    wins, got = compact.telemetry_window_series()
    wins_single, want = single.telemetry_window_series()
    np.testing.assert_array_equal(wins, wins_single)
    np.testing.assert_array_equal(got[:, :, column], want[:, :, column])
    assert got[:, 77, column].tolist().count(4) == 2 and got[:, 210, column].max() == 4
    assert got[:, 0, column].max() == 1


# --- (b) the predicate ---------------------------------------------------------


def slab_with_due(due, window=3):
    """A slab whose cluster c holds due[c] events of window `window` - 1 and
    then a few of a later window, the cursors at 0."""
    n = max(due) + 4
    win = np.full((len(due), n), window + 5, np.int32)
    for c, d in enumerate(due):
        win[c, :d] = window - 1
    zeros = np.zeros_like(win)
    return TraceSlab.build(win, zeros.astype(np.float32), zeros + 3, zeros)


def due_counts(deep, shallow=20, n=1250, seed=49):
    due = np.random.default_rng(seed).poisson(shallow, n).astype(np.int32)
    due[list(deep)] = list(deep.values())
    return due


@pytest.mark.parametrize(
    "due, chunk, moves",
    [
        # sched1k-backlog.bursts: some thirty thousand-pod bursts over ten
        # tiles, on a chunk of 128 (moves past 128 + 36).
        (due_counts({int(c): 1020 for c in np.linspace(5, 1240, 29)}), 128, True),
        # One burst alone: nine tiles stop running its seven further passes.
        (due_counts({700: 1020}), 128, True),
        # Just past the price, and at it.
        (due_counts({700: 165}), 128, True),
        (due_counts({700: 164}), 128, False),
        # sched1k-faults: a rack's 50 crashes on a window's arrivals, a few
        # past a chunk of 64 (moves past 64 + 36) in a cluster or three.
        (due_counts({100: 71}), 64, False),
        (due_counts({100: 71, 600: 88, 1100: 95}), 64, False),
        # More deep clusters than a tile holds (a node burst in every cluster).
        (due_counts({c: 1000 for c in range(0, 1250, 9)}), 128, False),
        (due_counts({c: 1000 for c in range(1250)}), 128, False),
        (due_counts({}), 64, False),
        # Two tiles (the stream's 256 clusters, chunk 96: moves past 96 + 64).
        (due_counts({3: 900, 200: 161}, shallow=50, n=256), 96, True),
        (due_counts({3: 160}, shallow=50, n=256), 96, False),
    ],
    ids=["bursts", "lone-burst", "past-the-price", "at-the-price", "rack", "three-racks",
         "over-a-tile", "every-cluster", "shallow", "two-tiles", "two-tiles-at-the-price"],
)
def test_lanes_move_where_the_move_pays(due, chunk, moves):
    tiles = -(-len(due) // R)
    depth = chunk + -(-step.EVENT_COMPACT_PAYS * tiles // (tiles - 1))
    slab = slab_with_due(due)
    cursor = jnp.zeros(len(due), jnp.int32)
    W = jnp.full(len(due), 3, jnp.int32)
    moved = np.asarray(step._event_lanes_to_move(slab, cursor, W, chunk, R))
    # Those past the price, where a tile holds them.
    bursts = due > depth
    np.testing.assert_array_equal(moved, bursts & (bursts.sum() <= R))
    assert moved.any() == moves
    # Nothing is due before the events' own window, whatever lies ahead.
    assert not np.asarray(step._event_lanes_to_move(slab, cursor, W - 1, chunk, R)).any()


# --- (c) where the tile's loop sits ----------------------------------------------


def test_each_chip_takes_its_own_branch_and_no_collective(runs):
    """Two devices, 150 clusters each, two tiles a shard. Under the shard_map
    the predicate, the tile and the put-back are a shard's own: in window 2
    the first shard holds three bursts and the second one, in window 4 two
    and three; the state is the one-device run's leaf for leaf, and the
    compiled program holds no collective."""
    from test_batched_sharding import _COLLECTIVE, _compiled_window_program

    case = "a-burst-in-every-tile"
    sharded = run(case, mesh=mesh_of(2))
    assert sharded.kernel_formulation()["shards"] == 2
    assert leaves_differing(sharded.state, runs[0][case].state) == []
    np.testing.assert_array_equal(
        np.asarray(sharded.state.metrics.events_compacted), burst_windows(CASES[case].deep, True)
    )
    assert _COLLECTIVE.findall(_compiled_window_program(sharded, "run_windows")) == []


def lowered_events(n_clusters, kernel=True, razor=True):
    """The window's event application alone, as a jaxpr, for `n_clusters`
    clusters."""
    config = config_with("zero")
    compiled = compile_cluster_trace(
        cluster_events(False),
        GenericWorkloadTrace(events=workload_events("early", 0)).convert_to_simulator_events(),
        config,
    )
    sim = BatchedSimulation(
        config, [compiled] * n_clusters, use_pallas=kernel, pallas_interpret=kernel,
        max_events_per_window=E,
    )
    if kernel:
        sim.use_pallas_select = sim.use_megakernel = True
    assert sim.kernel_formulation()["events"] == ("kernel" if kernel else "scatter")
    W = jnp.ones((n_clusters,), jnp.int32)
    return jax.make_jaxpr(
        lambda state, W: step._apply_window_events(
            state, sim.slab, W, sim.consts, E, use_pallas=kernel, pallas_interpret=kernel,
            use_pallas_select=kernel, lane_major=sim.lane_major, window_razor=razor,
        )[0]
    )(sim.state, W).jaxpr


def loops(jaxpr, branches=()):
    """(clusters the loop runs over, the branch indices of the `cond`s and
    `switch`es it sits in, outermost first) of every event chunk loop under
    `jaxpr`: the `while`s whose carry starts with a cursor and a plane."""
    found = []
    for eqn in jaxpr.eqns:
        shapes = [v.aval.shape for v in eqn.outvars]
        if eqn.primitive.name == "while" and len(shapes) >= 7 and len(shapes[0]) == 1 and len(shapes[1]) == 2:
            found.append((shapes[0][0], branches))
        if eqn.primitive.name == "cond":
            for index, arm in enumerate(eqn.params["branches"]):
                found += loops(arm.jaxpr, branches + (index,))
            continue
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += loops(sub, branches)
    return found


@pytest.mark.parametrize("kernel", [True, False], ids=["kernel", "scatters"])
@pytest.mark.parametrize("razor", [True, False], ids=["razor", "no-razor"])
def test_the_tiles_loop_sits_in_the_moving_windows_branch_alone(razor, kernel):
    # Three tiles. With the razor the soup is the `switch`'s second branch as
    # the single loop has it, and its third with the tile's loop before the
    # batch's and no `cond` of its own; without it the two are a `cond`'s arms.
    single, tiled = ((1,), (2,)) if razor else ((0,), (1,))
    assert sorted(loops(lowered_events(C, kernel, razor))) == sorted(
        [(C, single), (C, tiled), (R, tiled)]
    )


@pytest.mark.parametrize("n_clusters", [R, 40], ids=["one-tile", "part-of-a-tile"])
def test_one_tile_traces_the_single_loop_and_no_counter(n_clusters):
    # The parent's program: one loop, in the razor's taken arm, over the
    # parent's state.
    assert loops(lowered_events(n_clusters)) == [(n_clusters, (1,))]
    assert loops(lowered_events(n_clusters, razor=False)) == [(n_clusters, ())]
    metrics = jax.eval_shape(lambda: _one_tile_state(n_clusters)).metrics
    assert metrics.events_deep is None and metrics.events_compacted is None


def _one_tile_state(n_clusters):
    from kubernetriks_tpu.batched.state import init_state

    zeros = np.zeros((n_clusters, 2), np.int32)
    return init_state(n_clusters, 2, 2, zeros, zeros, zeros, zeros, zeros.astype(np.float64), 10.0)
