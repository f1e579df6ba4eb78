"""The event loop's slab read (state.TraceSlab): each cluster's chunk comes out
of the blocked slab as whole 128-lane rows, 2 x C gather indices, and must be
the point gather `rows[c, clip(cursor + arange(E), 0, E_total - 1)]` it
replaced wherever an entry is `valid`. The guard at the bottom reads the
lowered window program so that the C x E point gather cannot come back
unnoticed."""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched import step
from kubernetriks_tpu.batched.state import (
    EV_CREATE_POD,
    EV_NONE,
    SLAB_BLOCK_EVENTS,
    TraceSlab,
    compare_states,
    strip_telemetry,
    swap_node_layout,
)
from kubernetriks_tpu.batched.timerep import INF_WIN
from kubernetriks_tpu.test_util import leaves_differing
from tests.sharded_builds import autoscaled_batch, bare_batch, mesh_of


def _random_slab(C: int, E_total: int, seed: int = 0):
    """Time-sorted rows with a ragged real length a cluster (the trace
    compiler's own EV_NONE / INF_WIN padding after it) and the slab built
    from them."""
    rng = np.random.default_rng(seed)
    win = np.sort(rng.integers(0, 40, size=(C, E_total)), axis=1).astype(np.int32)
    real = rng.integers(E_total // 2, E_total + 1, size=(C, 1))
    pad = np.arange(E_total)[None, :] >= real
    win[pad] = INF_WIN
    off = rng.random((C, E_total)).astype(np.float32)
    kind = np.where(pad, EV_NONE, rng.integers(1, 5, size=(C, E_total))).astype(np.int32)
    slot = rng.integers(0, 1 << 20, size=(C, E_total)).astype(np.int32)
    rows = np.stack([win, off.view(np.int32), kind, slot], axis=-1)
    return rows, TraceSlab.build(win, off, kind, slot)


def _point_gather(rows, cursor, E, W):
    """The read this PR replaced, with its `valid`."""
    E_total = rows.shape[1]
    offs = cursor[:, None] + np.arange(E)[None, :]
    pk = rows[np.arange(rows.shape[0])[:, None], np.clip(offs, 0, E_total - 1)]
    valid = (offs < E_total) & (pk[..., 0] < W[:, None])
    return pk, valid


def _assert_chunk_is_point_gather(pk, rows, cursor, E, W):
    """`pk` is the point gather wherever an entry is `valid`, and `valid` is
    what the step computes now: the sentinel is never due."""
    want, valid = _point_gather(rows, cursor, E, W)
    np.testing.assert_array_equal(pk[..., 0] < W[:, None], valid)
    np.testing.assert_array_equal(np.where(valid[..., None], pk, 0), np.where(valid[..., None], want, 0))


def _cursor_cases(C, E, E_total):
    """(C,) cursor vectors: every cluster at one edge, then the edges mixed."""
    edges = [
        0, 1, SLAB_BLOCK_EVENTS - 1, SLAB_BLOCK_EVENTS, SLAB_BLOCK_EVENTS + 1,
        E_total // 2, E_total // 2 + 17, max(E_total - E, 0), E_total - 1,
        E_total, E_total + 1, E_total + E - 1, E_total + 3 * SLAB_BLOCK_EVENTS,
    ]
    cases = [np.full((C,), e, np.int32) for e in edges]
    cases.append(np.asarray([edges[(3 * c) % len(edges)] for c in range(C)], np.int32))
    return cases


@pytest.mark.parametrize("E_total", [3, 64, 75])
@pytest.mark.parametrize("E", [1, 20, 32, 70])
@pytest.mark.parametrize("C", [1, 4, 130])
def test_read_chunk_is_the_point_gather_where_valid(C, E, E_total):
    """Cursors at 0, unaligned mid-slab, at E_total - E, E_total - 1, E_total
    and in the sentinel tail; a chunk of 1, of a block, of neither (20: not a
    multiple of 8) and of more than two blocks (70); a slab shorter than a
    chunk, a whole number of blocks, and neither."""
    rows, slab = _random_slab(C, E_total, seed=C * 1000 + E)
    assert slab.packed.shape == (C, -(-E_total // SLAB_BLOCK_EVENTS) + 1, 4 * SLAB_BLOCK_EVENTS)
    read_slab = jax.jit(lambda cursor: (slab.read_chunk(cursor, E), slab.win_at(cursor)))
    W = np.full((C,), 25, np.int32)
    for cursor in _cursor_cases(C, E, E_total):
        pk, win_at = (np.asarray(x) for x in read_slab(jnp.asarray(cursor)))
        _assert_chunk_is_point_gather(pk, rows, cursor, E, W)
        # past the end every entry is the sentinel, whatever the cursor
        offs = cursor[:, None] + np.arange(E)[None, :]
        assert (pk[offs >= E_total] == (INF_WIN, 0, EV_NONE, 0)).all()
        want_win = np.where(cursor < E_total, rows[np.arange(C), np.clip(cursor, 0, E_total - 1), 0], INF_WIN)
        np.testing.assert_array_equal(win_at, want_win)


@pytest.mark.parametrize("E,n_rows", [(64, 3), (96, 4), (128, 5)])
def test_read_chunk_of_several_blocks_is_rows_sliced_on_the_host(E, n_rows):
    """The chunks the engine's rule picks beyond one block (engine.
    event_chunk_size: 64, 96, 128 entries, read as 3, 4, 5 block rows a
    cluster), with the cursor at every offset of a block and at, one before
    and past the last real event: each read is `rows()[c, cursor : cursor +
    E]` sliced on the host, sentinel rows where that runs past the slab."""
    C, E_total = 3, 5 * SLAB_BLOCK_EVENTS + 11
    _, slab = _random_slab(C, E_total, seed=E)
    assert (E + SLAB_BLOCK_EVENTS - 2) // SLAB_BLOCK_EVENTS + 1 == n_rows
    rows = np.asarray(slab.rows())
    sentinel = np.asarray((INF_WIN, 0, EV_NONE, 0), np.int32)
    # A cluster's last real event, by its own ragged length.
    last_real = (rows[..., 2] != EV_NONE).sum(axis=1) - 1
    chunk_at = jax.jit(lambda cursor: slab.read_chunk(cursor, E))
    cursors = [np.full((C,), SLAB_BLOCK_EVENTS + o, np.int32) for o in range(SLAB_BLOCK_EVENTS)]
    cursors += [(last_real + d).astype(np.int32) for d in (-1, 0, 1, E, 4 * E)]
    for cursor in cursors:
        pk = np.asarray(chunk_at(jnp.asarray(cursor)))
        assert pk.shape == (C, E, 4)
        for c in range(C):
            want = np.tile(sentinel, (E, 1))
            have = rows[c, cursor[c] : cursor[c] + E]
            want[: len(have)] = have
            np.testing.assert_array_equal(pk[c], want, err_msg=f"cluster {c} cursor {cursor[c]}")


def _ev_time(per_window_counts, interval=10.0):
    """(C, E) event times, +inf padded: cluster c has per_window_counts[c][w]
    events spread inside window w."""
    rows = [
        np.concatenate([w * interval + interval * (np.arange(n) + 0.5) / max(n, 1) for w, n in enumerate(counts)] or [[]])
        for counts in per_window_counts
    ]
    out = np.full((len(rows), max(len(r) for r in rows) + 2), np.inf)
    for c, r in enumerate(rows):
        out[c, : len(r)] = r
    return out


@pytest.mark.parametrize(
    "what,counts,chunk",
    [
        # 1000 CreateNodes at t = 0 next to twenty windows of 30-45 arrivals: the burst is an outlier, not the chunk
        ("burst window excluded", [[1000 + 40] + [30 + (3 * w + c) % 16 for w in range(20)] for c in range(3)], 64),
        # the batch-wide maximum a window decides, not a cluster's own typical count
        ("one busy cluster decides", [[5] * 12, [5] * 12, [70] * 12], 96),
        ("whole blocks, up", [[33] * 12], 64),
        ("a block exactly", [[32] * 12], 32),
        ("floor: one block", [[1, 0, 2, 1]], 32),
        ("no events at all", [[]], 32),
        ("ceiling: four blocks", [[500] * 12], 128),
        # nine windows in ten take one pass: the tenth busiest is the chunk
        ("the 90th percentile", [[10] * 17 + [40] * 2 + [70] * 2], 64),
    ],
)
def test_event_chunk_is_sized_from_the_traces_own_windows(what, counts, chunk):
    from kubernetriks_tpu.batched.engine import event_chunk_size

    assert event_chunk_size(_ev_time(counts), 10.0) == chunk, what
    assert chunk % SLAB_BLOCK_EVENTS == 0 and SLAB_BLOCK_EVENTS <= chunk <= 4 * SLAB_BLOCK_EVENTS


def test_an_explicit_chunk_wins_over_the_traces_rule():
    from kubernetriks_tpu.batched.engine import event_chunk_size

    sim = bare_batch(4)
    assert sim.max_events_per_window == event_chunk_size(sim._ev_time_np, 10.0) == SLAB_BLOCK_EVENTS
    assert bare_batch(4, max_events_per_window=8).max_events_per_window == 8
    assert bare_batch(4, max_events_per_window=200).max_events_per_window == 200


def test_slab_rows_are_the_build_rows_with_a_sentinel_tail():
    rows, slab = _random_slab(4, 75)
    back = np.asarray(slab.rows())
    np.testing.assert_array_equal(back[:, :75], rows)
    assert back.shape[1] == 4 * SLAB_BLOCK_EVENTS
    assert (back[:, 75:] == (INF_WIN, 0, EV_NONE, 0)).all()


@pytest.mark.parametrize("chunk", [1, 7, 32])
def test_burst_window_takes_several_chunks(chunk):
    """1000 CreateNodes at t = 0 (window 0's burst) applied in chunks of 1, 7
    and 32 entries, across every block boundary of the slab: the final state
    is the one a single 1,024-entry chunk gives, leaf for leaf, and with the
    device ring on window 0 records ceil(1000 / chunk) chunks."""
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import PoissonWorkloadTrace, UniformClusterTrace

    config = SimulationConfig.from_yaml("sim_name: burst\nseed: 1\nscheduling_cycle_interval: 10.0\n")
    cluster = UniformClusterTrace(1000, cpu=4000, ram=8 * 1024**3).convert_to_simulator_events()
    compiled = [
        compile_cluster_trace(
            cluster,
            PoissonWorkloadTrace(
                rate_per_second=0.5, horizon=60.0, seed=5 + i, cpu=3000, ram=6 * 1024**3,
                duration_range=(15.0, 40.0),
            ).convert_to_simulator_events(),
            config,
        )
        for i in range(2)
    ]

    def run(**kwargs):
        sim = BatchedSimulation(config, compiled, max_pods_per_cycle=8, fast_forward=False, **kwargs)
        sim.step_until_time(120.0)
        return sim

    whole = run(max_events_per_window=1024)
    chunked = run(max_events_per_window=chunk, telemetry=True)
    assert chunked.metrics_summary()["counters"]["pods_succeeded"] > 0
    assert int(np.asarray(chunked.state.nodes.alive).sum()) == 2 * 1000
    assert leaves_differing(whole.state, strip_telemetry(chunked.state)) == []
    wins, data = chunked.telemetry_window_series()
    col = chunked.telemetry_report()["ring"]["columns"].index("event_chunks")
    # window 1 applies the events of [0, 10) s: the 1000 creations and the first pods
    due = (chunked._ev_time_np < 10.0).sum(axis=1)
    assert (due >= 1000).all()
    np.testing.assert_array_equal(data[list(wins).index(1)][:, col], -(-due // chunk))


def _kernel_build(make, **kwargs):
    """The chip's program family, interpreted: the dense kernel set (event
    and free scatters, megakernel) with lane-major node state."""
    sim = make(8, use_pallas=True, pallas_interpret=True, lane_major=True, **kwargs)
    sim.use_pallas_select = sim.use_megakernel = True
    return sim


@pytest.mark.parametrize("executor", ["ladder", "superspan"])
@pytest.mark.parametrize("build", ["bare", "autoscaled"])
def test_whole_job_matches_the_scan_formulation(build, executor):
    """A whole job of the kernel build (whose event loop feeds
    fused_event_scatter from the blocked read) against the plain lax.scan
    formulation (XLA scatters, ladder, row-major), both through the same
    read: every simulation leaf exactly, the float32 metric accumulators to
    the documented tolerance (compare_states)."""
    make = bare_batch if build == "bare" else autoscaled_batch
    shared = {"reclaim": False} if build == "autoscaled" else {}
    if executor == "superspan":
        shared["pod_window"] = 64  # the plain build slides it on the host, by the ladder
    kernel = _kernel_build(make, superspan=executor == "superspan", **shared)
    plain = make(8, use_pallas=False, superspan=False, **shared)
    horizon = 700.0 if build == "bare" else 1000.0
    kernel.step_until_time(horizon)
    plain.step_until_time(horizon)
    assert kernel.kernel_formulation()["cycle"] == "megakernel"
    if executor == "superspan":
        assert kernel.dispatch_stats["superspans"] > 0 and kernel._pod_base > 0
    assert kernel.metrics_summary()["counters"]["pods_succeeded"] > 0
    bad = compare_states(plain.state, kernel.state)
    assert not bad, bad


@pytest.mark.parametrize(
    "C,passes,lane_major", [(5, 2, False), (5, 3, True), (130, 2, True), (130, 3, False)]
)
def test_kernel_layout_carry_is_the_scatter_path_bit_for_bit(monkeypatch, C, passes, lane_major):
    """The event loop of a build that takes the event kernel carries its
    five accumulators in the kernel's padded lane-major layout, (Np, Cp) and
    (Pp, Cp), and leaves it once after the loop; the plain scatter path
    carries them row-major. One window whose due events take two and three
    passes, at C not a multiple of 128 (one lane tile, and two with a ragged
    second) and P not a multiple of 8: every leaf of the state after the
    event application is the same, bit for bit."""
    from kubernetriks_tpu.batched.engine import BatchedSimulation
    from kubernetriks_tpu.batched.trace_compile import compile_cluster_trace
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import PoissonWorkloadTrace, UniformClusterTrace

    monkeypatch.setenv("KTPU_ALIGN_PODS", "0")  # keep P the trace's own count
    config = SimulationConfig.from_yaml("sim_name: carry\nseed: 1\nscheduling_cycle_interval: 10.0\n")
    cluster = UniformClusterTrace(11, cpu=16000, ram=32 * 1024**3).convert_to_simulator_events()
    compiled = [
        compile_cluster_trace(
            cluster,
            PoissonWorkloadTrace(
                rate_per_second=0.8 + 0.1 * (i % 7), horizon=30.0, seed=300 + i, cpu=1000, ram=2 * 1024**3,
                duration_range=(15.0, 40.0),
            ).convert_to_simulator_events(),
            config,
        )
        for i in range(C)
    ]
    sim = BatchedSimulation(config, compiled, fast_forward=False)
    N, P = sim.n_nodes, sim.n_pods
    # A state at rest is row-major; a lane-major program swaps the hot node leaves at its entry.
    state0 = swap_node_layout(sim.state) if lane_major else sim.state
    assert C % 128 and P % 8 and N % 8, (C, N, P)
    due = int((sim._ev_time_np < 10.0).sum(axis=1).max())
    E = -(-due // passes)
    assert -(-due // E) == passes and due > N  # pod creations among the due events
    W = jnp.ones((C,), jnp.int32)

    def apply(**kernels):
        return jax.jit(
            lambda state: step._apply_window_events_work(
                state, sim.slab, W, sim.consts, E, lane_major=lane_major, **kernels
            )
        )(state0)

    plain = apply(use_pallas=False)
    kernel = apply(use_pallas=True, pallas_interpret=True, use_pallas_select=True)
    assert leaves_differing(plain, kernel) == []
    state = kernel[0]
    assert int(np.asarray(state.nodes.alive).sum()) == C * N
    assert int(np.asarray(state.event_cursor).max()) == due
    assert (np.asarray(state.pods.phase) != 0).sum() == int((sim._ev_time_np < 10.0).sum()) - C * N


def test_whole_job_under_a_mesh_of_four_matches_unsharded_every_leaf():
    """Each shard reads its own rows of the slab: the sharded kernel build's
    final state is the unsharded one's on every leaf."""
    unsharded = _kernel_build(bare_batch)
    sharded = _kernel_build(bare_batch, mesh=mesh_of(4))
    unsharded.step_until_time(700.0)
    sharded.step_until_time(700.0)
    assert sharded.kernel_formulation()["shards"] == 4
    assert leaves_differing(unsharded.state, sharded.state) == []


def test_lane_trace_install_keeps_the_blocked_layout():
    """A lane-async build after `set_lane_trace` installs a row range: the
    lane's blocks hold the mux's masked rows with the sentinel tail, the
    other lanes are untouched, and the read equals the point gather."""
    from kubernetriks_tpu.batched.fleet import scenario_vectors
    from kubernetriks_tpu.config import SimulationConfig

    config = SimulationConfig.from_yaml("sim_name: sharded_bare\nseed: 1\nscheduling_cycle_interval: 10.0\n")
    sim = bare_batch(4, scenario=dict(scenario_vectors(config, 4, None)), lane_async=True)
    before = np.asarray(sim.slab.rows())
    n = sim.n_events
    lo, hi = n // 4, n // 2
    sim.set_lane_trace(2, lo, hi)
    after = np.asarray(sim.slab.rows())
    assert after.shape == before.shape
    np.testing.assert_array_equal(np.delete(after, 2, axis=0), np.delete(before, 2, axis=0))
    assert (after[2, n:] == (INF_WIN, 0, EV_NONE, 0)).all()
    creates_before = before[2, :n, 2] == EV_CREATE_POD
    creates_after = after[2, :n, 2] == EV_CREATE_POD
    assert creates_after.sum() < creates_before.sum()
    assert not creates_after[:lo].any() and not creates_after[hi:].any()
    np.testing.assert_array_equal(after[2, :n, [0, 1, 3]], before[2, :n, [0, 1, 3]])
    E = sim.max_events_per_window
    W = np.full((4,), 30, np.int32)
    for cursor in _cursor_cases(4, E, n):
        pk = np.asarray(sim.slab.read_chunk(jnp.asarray(cursor), E))
        _assert_chunk_is_point_gather(pk, after[:, :n], cursor, E, W)


_GATHER = re.compile(r'"stablehlo\.gather"\(.*?\) <\{.*?\}> : \((tensor<[^>]*>), (tensor<[^>]*>)\) ->')


def test_no_gather_reads_the_slab_with_more_than_two_indices_a_cluster():
    """The guard: in the lowered `run_windows` of a bare build every gather
    whose operand is the slab takes at most 2 x C start indices (the chunk
    read's two blocks a cluster, the loop condition's one entry a cluster).
    The point gather this replaced took C x E."""
    sim = bare_batch(16)
    C, E = sim.n_clusters, sim.max_events_per_window
    assert E > 2, "the guard needs a chunk the point gather would exceed it with"
    text = step.run_windows.lower(
        sim.state, sim.slab, jnp.arange(4, dtype=jnp.int32), sim.consts,
        collect_gauges=False, **sim._window_call_kwargs(),
    ).as_text()
    slab_type = "tensor<{}xi32>".format("x".join(str(d) for d in sim.slab.packed.shape))
    seen = []
    for operand, indices in _GATHER.findall(text):
        if operand == slab_type:
            dims = [int(d) for d in re.findall(r"(\d+)x", indices)]
            seen.append(int(np.prod(dims[:-1])))  # the last dimension is the index vector
    assert seen, "no gather on the slab found: the guard reads nothing"
    assert max(seen) <= 2 * C, seen
    assert 2 * C in seen, seen  # not vacuous: the chunk read is among them
