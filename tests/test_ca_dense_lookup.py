"""PR 40: the cluster autoscaler's dense look-ups are the gathers, scatters and
cumulative sums they replace, bit for bit.

(a) `autoscale._rows_at` against `x[rows, idx]` / `take_along_axis` for int32,
    bool and float32 rows (-0.0, +-inf, nan, the TPair sentinels), at index 0
    and L - 1, with repeated indices, out of a row of one (Gn == 1) and of
    three (Gn == 3);
(b) `autoscale._rows_put` against the three scatters it stands for (add, set
    through a permutation, set True under a mask with the out-of-range target
    dropped);
(c) `autoscale._segment_sums` against the sort-cumsum-boundary form, with an
    empty segment, a segment longer than K_sd, keys in no segment and sums
    that wrap;
(d) `_ca_scale_down` against the two-sort path's verdict on a composed state
    with live CA nodes, frozen as data when that path went (PR 46,
    tests/data/ca_scale_down_two_sort_verdict.json): the XLA walk and the
    (interpreted) kernel, a K_sd that binds included, at an instant at which
    nothing may go and at one at which two nodes a cluster do.
"""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched import autoscale
from kubernetriks_tpu.batched.autoscale import _rows_at, _rows_put, _segment_sums
from kubernetriks_tpu.batched.timerep import INF_WIN, t_add

C = 5


def _rows(c=C):
    return jnp.arange(c, dtype=jnp.int32)[:, None]


def _bits(a):
    """What `bit for bit` compares: a float's pattern, so that -0.0 != 0.0
    and one nan is not another."""
    a = np.asarray(a)
    return a.view(np.int32) if a.dtype == np.float32 else a


def _row_values(dtype, L, rng):
    if dtype == "int32":
        x = rng.integers(-(2**31), 2**31, (C, L)).astype(np.int32)
        x[:, 0] = INF_WIN  # the TPair sentinel: "no pending effect"
        x[:, -1] = np.iinfo(np.int32).max
    elif dtype == "bool":
        x = rng.integers(0, 2, (C, L)).astype(bool)
    else:
        x = rng.standard_normal((C, L)).astype(np.float32)
        special = np.array([-0.0, np.inf, -np.inf, np.nan, 0.0], np.float32)
        x[:, : min(L, 5)] = special[: min(L, 5)]
        if L > 6:
            # a nan with a payload: a float add would quiet or lose it
            x[:, 6] = np.array([0x7FA00001], np.uint32).view(np.float32)[0]
    return x


def _indices(kind, L, M, rng):
    if kind == "first":
        return np.zeros((C, M), np.int32)
    if kind == "last":
        return np.full((C, M), L - 1, np.int32)
    if kind == "repeated":
        return np.repeat(rng.integers(0, L, (C, 1)), M, axis=1).astype(np.int32)
    return rng.integers(0, L, (C, M)).astype(np.int32)


@pytest.mark.parametrize("kind", ["random", "first", "last", "repeated"])
@pytest.mark.parametrize("L", [1, 3, 37])
@pytest.mark.parametrize("dtype", ["int32", "bool", "float32"])
def test_rows_at_is_the_gather(dtype, L, kind):
    rng = np.random.default_rng(zlib.crc32(f"{dtype}{L}{kind}".encode()))
    x = jnp.asarray(_row_values(dtype, L, rng))
    idx = jnp.asarray(_indices(kind, L, 11, rng))
    got = jax.jit(_rows_at)(x, idx)
    assert got.dtype == x.dtype and got.shape == idx.shape
    assert np.array_equal(_bits(got), _bits(x[_rows(), idx]))
    assert np.array_equal(_bits(got), _bits(jnp.take_along_axis(x, idx, axis=1)))


def test_rows_at_more_indices_than_the_row_is_long():
    """(C, P) look-ups out of (C, S): ca_reclaim's slot-pointer remap."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 50, (C, 7)).astype(np.int32))
    idx = jnp.asarray(rng.integers(0, 7, (C, 300)).astype(np.int32))
    assert np.array_equal(_rows_at(x, idx), x[_rows(), idx])


def test_rows_at_reads_nothing_outside_its_row():
    """The contract is 0 <= idx < L; what an index outside it reads is 0 /
    False, not the clamped element: stated, so that no site leans on it."""
    x = jnp.asarray([[5, 6, 7]], jnp.int32)
    assert _rows_at(x, jnp.asarray([[3, -1, 2]], jnp.int32)).tolist() == [[0, 0, 7]]
    assert _rows_at(x > 0, jnp.asarray([[3, 0]], jnp.int32)).tolist() == [[False, True]]


@pytest.mark.parametrize("L", [1, 3, 40])
def test_rows_put_adds_like_the_scatter_add(L):
    """`_per_group`, `keep_cnt`: counts by group, the padding column (group
    L, or -1) dropped; sums wrap like the scatter's."""
    rng = np.random.default_rng(L)
    idx = jnp.asarray(rng.integers(-1, L + 1, (C, 23)).astype(np.int32))
    v = jnp.asarray(rng.integers(-(2**31), 2**31, (C, 23)).astype(np.int32))
    tgt = jnp.where(idx < 0, L, idx)
    want = jnp.zeros((C, L + 1), jnp.int32).at[_rows(), tgt].add(v)[:, :L]
    assert np.array_equal(jax.jit(_rows_put, static_argnums=2)(idx, v, L), want)


@pytest.mark.parametrize("dtype", ["int32", "bool"])
def test_rows_put_sets_through_a_permutation(dtype):
    """`removed` back through `sd_order`, `pos` / `inv`: one source a slot."""
    rng = np.random.default_rng(11)
    S = 19
    perm = jnp.asarray(np.stack([rng.permutation(S) for _ in range(C)]).astype(np.int32))
    v = jnp.asarray(_row_values(dtype, S, rng))
    want = jnp.zeros((C, S), v.dtype).at[_rows(), perm].set(v)
    assert np.array_equal(_rows_put(perm, v, S), want)


def test_rows_put_touches_under_a_mask_and_drops():
    """`touch_create` / `touch_remove` / `node_blocked`: True at the targets
    of the masked sources, several sources a target, padding (-1) and the
    out-of-range target dropped."""
    rng = np.random.default_rng(12)
    N = 31
    idx = jnp.asarray(rng.integers(-1, N, (C, 64)).astype(np.int32))
    mask = jnp.asarray(rng.integers(0, 2, (C, 64)).astype(bool))
    tgt = jnp.where(mask & (idx >= 0), idx, N)
    want = jnp.zeros((C, N), bool).at[_rows(), tgt].set(True, mode="drop")
    assert np.array_equal(_rows_put(idx, mask, N), want)


def _segment_sums_by_cumsum(key, N, *values):
    """The parent's form: sort by key, cumulative sums, read at the
    segment's two boundaries."""
    rows = _rows(key.shape[0])
    col = jnp.arange(N, dtype=jnp.int32)[None, :]
    sorted_ = jax.lax.sort((key,) + tuple(v.astype(jnp.int32) for v in values), dimension=1, num_keys=1)
    key_s, vals_s = sorted_[0], sorted_[1:]
    start = (key_s[:, :, None] < col[:, None, :]).sum(axis=1, dtype=jnp.int32)
    end = start + (key_s[:, :, None] == col[:, None, :]).sum(axis=1, dtype=jnp.int32)
    zero = jnp.zeros((key.shape[0], 1), jnp.int32)
    out = []
    for v in vals_s:
        ecs = jnp.concatenate([zero, jnp.cumsum(v, axis=1)], axis=1)
        out.append(ecs[rows, end] - ecs[rows, start])
    return start, out


@pytest.mark.parametrize(
    "case", ["random", "empty_segments", "one_long_segment", "nothing_in_a_segment", "wrapping_sums"]
)
def test_segment_sums_are_the_cumsum_differences(case):
    rng = np.random.default_rng(zlib.crc32(case.encode()))
    P, N = 96, 9
    key = rng.integers(0, N + 1, (C, P)).astype(np.int32)
    big = 2**31 if case == "wrapping_sums" else 2**16
    a = rng.integers(-big, big, (C, P)).astype(np.int32)
    b = rng.integers(0, 2, (C, P)).astype(bool)
    if case == "empty_segments":
        key[np.isin(key, (0, 4, N - 1))] = N  # first, middle and last node hold nothing
    elif case == "one_long_segment":
        key[:, : P - 8] = 3  # 88 pods on one node: far past any K_sd
    elif case == "nothing_in_a_segment":
        key[:] = N
    start, (sa, sb) = jax.jit(_segment_sums, static_argnums=1)(jnp.asarray(key), N, jnp.asarray(a), jnp.asarray(b))
    want_start, (wa, wb) = _segment_sums_by_cumsum(jnp.asarray(key), N, jnp.asarray(a), jnp.asarray(b))
    assert np.array_equal(start, want_start)
    assert np.array_equal(sa, wa) and np.array_equal(sb, wb)
    if case == "empty_segments":
        assert not np.asarray(sb)[:, (0, 4, N - 1)].any()
    if case == "one_long_segment":
        assert (np.asarray(sb)[:, 3] > 8).all()


# --- (d) the whole pass against the two-sort path's frozen verdict ------------

CA_YAML = """
sim_name: ca_dense
seed: 1
scheduling_cycle_interval: 10.0
as_to_ps_network_delay: 0.050
ps_to_sched_network_delay: 0.089
sched_to_as_network_delay: 0.023
as_to_node_network_delay: 0.152
as_to_ca_network_delay: 0.67
as_to_hpa_network_delay: 0.50
cluster_autoscaler:
  enabled: true
  scan_interval: 10.0
  max_node_count: 8
  node_groups:
  - node_template:
      metadata: {name: ca_node}
      status: {capacity: {cpu: 16000, ram: 34359738368}}
"""


@pytest.fixture(scope="module")
def composed():
    """Four clusters whose load opens CA nodes and then drains: the state
    at an instant at which CA nodes are alive and hold pods (160 s) and at
    one at which the scale-down takes two a cluster (260 s)."""
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import PoissonWorkloadTrace, UniformClusterTrace

    config = SimulationConfig.from_yaml(CA_YAML)
    cluster = UniformClusterTrace(2, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=0.5, horizon=150.0, seed=5, cpu=4000, ram=8 * 1024**3,
        duration_range=(40.0, 160.0), name_prefix="p",
    )
    sim = build_batched_from_traces(
        config, cluster.convert_to_simulator_events(), workload.convert_to_simulator_events(),
        n_clusters=4, max_pods_per_cycle=16, use_pallas=False, fast_forward=False,
    )
    states = {}
    for instant in (160.0, 260.0):
        sim.step_until_time(instant)
        states[f"{instant:g}"] = sim.state
    yield sim, states
    sim.close()


def _scale_down(sim, state, k_sd, use_pallas):
    st = sim.autoscale_statics
    n = state.pods.phase.shape[0]
    interval = jnp.float32(sim.consts.scheduling_interval)
    snap = t_add(state.auto.ca_next, st.ca_snap, interval)
    return autoscale._ca_scale_down(
        state, state.auto, st, jnp.ones((n,), bool), k_sd,
        state.pods.phase, state.nodes.alloc_cpu, state.nodes.alloc_ram, snap, interval,
        use_pallas=use_pallas, pallas_interpret=use_pallas,
    )


@pytest.mark.parametrize("use_pallas", [False, True], ids=["walk", "kernel"])
@pytest.mark.parametrize("k_sd", [8, 1], ids=["k_sd_8", "k_sd_binds"])
def test_scale_down_equals_the_two_sort_paths_frozen_verdict(composed, k_sd, use_pallas, request):
    sim, states = composed
    state = states["160"]
    S = sim.autoscale_statics.ca_slots.shape[1]
    ca_alive = np.asarray(state.nodes.alive)[:, -S:]
    assert int(np.asarray(state.auto.ca_count).sum()) > 0 and ca_alive.any()
    on_ca = np.asarray(state.pods.node) >= state.nodes.alive.shape[1] - S
    running = np.asarray(state.pods.phase) == autoscale.PHASE_RUNNING
    per_node = np.bincount(np.asarray(state.pods.node)[on_ca & running], minlength=1)
    assert per_node.max() > 1, "no CA node holds more than one pod: k_sd = 1 would not bind"
    with open(os.path.join(os.path.dirname(__file__), "data", "ca_scale_down_two_sort_verdict.json")) as fh:
        frozen = json.load(fh)["cases"][request.node.callspec.id]
    for instant, want in frozen.items():
        removed, per_group = _scale_down(sim, states[instant], k_sd, use_pallas)
        assert removed.dtype == bool and per_group.dtype == jnp.int32
        want_removed = np.array([[c == "1" for c in row] for row in want["removed"]])
        assert np.array_equal(removed, want_removed), instant
        assert np.array_equal(per_group, np.array(want["removed_per_group"], np.int32)), instant
    assert np.array(frozen["260"]["removed_per_group"]).sum() > 0, "the frozen verdict removes nothing"
    if use_pallas:
        from kubernetriks_tpu.ops.autoscale_kernel import ca_down_kernel_fits

        assert ca_down_kernel_fits(state.nodes.alive.shape[1], S, k_sd)
