"""Parity tests for the fused Pallas scheduling kernel (interpret mode on the
CPU test platform): the kernel must reproduce the lax.scan formulation of the
scheduling cycle bit for bit — same decisions, same allocatables, same parks —
at both the kernel-call level and the full-simulation level.

Scalar semantics under test: the compiled scheduler profile's filter mask +
weighted score (batched/pipeline.py; default = Fit + LeastAllocatedResources,
reference: src/core/scheduler/kube_scheduler.rs:63-152, plugin.rs:33-63) +
last-max-wins argmax. Kernel-level parity is gated PER PROFILE: every
supported profile has an independent NumPy restatement of its scoring below,
so a lowering bug in one profile's expressions cannot hide behind another's.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.batched.pipeline import compile_profile
from kubernetriks_tpu.batched.state import compare_states
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu.ops.scheduler_kernel import fused_schedule_cycle
from kubernetriks_tpu.trace.generator import (
    PoissonWorkloadTrace,
    UniformClusterTrace,
)

NEG_INF = np.float32(-np.inf)


def _np_least_allocated(cpu, ram, rc, rr):
    cpu_f = cpu.astype(np.float32)
    ram_f = ram.astype(np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        cpu_s = np.where(
            cpu > 0, (cpu_f - np.float32(rc)) * np.float32(100.0) / cpu_f, NEG_INF
        )
        ram_s = np.where(
            ram > 0, (ram_f - np.float32(rr)) * np.float32(100.0) / ram_f, NEG_INF
        )
    return (cpu_s + ram_s) * np.float32(0.5)


def _np_most_allocated(cpu, ram, rc, rr):
    cpu_f = cpu.astype(np.float32)
    ram_f = ram.astype(np.float32)
    with np.errstate(divide="ignore", invalid="ignore"):
        cpu_s = np.where(
            cpu > 0, (np.float32(rc) - cpu_f) * np.float32(100.0) / cpu_f, NEG_INF
        )
        ram_s = np.where(
            ram > 0, (np.float32(rr) - ram_f) * np.float32(100.0) / ram_f, NEG_INF
        )
    return (cpu_s + ram_s) * np.float32(0.5)


def _np_balanced(cpu, ram, rc, rr):
    cpu_f = cpu.astype(np.float32)
    ram_f = ram.astype(np.float32)
    ok = (cpu > 0) & (ram > 0)
    with np.errstate(divide="ignore", invalid="ignore"):
        cpu_frac = np.float32(rc) / np.where(ok, cpu_f, np.float32(1.0))
        ram_frac = np.float32(rr) / np.where(ok, ram_f, np.float32(1.0))
    return np.where(
        ok,
        np.float32(100.0) - np.abs(cpu_frac - ram_frac) * np.float32(100.0),
        NEG_INF,
    )


# Independent score restatements per profile: name -> [(scorer fn, weight)].
NP_PROFILE_SCORERS = {
    "default": [(_np_least_allocated, 1.0)],
    "best_fit": [(_np_most_allocated, 1.0)],
    "balanced_packing": [(_np_most_allocated, 1.0), (_np_balanced, 0.25)],
}


def scan_reference(
    alive, alloc_cpu, alloc_ram, valid, req_cpu, req_ram, profile="default"
):
    """NumPy restatement of the lax.scan scheduling core under the given
    profile (float32 scores, last-max-wins argmax), the oracle for the
    kernel."""
    C, N = alloc_cpu.shape
    K = valid.shape[1]
    alloc_cpu = alloc_cpu.copy()
    alloc_ram = alloc_ram.copy()
    assign = np.zeros((C, K), bool)
    fit_any = np.zeros((C, K), bool)
    best = np.zeros((C, K), np.int32)
    scorers = NP_PROFILE_SCORERS[profile]
    for c in range(C):
        for k in range(K):
            fit = alive[c] & (req_cpu[c, k] <= alloc_cpu[c]) & (req_ram[c, k] <= alloc_ram[c])
            total = np.zeros(N, np.float32)
            for fn, w in scorers:
                s = fn(alloc_cpu[c], alloc_ram[c], req_cpu[c, k], req_ram[c, k])
                total = total + (s if w == 1.0 else s * np.float32(w))
            score = np.where(fit, total, NEG_INF)
            fit_any[c, k] = fit.any()
            if fit.any():
                m = score.max()
                b = np.max(np.where(score == m, np.arange(N), -1))
                best[c, k] = b
                if valid[c, k]:
                    assign[c, k] = True
                    alloc_cpu[c, b] -= req_cpu[c, k]
                    alloc_ram[c, b] -= req_ram[c, k]
    return assign, fit_any, best, alloc_cpu, alloc_ram


@pytest.mark.parametrize(
    "profile", ["default", "best_fit", "balanced_packing"]
)
@pytest.mark.parametrize("shape", [(3, 7, 5), (5, 130, 9), (2, 256, 33)])
def test_kernel_matches_scan_reference(shape, profile):
    C, N, K = shape
    rng = np.random.default_rng(shape[1])
    alive = rng.random((C, N)) < 0.8
    cap = rng.integers(1_000, 64_000, size=(C, N)).astype(np.int32)
    alloc_cpu = (cap * rng.random((C, N))).astype(np.int32)
    alloc_ram = (cap * rng.random((C, N))).astype(np.int32)
    valid = rng.random((C, K)) < 0.9
    req_cpu = rng.integers(0, 8_000, size=(C, K)).astype(np.int32)
    req_ram = rng.integers(0, 8_000, size=(C, K)).astype(np.int32)

    out = fused_schedule_cycle(
        jnp.asarray(alive),
        jnp.asarray(alloc_cpu),
        jnp.asarray(alloc_ram),
        jnp.asarray(valid),
        jnp.asarray(req_cpu),
        jnp.asarray(req_ram),
        interpret=True,
        profile=compile_profile(profile),
    )
    a_ref, f_ref, b_ref, cpu_ref, ram_ref = scan_reference(
        alive, alloc_cpu, alloc_ram, valid, req_cpu, req_ram, profile=profile
    )
    np.testing.assert_array_equal(np.asarray(out[0]), a_ref)
    # fit_any/best are only defined for valid candidates: the kernel's
    # early-exit loop skips iterations past the tile's last valid candidate
    # (leaving zeros), and best additionally holds garbage sentinels where
    # fit_any is false on both paths. Every consumer gates on `valid`.
    np.testing.assert_array_equal(
        np.where(valid, np.asarray(out[1]), False), np.where(valid, f_ref, False)
    )
    defined = valid & f_ref
    np.testing.assert_array_equal(
        np.where(defined, np.asarray(out[2]), -1), np.where(defined, b_ref, -1)
    )
    np.testing.assert_array_equal(np.asarray(out[3]), cpu_ref)
    np.testing.assert_array_equal(np.asarray(out[4]), ram_ref)


def _build(use_pallas, profile=None, **kwargs):
    config = SimulationConfig.from_yaml(
        "sim_name: pallas_parity\nseed: 9\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(12, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=1.0,
        horizon=300.0,
        seed=11,
        cpu=3000,
        ram=6 * 1024**3,
        duration_range=(15.0, 90.0),
    )
    return build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=3,
        max_pods_per_cycle=16,
        use_pallas=use_pallas,
        pallas_interpret=use_pallas,
        scheduler_profile=profile,
        **kwargs,
    )


@pytest.mark.parametrize("profile", [None, "best_fit"])
def test_full_sim_pallas_matches_scan(profile):
    """Whole-run parity: identical final state pytrees (phases, assignments,
    allocatables, timings, metrics) between the scan and Pallas paths —
    under the default AND a non-default compiled profile (the profile is a
    kernel static; both formulations must lower it identically)."""
    sim_scan = _build(use_pallas=False, profile=profile)
    sim_pallas = _build(use_pallas=True, profile=profile)
    assert sim_pallas.use_pallas and not sim_scan.use_pallas
    assert sim_pallas.profile.name == (profile or "default")
    sim_scan.step_until_time(500.0)
    sim_pallas.step_until_time(500.0)

    assert compare_states(sim_scan.state, sim_pallas.state) == []

    summary = sim_pallas.metrics_summary()
    assert summary["counters"]["scheduling_decisions"] > 50


# --- fused selection + cycle kernel ------------------------------------------


def selection_oracle(alive, alloc_cpu, alloc_ram, eligible, qwin, qoff, qseq,
                     req_cpu, req_ram, K):
    """NumPy restatement of prepare_cycle's sorted top-K compaction followed
    by the scan core: candidates in (win, off, seq) order."""
    C, P = eligible.shape
    cand = np.zeros((C, K), np.int32)
    valid = np.zeros((C, K), bool)
    creq_cpu = np.zeros((C, K), np.int32)
    creq_ram = np.zeros((C, K), np.int32)
    for c in range(C):
        keys_w = np.where(eligible[c], qwin[c], np.iinfo(np.int32).max)
        keys_o = np.where(eligible[c], qoff[c], np.inf)
        keys_s = np.where(eligible[c], qseq[c], np.iinfo(np.int32).max)
        order = np.lexsort((np.arange(P), keys_s, keys_o, keys_w))[:K]
        n = min(K, len(order))
        cand[c, :n] = order
        valid[c, :n] = eligible[c][order]
        creq_cpu[c, :n] = req_cpu[c][order]
        creq_ram[c, :n] = req_ram[c][order]
    assign, fit_any, best, cpu, ram = scan_reference(
        alive, alloc_cpu, alloc_ram, valid, creq_cpu, creq_ram
    )
    return cand, valid, assign, fit_any, best, cpu, ram


@pytest.mark.parametrize("shape", [(3, 7, 20, 5), (5, 130, 40, 9), (2, 64, 300, 33)])
def test_select_kernel_matches_sort_plus_scan(shape):
    from kubernetriks_tpu.ops.scheduler_kernel import fused_select_schedule_cycle

    C, N, P, K = shape
    rng = np.random.default_rng(P)
    alive = rng.random((C, N)) < 0.8
    cap = rng.integers(1_000, 64_000, size=(C, N)).astype(np.int32)
    alloc_cpu = (cap * rng.random((C, N))).astype(np.int32)
    alloc_ram = (cap * rng.random((C, N))).astype(np.int32)
    eligible = rng.random((C, P)) < 0.5
    qwin = rng.integers(0, 5, size=(C, P)).astype(np.int32)
    # Quantized offsets: with only 4 distinct values, exact (win, off)
    # collisions among eligible pods are common, so the kernel's FINAL
    # seq-level tie-break stage is genuinely exercised (a continuous random
    # off would never collide and a broken seq stage would pass).
    qoff = (
        rng.integers(0, 4, size=(C, P)).astype(np.float32) * np.float32(2.5)
    )
    # seq unique per cluster, like the queue counter guarantees.
    qseq = np.stack([rng.permutation(P) for _ in range(C)]).astype(np.int32)
    req_cpu = rng.integers(0, 8_000, size=(C, P)).astype(np.int32)
    req_ram = rng.integers(0, 8_000, size=(C, P)).astype(np.int32)

    out = fused_select_schedule_cycle(
        jnp.asarray(alive),
        jnp.asarray(alloc_cpu),
        jnp.asarray(alloc_ram),
        jnp.asarray(eligible),
        jnp.asarray(qwin),
        jnp.asarray(qoff),
        jnp.asarray(qseq),
        jnp.asarray(req_cpu),
        jnp.asarray(req_ram),
        k_pods=K,
        interpret=True,
    )
    cand_r, valid_r, assign_r, fit_r, best_r, cpu_r, ram_r = selection_oracle(
        alive, alloc_cpu, alloc_ram, eligible, qwin, qoff, qseq,
        req_cpu, req_ram, K,
    )
    cand, valid, assign, fit_any, best, cpu, ram = (np.asarray(o) for o in out)
    np.testing.assert_array_equal(valid, valid_r)
    np.testing.assert_array_equal(
        np.where(valid, cand, -1), np.where(valid_r, cand_r, -1)
    )
    np.testing.assert_array_equal(assign, assign_r)
    np.testing.assert_array_equal(
        np.where(valid, fit_any, False), np.where(valid_r, fit_r, False)
    )
    defined = valid & fit_r
    np.testing.assert_array_equal(
        np.where(defined, best, -1), np.where(defined, best_r, -1)
    )
    np.testing.assert_array_equal(cpu, cpu_r)
    np.testing.assert_array_equal(ram, ram_r)


def test_full_sim_selection_kernel_matches_scan():
    """Full-simulation equivalence with the selection kernel FORCED on
    (interpret mode; the auto gate needs C >= 128, which suite shapes
    don't reach)."""
    scan_sim = _build(False)
    sel_sim = _build(True)
    sel_sim.use_pallas_select = True
    scan_sim.step_until_time(400.0)
    sel_sim.step_until_time(400.0)
    bad = compare_states(scan_sim.state, sel_sim.state)
    assert not bad, bad


# --- live row tiles: the masks the serial pod-block kernels must survive ------

# (C, P) bool masks by name. P = 300 pads to 304 rows: three 128-row tiles,
# the last starting early (row 176) and overlapping the second; P = 256 is
# two whole tiles. Each is a case of the megakernel, free and event tests
# below: the kernels list the tiles holding a set row of any lane and sweep
# only those, and must equal the whole-block references bit for bit.
LIVE_MASKS = {
    "inside_one_tile": lambda rng, C, P: _band(C, P, 10, 30) & (rng.random((C, P)) < 0.7),
    "across_a_tile_edge": lambda rng, C, P: _band(C, P, 118, 140),
    "two_bands_far_apart": lambda rng, C, P: _band(C, P, 3, 9)
    | (_band(C, P, P - 20, P - 4) & (rng.random((C, P)) < 0.5)),
    "a_band_of_its_own_per_lane": lambda rng, C, P: np.stack(
        [_band(1, P, 40 * c, 40 * c + 12)[0] for c in range(C)]
    ),
    "no_row": lambda rng, C, P: np.zeros((C, P), bool),
    "every_row": lambda rng, C, P: np.ones((C, P), bool),
    "scattered": lambda rng, C, P: rng.random((C, P)) < 0.5,
}


def _band(C, P, lo, hi):
    cols = np.arange(P)[None, :]
    return np.broadcast_to((cols >= lo) & (cols < hi), (C, P)).copy()


def _live_tiles(mask):
    """(live tiles, tiles) of one 128-lane block as the kernels count them:
    tiles of scheduler_kernel._ROW_TILE rows over the sublane-padded block,
    the last one starting early where the height does not divide it."""
    from kubernetriks_tpu.ops import scheduler_kernel as sk

    n_rows = -(-mask.shape[1] // 8) * 8
    tile, n_tiles = sk._row_tiles(n_rows)
    rows = np.zeros(n_rows, bool)
    rows[: mask.shape[1]] = mask.any(axis=0)
    starts = [min(t * tile, n_rows - tile) for t in range(n_tiles)]
    return sum(bool(rows[s : s + tile].any()) for s in starts), n_tiles


def megakernel_oracle(alive, alloc_cpu, alloc_ram, eligible, qwin, qoff, qseq,
                      req_cpu, req_ram, waited, phase, node, qpre, start_t,
                      park_t, K):
    """selection_oracle's decisions committed the way commit_cycle's
    scatters do, and the queue-time estimator folded in decision order in
    float32 (the kernel's own order, so the sums are exact too)."""
    cand, valid, assign, fit_any, best, cpu, ram = selection_oracle(
        alive, alloc_cpu, alloc_ram, eligible, qwin, qoff, qseq,
        req_cpu, req_ram, K,
    )
    C, P = eligible.shape
    phase, node = phase.copy(), node.copy()
    start = np.full((C, P), np.inf, np.float32)
    park = np.full((C, P), np.inf, np.float32)
    stats = np.zeros((C, 5), np.float32)
    stats[:, 3], stats[:, 4] = np.inf, -np.inf
    for c in range(C):
        for k in range(K):
            if not valid[c, k]:
                continue
            s = cand[c, k]
            if assign[c, k]:
                phase[c, s], node[c, s] = 3, best[c, k]
                start[c, s] = start_t[c, k]
                q = np.float32(waited[c, s] + qpre[c, k])
                stats[c, 0] += np.float32(1.0)
                stats[c, 1] += q
                stats[c, 2] += np.float32(q * q)
                stats[c, 3] = min(stats[c, 3], q)
                stats[c, 4] = max(stats[c, 4], q)
            else:
                phase[c, s] = 2
                park[c, s] = park_t[c, k]
    return cpu, ram, phase, node, start, park, stats


def _megakernel_case(C, N, P, K, eligible, seed):
    rng = np.random.default_rng(seed)
    alive = rng.random((C, N)) < 0.8
    cap = rng.integers(1_000, 64_000, size=(C, N)).astype(np.int32)
    alloc_cpu = (cap * rng.random((C, N))).astype(np.int32)
    alloc_ram = (cap * rng.random((C, N))).astype(np.int32)
    qwin = rng.integers(0, 5, size=(C, P)).astype(np.int32)
    # Quantized offsets, as in the select test above: (win, off) collide
    # often, so the seq stage of the order decides.
    qoff = rng.integers(0, 4, size=(C, P)).astype(np.float32) * np.float32(2.5)
    qseq = np.stack([rng.permutation(P) for _ in range(C)]).astype(np.int32)
    # Requests up to 24,000 against allocatables under 64,000: some fit
    # nowhere, so both the assign and the park writes are exercised.
    req_cpu = rng.integers(0, 24_000, size=(C, P)).astype(np.int32)
    req_ram = rng.integers(0, 24_000, size=(C, P)).astype(np.int32)
    waited = rng.uniform(0.0, 50.0, size=(C, P)).astype(np.float32)
    phase = rng.integers(0, 4, size=(C, P)).astype(np.int32)
    node = rng.integers(-1, N, size=(C, P)).astype(np.int32)
    qpre = np.cumsum(rng.uniform(0, 1, size=(C, K)), axis=1).astype(np.float32)
    return (alive, alloc_cpu, alloc_ram, eligible, qwin, qoff, qseq, req_cpu,
            req_ram, waited, phase, node, qpre, qpre + np.float32(0.5),
            qpre + np.float32(0.25))


@pytest.mark.parametrize(
    "mask,shape",
    [(name, (5, 20, 300, 9)) for name in LIVE_MASKS]
    + [
        ("scattered", (3, 7, 256, 5)),  # the tile height divides the block
        ("scattered", (3, 7, 20, 5)),  # the block is shorter than a tile
        ("deepest_lane_at_k", (5, 20, 300, 9)),
        ("deepest_lane_past_k", (5, 20, 300, 9)),
        ("scattered", (130, 9, 300, 4)),  # two grid programs
    ],
)
def test_megakernel_matches_sort_scan_and_commit(mask, shape):
    """The megakernel against the NumPy restatement of what it fuses (the
    sorted top-K selection, the scan core, commit_cycle's scatters, the
    estimator fold): every output bit-identical, and the sweep counter in
    its stats rows 5-7 equal to the live tiles of the mask it was given."""
    from kubernetriks_tpu.ops.scheduler_kernel import fused_select_cycle_commit

    C, N, P, K = shape
    rng = np.random.default_rng(len(mask) + P)
    if mask.startswith("deepest_lane"):
        # One lane holds exactly K (or K + 3) eligible rows, the others few.
        eligible = _band(C, P, 50, 53)
        eligible[2] = _band(1, P, 100, 100 + K + (3 if mask.endswith("past_k") else 0))[0]
    else:
        eligible = LIVE_MASKS[mask](rng, C, P)
    args = _megakernel_case(C, N, P, K, eligible, seed=P + C)
    got = fused_select_cycle_commit(
        *(jnp.asarray(a) for a in args), k_pods=K, interpret=True
    )
    want = megakernel_oracle(*args, K)
    got = [np.asarray(g) for g in got]
    for name, g, w in zip(
        ("alloc_cpu", "alloc_ram", "phase", "node", "start", "park"), got, want
    ):
        np.testing.assert_array_equal(g, w, err_msg=name)
    np.testing.assert_array_equal(got[6][:, :5], want[6], err_msg="estimator rows")
    for lo in range(0, C, 128):  # one counter per 128-lane grid program
        block = eligible[lo : lo + 128]
        live, tiles = _live_tiles(block)
        steps = min(int(block.sum(axis=1).max()), K)
        np.testing.assert_array_equal(
            got[6][lo : lo + 128, 5:],
            np.broadcast_to(
                np.float32([live * steps, steps, tiles]), (len(block), 3)
            ),
        )


# --- free / event / commit scatter kernels -----------------------------------


@pytest.mark.parametrize(
    "mask,shape",
    [("scattered", (5, 40, 9))]
    + [(name, (5, 300, 9)) for name in LIVE_MASKS]
    + [("scattered", (3, 256, 7)), ("scattered", (130, 300, 5))],
)
def test_free_kernel_matches_scatter_add(mask, shape):
    from kubernetriks_tpu.ops.scheduler_kernel import fused_free_resources

    rng = np.random.default_rng(7)
    C, P, N = shape
    freed = LIVE_MASKS[mask](rng, C, P)
    node = rng.integers(0, N, size=(C, P)).astype(np.int32)
    req_cpu = rng.integers(1, 500, size=(C, P)).astype(np.int32)
    req_ram = rng.integers(1, 500, size=(C, P)).astype(np.int32)
    alloc_cpu = rng.integers(0, 10_000, size=(C, N)).astype(np.int32)
    alloc_ram = rng.integers(0, 10_000, size=(C, N)).astype(np.int32)

    finishes = freed & (rng.random((C, P)) < 0.7)
    value = rng.uniform(0.0, 100.0, size=(C, P)).astype(np.float32)
    got_cpu, got_ram, stats = fused_free_resources(
        jnp.asarray(freed), jnp.asarray(node), jnp.asarray(req_cpu),
        jnp.asarray(req_ram), jnp.asarray(finishes), jnp.asarray(value),
        jnp.asarray(alloc_cpu), jnp.asarray(alloc_ram),
        interpret=True,
    )
    want_cpu, want_ram = alloc_cpu.copy(), alloc_ram.copy()
    for c in range(C):
        for p in range(P):
            if freed[c, p]:
                want_cpu[c, node[c, p]] += req_cpu[c, p]
                want_ram[c, node[c, p]] += req_ram[c, p]
    np.testing.assert_array_equal(np.asarray(got_cpu), want_cpu)
    np.testing.assert_array_equal(np.asarray(got_ram), want_ram)
    # Estimator fold over the finished subset.
    stats = np.asarray(stats)
    # The kernel frees a lane's pods in rising slot order: a float32 fold in
    # that order is its sum to the bit.
    for c in range(C):
        vals = value[c][finishes[c]]
        assert stats[c, 0] == len(vals)
        total = total_sq = np.float32(0.0)
        for v in vals:
            total, total_sq = total + v, total_sq + np.float32(v * v)
        assert stats[c, 1] == total and stats[c, 2] == total_sq
        assert stats[c, 3] == (vals.min() if len(vals) else np.inf)
        assert stats[c, 4] == (vals.max() if len(vals) else -np.inf)


# Pod slots an event chunk names, by case: (rng, shape, P) -> slots. The
# event kernel sweeps the row tiles from the lowest to the highest in-range
# pod slot of the chunk's valid pod events.
EVENT_POD_SLOTS = {
    "anywhere": lambda rng, shape, P: rng.integers(0, P + 3, size=shape),
    "inside_one_tile": lambda rng, shape, P: rng.integers(10, 30, size=shape),
    "across_a_tile_edge": lambda rng, shape, P: rng.integers(118, 140, size=shape),
    "two_bands_far_apart": lambda rng, shape, P: np.where(
        rng.random(shape) < 0.5,
        rng.integers(0, 9, size=shape),
        rng.integers(P - 20, P, size=shape),
    ),
    "a_band_of_its_own_per_lane": lambda rng, shape, P: (
        40 * np.arange(shape[0])[:, None] + rng.integers(0, 12, size=shape)
    ),
    "no_row": lambda rng, shape, P: rng.choice([-1, P + 4, 1 << 29], size=shape),
    "half_out_of_range": lambda rng, shape, P: np.where(
        rng.random(shape) < 0.5,
        rng.integers(100, 120, size=shape),
        rng.choice([-1, P, P + 3, P + 4, 1 << 29], size=shape),
    ),
}


def pack_event_accumulators(created, nrm, pcr, pseq, prm):
    """Row-major (C, N) / (C, P) accumulators in the event kernel's padded
    lane-major layout (scheduler_kernel.event_accumulators): what the event
    loop's carry holds."""
    from kubernetriks_tpu.ops.scheduler_kernel import event_accumulators

    C, N = created.shape
    empty = event_accumulators(C, N, pcr.shape[1])
    return tuple(
        e.at[: x.shape[1], :C].set(jnp.asarray(x.T).astype(e.dtype))
        for e, x in zip(empty, (created, nrm, pcr, pseq, prm))
    )


@pytest.mark.parametrize(
    "slots,shape",
    [("anywhere", (4, 12, 7, 20))]
    + [(name, (5, 24, 9, 300)) for name in EVENT_POD_SLOTS]
    + [("anywhere", (3, 12, 7, 256)), ("anywhere", (130, 8, 5, 300))],
)
def test_event_kernel_matches_scatters(slots, shape):
    from kubernetriks_tpu.ops.scheduler_kernel import (
        event_accumulators_unpack,
        fused_event_scatter,
    )

    rng = np.random.default_rng(11)
    C, E, N, P = shape
    kind = rng.integers(1, 5, size=(C, E)).astype(np.int32)
    # Node events index N-space, pod events P-space; sprinkle out-of-range
    # slots (sliding-window drops).
    slot = np.where(
        (kind == 1) | (kind == 2),
        rng.integers(0, N + 2, size=(C, E)),
        EVENT_POD_SLOTS[slots](rng, (C, E), P),
    ).astype(np.int32)
    rel = rng.uniform(-5.0, 15.0, size=(C, E)).astype(np.float32)
    seq = rng.integers(0, 1000, size=(C, E)).astype(np.int32)
    # valid must be a per-lane prefix (due events are a sorted slab prefix).
    counts = rng.integers(0, E + 1, size=(C,))
    valid = np.arange(E)[None, :] < counts[:, None]

    created0 = rng.random((C, N)) < 0.2
    nrm0 = np.where(rng.random((C, N)) < 0.3, rng.uniform(0, 20, (C, N)), np.inf).astype(np.float32)
    pcr0 = np.full((C, P), np.inf, np.float32)
    pseq0 = np.zeros((C, P), np.int32)
    prm0 = np.full((C, P), np.inf, np.float32)

    got = fused_event_scatter(
        jnp.asarray(kind), jnp.asarray(slot), jnp.asarray(rel),
        jnp.asarray(seq), jnp.asarray(valid),
        *pack_event_accumulators(created0, nrm0, pcr0, pseq0, prm0),
        interpret=True,
    )
    got = event_accumulators_unpack(got, C, N, P, False)
    created, nrm, pcr, pseq, prm = (
        created0.copy(), nrm0.copy(), pcr0.copy(), pseq0.copy(), prm0.copy()
    )
    for c in range(C):
        for e in range(E):
            if not valid[c, e]:
                continue
            s = slot[c, e]
            if kind[c, e] == 1 and s < N:
                created[c, s] = True
            elif kind[c, e] == 2 and s < N:
                nrm[c, s] = min(nrm[c, s], rel[c, e])
            elif kind[c, e] == 3 and 0 <= s < P:
                pcr[c, s] = min(pcr[c, s], rel[c, e])
                pseq[c, s] = max(pseq[c, s], seq[c, e])
            elif kind[c, e] == 4 and 0 <= s < P:
                prm[c, s] = min(prm[c, s], rel[c, e])
    np.testing.assert_array_equal(np.asarray(got[0]), created)
    np.testing.assert_array_equal(np.asarray(got[1]), nrm)
    np.testing.assert_array_equal(np.asarray(got[2]), pcr)
    np.testing.assert_array_equal(np.asarray(got[3]), pseq)
    np.testing.assert_array_equal(np.asarray(got[4]), prm)


def test_commit_kernel_matches_scatters():
    from kubernetriks_tpu.ops.scheduler_kernel import fused_commit_scatter

    rng = np.random.default_rng(13)
    C, K, P, N = 4, 10, 30, 6
    # Unique candidate slots per cluster (a pod is selected at most once).
    cand = np.stack([rng.permutation(P)[:K] for _ in range(C)]).astype(np.int32)
    counts = rng.integers(0, K + 1, size=(C,))
    valid = np.arange(K)[None, :] < counts[:, None]
    assign = valid & (rng.random((C, K)) < 0.6)
    park = valid & ~assign
    best = rng.integers(0, N, size=(C, K)).astype(np.int32)
    start_s = rng.uniform(0, 5, size=(C, K)).astype(np.float32)
    park_s = rng.uniform(0, 5, size=(C, K)).astype(np.float32)
    phase0 = rng.integers(0, 4, size=(C, P)).astype(np.int32)
    node0 = rng.integers(-1, N, size=(C, P)).astype(np.int32)

    got = fused_commit_scatter(
        jnp.asarray(cand), jnp.asarray(assign), jnp.asarray(park),
        jnp.asarray(best), jnp.asarray(start_s), jnp.asarray(park_s),
        jnp.asarray(phase0), jnp.asarray(node0),
        interpret=True,
    )
    phase, node = phase0.copy(), node0.copy()
    start_tmp = np.full((C, P), np.inf, np.float32)
    park_tmp = np.full((C, P), np.inf, np.float32)
    for c in range(C):
        for k in range(K):
            s = cand[c, k]
            if assign[c, k]:
                phase[c, s] = 3
                node[c, s] = best[c, k]
                start_tmp[c, s] = start_s[c, k]
            elif park[c, k]:
                phase[c, s] = 2
                park_tmp[c, s] = park_s[c, k]
    np.testing.assert_array_equal(np.asarray(got[0]), phase)
    np.testing.assert_array_equal(np.asarray(got[1]), node)
    np.testing.assert_array_equal(np.asarray(got[2]), start_tmp)
    np.testing.assert_array_equal(np.asarray(got[3]), park_tmp)


@pytest.mark.parametrize(
    "seed,megakernel,profile",
    [
        (3, "1", None),
        (17, "1", "balanced_packing"),
        (17, "0", "best_fit"),
    ],
)
def test_random_trace_all_kernels_match_scan(seed, megakernel, profile, monkeypatch):
    # Pin the megakernel choice regardless of ambient env (the engine reads
    # KTPU_MEGAKERNEL at build time); the "0" case keeps the two-kernel
    # fallback path covered. The non-default profiles ride the same
    # engines (zero extra compiles vs parametrizing profiles separately):
    # the megakernel case lowers balanced_packing into
    # _select_cycle_commit_kernel, the two-kernel case lowers best_fit
    # into _select_cycle_kernel — so every in-kernel decision core is
    # profile-exercised against the scan path.
    monkeypatch.setenv("KTPU_MEGAKERNEL", megakernel)
    """Randomized full-sim equivalence with EVERY Pallas kernel forced on
    (the r4 MEGAKERNEL — selection + cycle + commit + queue-time estimator
    fold in one launch — plus the free and event kernels, interpret mode)
    against the pure-XLA scan path, over a trace with node churn and
    autoscalers — the strongest single parity statement the suite makes
    about the kernel set."""
    from kubernetriks_tpu.test_util import default_test_simulation_config
    from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

    rng = np.random.default_rng(seed)
    config = default_test_simulation_config(
        """
cluster_autoscaler:
  enabled: true
  scan_interval: 10.0
  max_node_count: 6
  node_groups:
  - node_template:
      metadata: {name: kca}
      status: {capacity: {cpu: 16000, ram: 34359738368}}
"""
    )
    cluster_events = ["events:"]
    for i in range(4):
        ts = round(float(rng.uniform(1.0, 20.0)), 1)
        cluster_events.append(
            f"""
- timestamp: {ts}
  event_type:
    !CreateNode
      node:
        metadata: {{name: n{i}}}
        status: {{capacity: {{cpu: 8000, ram: 17179869184}}}}"""
        )
    # One mid-run node failure to exercise reschedules through the kernels.
    cluster_events.append(
        """
- timestamp: 120.0
  event_type:
    !RemoveNode
      node_name: n0"""
    )
    workload_events = ["events:"]
    for i in range(int(rng.integers(25, 40))):
        ts = round(float(rng.uniform(2.0, 300.0)), 1)
        cpu = int(rng.choice([1000, 2000, 4000, 12000]))
        dur = round(float(rng.uniform(15.0, 90.0)), 1)
        workload_events.append(
            f"""
- timestamp: {ts}
  event_type:
    !CreatePod
      pod:
        metadata: {{name: p{i:03d}}}
        spec:
          resources:
            requests: {{cpu: {cpu}, ram: {cpu * 1048576}}}
            limits: {{cpu: {cpu}, ram: {cpu * 1048576}}}
          running_duration: {dur}"""
        )
    cluster = GenericClusterTrace.from_yaml("".join(cluster_events)).convert_to_simulator_events()
    workload = GenericWorkloadTrace.from_yaml("".join(workload_events)).convert_to_simulator_events()

    def build(pallas):
        sim = build_batched_from_traces(
            config,
            list(cluster),
            list(workload),
            n_clusters=4,
            max_pods_per_cycle=8,
            use_pallas=pallas,
            pallas_interpret=pallas,
            scheduler_profile=profile,
        )
        if pallas:
            # Force the dense kernel set at C=4: the build-time gates want
            # 128 clusters, and the megakernel's gate is read at build, so
            # the env alone would leave the two-kernel path on.
            sim.use_pallas_select = True
            sim.use_megakernel = megakernel == "1"
        return sim

    scan_sim, kern_sim = build(False), build(True)
    scan_sim.step_until_time(600.0)
    kern_sim.step_until_time(600.0)
    bad = compare_states(scan_sim.state, kern_sim.state)
    assert not bad, (seed, bad)
    counters = scan_sim.metrics_summary()["counters"]
    assert counters["scheduling_decisions"] > 0


def test_ring_swept_share_is_the_share_of_the_masks(monkeypatch):
    """The megakernel's sweep counter, carried by the device ring to
    telemetry_report()["ring"]["cycle_rows_swept_share"], equals the share
    counted on the host from the very `eligible` masks the kernel was given
    (live tiles x steps over tiles x steps, summed over the run's cycles);
    and the counter lives in the ring alone: a telemetry-off build has the
    leaves it had and the same values in them."""
    from kubernetriks_tpu.batched.state import strip_telemetry
    from kubernetriks_tpu.ops import scheduler_kernel as sk

    masks = []
    real = sk.fused_select_cycle_commit

    def spy(alive, alloc_cpu, alloc_ram, eligible, *rest, **kwargs):
        jax.debug.callback(lambda e: masks.append(np.asarray(e)), eligible)
        return real(alive, alloc_cpu, alloc_ram, eligible, *rest, **kwargs)

    monkeypatch.setattr(sk, "fused_select_cycle_commit", spy)
    on = _build(True, telemetry=True)
    on.use_pallas_select = on.use_megakernel = True  # the gates want C >= 128
    on.step_until_time(400.0)
    jax.effects_barrier()
    monkeypatch.setattr(sk, "fused_select_cycle_commit", real)

    assert masks and max(m.shape[1] for m in masks) > sk._ROW_TILE
    swept = whole = 0
    for mask in masks:
        live, tiles = _live_tiles(mask)
        steps = min(int(mask.sum(axis=1).max()), on.max_pods_per_cycle)
        swept, whole = swept + live * steps, whole + tiles * steps
    ring = on.telemetry_report()["ring"]
    assert 0 < swept < whole
    assert ring["cycle_rows_swept_share"] == swept / whole
    # One value per grid program, repeated on each of its 3 cluster lanes.
    assert ring["totals"]["cycle_tiles_swept"] == 3 * swept
    assert ring["totals"]["cycle_tile_steps"] == 3 * whole

    off = _build(True)
    off.use_pallas_select = off.use_megakernel = True
    off.step_until_time(400.0)
    assert off.state.telemetry is None
    assert jax.tree.structure(off.state) == jax.tree.structure(
        strip_telemetry(on.state)
    )
    assert compare_states(strip_telemetry(on.state), off.state) == []
