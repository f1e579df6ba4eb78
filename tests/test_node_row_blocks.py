"""The decision core's row blocks against the scan path's whole-axis sweep.

`ops/scheduler_kernel._fit_score_place` reads the node tile a block of rows
at a time and carries every reduction over the node axis as a running value
(one vreg a word: a row competes with the rows congruent to it mod 8, a later
row wins a tie, the 8 survivors are reduced once a sweep). The scan path
(`step.decide_scan`) reduces the whole axis at once with the same pipeline
functions. The two must agree bit for bit: decisions, allocatables, the
spread table and every flag and counter, in every arm (float32 score, with
and without the spread filter; the exact key under the label filters; the
integer scorers with and without soft planes) and in all three kernels.

The tiles are built to hit the merge rules: the best rank tied between the
last row of one block and the first of the next, tied across three blocks,
tied inside one (sublane, lane) position's rows, the only fit in the tile's
last real row (a ragged or 8-row block), no fit anywhere, a lane with
nothing to decide; widths whose padded tiles are 1,000 / 1,320 / 304 / 8
rows. Interpret mode, CPU.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from kubernetriks_tpu.batched import pipeline
from kubernetriks_tpu.batched.pipeline import NodeFacts, SoftFacts, compile_profile
from kubernetriks_tpu.ops import scheduler_kernel as sk

LANES = 8  # clusters of a case: one merge rule each, then random ones
K = 4  # candidates a cluster: a placement on a tied node moves the tie
UNITS = (500, 1024)  # the integer arms' (cpu, ram) units, the kubescore cell's
G, Z = 2, 3  # spread workloads and domains

ARMS = {
    "default": dict(profile=compile_profile("default")),
    "topology_spread": dict(profile=compile_profile("topology_spread"), spread=True),
    "node_pools_exact": dict(profile=compile_profile("node_pools")._replace(exact_bits=12), affinity=True),
    "kube_default": dict(profile=compile_profile("kube_default")._replace(units=UNITS), affinity=True, kube=True),
    "kube_default_soft": dict(
        profile=compile_profile("kube_default")._replace(units=UNITS, soft_taints=1),
        affinity=True, kube=True, soft=True,
    ),
}
WIDTHS = [1000, 1313, 300, 5]  # padded: 1,000 / 1,320 / 304 / 8 rows


@pytest.mark.parametrize(
    "rows,block",
    [(1000, 40), (1320, 40), (304, 16), (8, 8), (1024, 32), (2048, 32), (104, 8), (40, 40), (48, 24), (200, 40)],
)
def test_block_height_is_read_off_the_tile(rows, block):
    assert sk._node_block(rows) == block
    for height in (block, sk._node_block(rows, sk._PLACE_ROWS)):
        assert rows % height == 0 and height % 8 == 0  # no row is seen twice, no block splits a vreg


@pytest.mark.parametrize("least", [True, False], ids=["least_key", "largest_score"])
def test_fold_best_keeps_the_later_row_on_a_tie(least):
    """Two blocks of 16 rows folded into the running best: per (sublane,
    lane) position the survivor is the best rank's highest slot among the
    rows that may win, with what rode along."""
    rng = np.random.default_rng(5)
    lanes = 128
    hi = rng.integers(0, 3, size=(32, lanes)).astype(np.int32)
    lo = rng.integers(0, 2, size=(32, lanes)).astype(np.int32)
    ok = rng.random((32, lanes)) < 0.8
    slots = np.broadcast_to(np.arange(32, dtype=np.int32)[:, None], (32, lanes))
    rode = rng.integers(0, 1000, size=(32, lanes)).astype(np.int32)
    fill = np.int32(2**31 - 1 if least else -1)
    run = tuple(jnp.full((8, lanes), v, jnp.int32) for v in (fill, fill, -1, -1))
    for b in range(2):
        part = slice(16 * b, 16 * b + 16)
        run = sk._fold_best(
            run, (jnp.asarray(hi[part]), jnp.asarray(lo[part])), jnp.asarray(ok[part]),
            [jnp.asarray(slots[part]), jnp.asarray(rode[part])], least,
        )
    got_hi, got_lo, got_slot, got_rode = (np.asarray(x) for x in run)
    for pos in range(8):
        for lane in range(lanes):
            rows = [r for r in range(pos, 32, 8) if ok[r, lane]]
            if not rows:
                assert got_slot[pos, lane] == -1
                continue
            rank = [(hi[r, lane], lo[r, lane]) for r in rows]
            best = min(rank) if least else max(rank)
            want = max(r for r, x in zip(rows, rank) if x == best)
            assert (got_hi[pos, lane], got_lo[pos, lane]) == best
            assert got_slot[pos, lane] == want and got_rode[pos, lane] == rode[want, lane]


def _tied_rows(n: int, block: int):
    """The rows a lane's best rank is tied over, by lane: (the last row of a
    block, the first of the next), (three blocks), (one position's rows)."""
    clip = lambda rows: sorted({min(r, n - 1) for r in rows})
    return {0: clip([block - 1, block]), 1: clip([3, block + 3, 2 * block + 5]), 5: clip([1, 9, 17])}


def _case(arm: str, n: int, k: int, seed: int):
    """One tile of LANES clusters x n nodes and k candidates a cluster, as
    numpy arrays in the wrappers' row-major convention."""
    spec = ARMS[arm]
    rng = np.random.default_rng(seed)
    unit_c, unit_r = UNITS
    block = sk._node_block(-(-n // 8) * 8)
    C = LANES
    # Capacities of four shapes, in whole units; frees below them.
    shape = rng.integers(0, 4, size=(C, n))
    cap_cpu = (np.array([8, 16, 32, 64])[shape] * unit_c).astype(np.int32)
    cap_ram = (np.array([16, 32, 64, 128])[shape] * unit_r).astype(np.int32)
    cpu = (rng.integers(1, 8, size=(C, n)) * cap_cpu // 8 // unit_c * unit_c).astype(np.int32)
    ram = (rng.integers(1, 8, size=(C, n)) * cap_ram // 8 // unit_r * unit_r).astype(np.int32)
    alive = rng.random((C, n)) < 0.9
    node_bits = rng.integers(0, 8, size=(C, n)).astype(np.int32)  # three labels
    node_bits |= (rng.random((C, n)) < 0.2).astype(np.int32) << 4  # a hard taint
    if spec.get("soft"):
        node_bits |= (rng.random((C, n)) < 0.3).astype(np.int32) << pipeline.SOFT_TAINT_TOP_BIT
    domain = rng.integers(-1, Z, size=(C, n)).astype(np.int32)
    rc = (rng.integers(1, 4, size=(C, k)) * unit_c).astype(np.int32)
    rr = (rng.integers(1, 6, size=(C, k)) * unit_r).astype(np.int32)
    valid = np.ones((C, k), bool)
    want = rng.choice(np.array([0, 1, 2, 5], np.int32), size=(C, k))
    forbid = rng.choice(np.array([0, 1 << 4, -(2**31), -(2**31) | (1 << 4)], np.int64), size=(C, k)).astype(np.int32)
    # The rule lanes: identical best nodes, empty and whole, every label.
    for lane, rows in _tied_rows(n, block).items():
        alive[lane, rows] = True
        cap_cpu[lane, rows], cap_ram[lane, rows] = 64 * unit_c, 128 * unit_r
        cpu[lane, rows], ram[lane, rows] = 64 * unit_c, 128 * unit_r
        node_bits[lane, rows] = 7
        domain[lane, rows] = 0
        want[lane], forbid[lane] = 0, 0
    # Lane 2: nothing fits but the last real row; lane 3: nothing fits.
    for lane in (2, 3):
        cpu[lane], ram[lane] = unit_c, unit_r
        rc[lane], rr[lane] = 2 * unit_c, 2 * unit_r
        want[lane], forbid[lane] = 0, 0
    alive[2, n - 1] = True
    cap_cpu[2, n - 1], cap_ram[2, n - 1] = 64 * unit_c, 128 * unit_r
    cpu[2, n - 1], ram[2, n - 1] = 64 * unit_c, 128 * unit_r
    node_bits[2, n - 1] = 7
    valid[4] = False  # lane 4: nothing to decide
    # Lane 7 names a label no node carries: the labels alone refuse it.
    want[7, 0], forbid[7, 0] = 8, -(2**31)
    case = dict(alive=alive, cpu=cpu, ram=ram, valid=valid, rc=rc, rr=rr, spread=None, affinity=None, kube=None)
    if spec.get("spread"):
        counts = rng.integers(0, 3, size=(C, G, Z)).astype(np.int32)
        limits = rng.integers(1, 3, size=(C, G, Z)).astype(np.int32)
        zone_alive = np.ones((C, Z), bool)
        group = rng.integers(-1, G, size=(C, k)).astype(np.int32)
        bits = rng.integers(0, 2**G, size=(C, k)).astype(np.int32)
        group[:6] = -1  # the rule lanes' pods carry no constraint; the random lanes' do
        case["spread"] = (domain, counts, limits, zone_alive, group, bits)
    if spec.get("affinity"):
        case["affinity"] = (node_bits, want, forbid)
    if spec.get("kube"):
        soft = ()
        if spec.get("soft"):
            prefer = [rng.choice(np.array([1, 2, 4, -(2**31)], np.int64), size=(C, k)).astype(np.int32) for _ in range(2)]
            weights = (rng.integers(1, 100, size=(C, k)) | (rng.integers(1, 100, size=(C, k)) << pipeline.SOFT_WEIGHT_BITS)).astype(np.int32)
            soft_forbid = (rng.integers(0, 2, size=(C, k)) << pipeline.SOFT_TAINT_TOP_BIT).astype(np.int32)
            soft = (*prefer, weights, soft_forbid)
        case["kube"] = (cap_cpu, cap_ram, *soft)
    return case


def whole_axis_cycle(profile, alive, cpu, ram, valid, rc, rr, spread=None, affinity=None, kube=None):
    """step.decide_scan's body, candidate by candidate: pipeline's functions
    over whole (C, N) rows with the node axis reduced at once (axis 1)."""
    C, N = cpu.shape
    alive, cpu, ram = jnp.asarray(alive), jnp.asarray(cpu), jnp.asarray(ram)
    iota_n = jnp.arange(N, dtype=jnp.int32)[None, :]
    rows1 = jnp.arange(C)
    out = dict(assign=[], fit_any=[], best=[], zbest=[], sflags=[], aflags=[], kflags=[])
    if spread is not None:
        domain, counts, limits, zone_alive, groups, bitss = (jnp.asarray(x) for x in spread)
        tiles, limit_tiles = pipeline.spread_tiles(counts), pipeline.spread_tiles(limits)
        zalive_t = pipeline.spread_alive_tile(zone_alive)
    if affinity is not None:
        node_bits, *term_planes, forbids = (jnp.asarray(x) for x in affinity)
    if kube is not None:
        cap_cpu, cap_ram, *soft_planes = (jnp.asarray(x) for x in kube)
        caps = pipeline.integer_nodes(cap_cpu, cap_ram, profile.units)
    for k in range(valid.shape[1]):
        ok, req_cpu, req_ram = jnp.asarray(valid[:, k]), jnp.asarray(rc[:, k]), jnp.asarray(rr[:, k])
        nodes_and_pod = (cpu, ram, req_cpu[:, None], req_ram[:, None])
        facts = None
        if spread is not None:
            group, bits = groups[None, :, k], bitss[None, :, k]
            zone_ok, constrained, closed = pipeline.spread_zone_ok(list(tiles), limit_tiles, zalive_t, group, bits)
            facts = NodeFacts(spread_ok=pipeline.spread_node_mask(domain.T, zone_ok, constrained, Z).T)
        if affinity is not None:
            terms, forbid = [t[:, k, None] for t in term_planes], forbids[:, k, None]
            rest = pipeline.profile_fit_mask(profile, alive, *nodes_and_pod, facts)
            affinity_ok, taints_ok = pipeline.affinity_node_masks(node_bits, terms, forbid)
            facts = (facts or NodeFacts())._replace(affinity_ok=affinity_ok, taints_ok=taints_ok)
        part = None
        if kube is not None:
            fit = pipeline.profile_fit_mask(profile, alive, *nodes_and_pod, facts)
            soft = None
            if soft_planes:
                *wants, weights, soft_forbid = (x[:, k, None] for x in soft_planes)
                soft = SoftFacts(node_bits, tuple(wants), weights, soft_forbid, profile.soft_taints)
            total, part, soft_attempt = pipeline.integer_scores(profile, fit, *nodes_and_pod, caps, soft, axis=1)
            best = pipeline.integer_best_node(total, True, iota_n, axis=1)[:, 0]
        elif profile.exact_bits:
            fit = pipeline.profile_fit_mask(profile, alive, *nodes_and_pod, facts)
            hi, lo = pipeline.exact_least_allocated_key(fit, *nodes_and_pod, profile.exact_bits)
            best = pipeline.exact_best_node(hi, lo, True, iota_n, axis=1)[:, 0]
        else:
            fit, score = pipeline.profile_fit_score(profile, alive, *nodes_and_pod, facts)
            best = jnp.int32(N - 1) - jnp.argmax(score[:, ::-1], axis=1).astype(jnp.int32)
        any_fit = fit.any(axis=1)
        assign = ok & any_fit
        cpu = cpu.at[rows1, best].add(jnp.where(assign, -req_cpu, 0))
        ram = ram.at[rows1, best].add(jnp.where(assign, -req_ram, 0))
        out["assign"].append(assign), out["fit_any"].append(any_fit), out["best"].append(best)
        if spread is not None:
            zbest = jnp.where(assign, domain[rows1, best], jnp.int32(-1))
            tiles = tuple(pipeline.spread_place(list(tiles), zbest[None, :], assign[None, :], bits))
            out["zbest"].append(zbest)
            out["sflags"].append((assign & constrained[0]).astype(jnp.int32) + 2 * (assign & closed[0]).astype(jnp.int32))
        if affinity is not None:
            attempt = ok & pipeline.affinity_names_nodes(forbid[:, 0])
            out["aflags"].append(attempt.astype(jnp.int32) + 2 * (attempt & ~any_fit & rest.any(axis=1)).astype(jnp.int32))
        if part is not None:
            chosen = assign[:, None] & (iota_n == best[:, None])
            soft_attempt = ok & soft_attempt[:, 0]
            honoured = pipeline.soft_honoured(part, fit, chosen, axis=1)[:, 0]
            out["kflags"].append(soft_attempt.astype(jnp.int32) + 2 * (soft_attempt & honoured).astype(jnp.int32))
    got = {name: np.stack([np.asarray(x) for x in xs], axis=1) for name, xs in out.items() if xs}
    got["cpu"], got["ram"] = np.asarray(cpu), np.asarray(ram)
    if spread is not None:
        got["counts"] = np.stack([np.asarray(t)[:Z].T for t in tiles], axis=1)
    return got


def _jnp(x):
    return None if x is None else tuple(jnp.asarray(a) for a in x)


def _check_rules_were_hit(n, want, case):
    """The tiles do what they were built for (else the case proves little)."""
    block = sk._node_block(-(-n // 8) * 8)
    for lane, rows in _tied_rows(n, block).items():
        assert want["best"][lane, 0] == rows[-1], (lane, rows)  # the highest tied slot wins
        if len(rows) > 1 and n > 8:
            assert want["best"][lane, 1] in rows[:-1], (lane, rows)  # then the tie moves down
    assert want["assign"][2].all() and (want["best"][2] == n - 1).all()
    assert not want["assign"][3].any() and not want["fit_any"][3].any()
    assert not want["assign"][4].any()
    if case["affinity"] is not None:
        assert want["aflags"][7, 0] == 3  # named its nodes, and the labels alone refused it


@pytest.mark.parametrize("n", WIDTHS)
@pytest.mark.parametrize("arm", ARMS)
def test_candidate_kernel_equals_the_whole_axis_sweep(arm, n):
    profile = ARMS[arm]["profile"]
    case = _case(arm, n, K, seed=n)
    want = whole_axis_cycle(profile, **case)
    _check_rules_were_hit(n, want, case)
    assign, fit_any, best, cpu, ram, *rest = sk.fused_schedule_cycle(
        *(jnp.asarray(case[x]) for x in ("alive", "cpu", "ram", "valid", "rc", "rr")),
        interpret=True, profile=profile,
        spread=_jnp(case["spread"]), affinity=_jnp(case["affinity"]), kube=_jnp(case["kube"]),
    )
    got = dict(assign=assign, fit_any=fit_any, best=best, cpu=cpu, ram=ram)
    if case["spread"] is not None:
        got["zbest"], got["sflags"], got["counts"], *rest = rest
    if case["affinity"] is not None:
        got["aflags"], *rest = rest
    if "kflags" in want:
        got["kflags"], *rest = rest
    assert not rest and set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(got[name]), want[name], err_msg=f"{arm} {n}: {name}")


def _queue_in_slot_order(C: int, P: int):
    """Pod planes under which a cluster's queue order is its slot order and
    every pod is eligible: the kernels that select pick candidate k = pod k."""
    eligible = np.ones((C, P), bool)
    eligible[4] = False  # the lane with nothing left: slot -1 at every step
    zeros = np.zeros((C, P), np.int32)
    qseq = np.broadcast_to(np.arange(P, dtype=np.int32), (C, P))
    return eligible, zeros, zeros.astype(np.float32), qseq


@pytest.mark.parametrize("arm", ARMS)
def test_select_kernel_equals_the_whole_axis_sweep(arm):
    n, profile = 300, ARMS[arm]["profile"]
    case = _case(arm, n, K, seed=7 * n)
    want = whole_axis_cycle(profile, **case)
    eligible, qwin, qoff, qseq = _queue_in_slot_order(LANES, K)
    cand, valid, assign, fit_any, best, cpu, ram, *rest = sk.fused_select_schedule_cycle(
        *(jnp.asarray(x) for x in (case["alive"], case["cpu"], case["ram"], eligible, qwin, qoff, qseq, case["rc"], case["rr"])),
        k_pods=K, interpret=True, profile=profile,
        spread=_jnp(case["spread"]), affinity=_jnp(case["affinity"]), kube=_jnp(case["kube"]),
    )
    np.testing.assert_array_equal(np.asarray(valid), case["valid"])
    np.testing.assert_array_equal(np.asarray(cand)[np.asarray(valid)], np.broadcast_to(np.arange(K), (LANES, K))[case["valid"]])
    got = dict(assign=assign, cpu=cpu, ram=ram)
    for name in ("fit_any", "best"):  # an invalid row's are zeroed, and nothing reads them
        got[name] = np.where(case["valid"], np.asarray(locals()[name]), want[name])
    if case["spread"] is not None:
        zbest, sflags, got["counts"], *rest = rest
        got["zbest"] = np.where(case["valid"], np.asarray(zbest), want["zbest"])
        got["sflags"] = sflags
    if case["affinity"] is not None:
        got["aflags"], *rest = rest
    if "kflags" in want:
        got["kflags"], *rest = rest
    assert not rest and set(got) == set(want)
    for name in want:
        np.testing.assert_array_equal(np.asarray(got[name]), want[name], err_msg=f"{arm}: {name}")


@pytest.mark.parametrize("n", [1000, 300])
@pytest.mark.parametrize("arm", ARMS)
def test_megakernel_equals_the_whole_axis_sweep(arm, n):
    """The megakernel commits what it decides: a pod's node and phase, the
    allocatables, and the counters it folds where the other two kernels
    return a decision's flags."""
    profile, P = ARMS[arm]["profile"], K
    case = _case(arm, n, P, seed=3 * n)
    want = whole_axis_cycle(profile, **case)
    eligible, qwin, qoff, qseq = _queue_in_slot_order(LANES, P)
    waited = np.zeros((LANES, P), np.float32)
    phase = np.zeros((LANES, P), np.int32)
    node = np.full((LANES, P), -1, np.int32)
    times = np.full((LANES, K), 0.25, np.float32)
    cpu, ram, phase_o, node_o, start, park, stats, *rest = sk.fused_select_cycle_commit(
        *(jnp.asarray(x) for x in (
            case["alive"], case["cpu"], case["ram"], eligible, qwin, qoff, qseq, case["rc"], case["rr"],
            waited, phase, node, times, times, times,
        )),
        k_pods=K, interpret=True, profile=profile,
        spread=_jnp(case["spread"]), affinity=_jnp(case["affinity"]), kube=_jnp(case["kube"]),
    )
    np.testing.assert_array_equal(np.asarray(cpu), want["cpu"], err_msg="cpu")
    np.testing.assert_array_equal(np.asarray(ram), want["ram"], err_msg="ram")
    assign, parked = want["assign"], case["valid"] & ~want["fit_any"]
    np.testing.assert_array_equal(np.asarray(node_o), np.where(assign, want["best"], -1), err_msg="node")
    np.testing.assert_array_equal(
        np.asarray(phase_o),
        np.where(assign, sk._PHASE_RUNNING, np.where(parked, sk._PHASE_UNSCHEDULABLE, 0)), err_msg="phase",
    )
    np.testing.assert_array_equal(np.isfinite(np.asarray(start)), assign)
    np.testing.assert_array_equal(np.isfinite(np.asarray(park)), parked)
    np.testing.assert_array_equal(np.asarray(stats)[:, 0], assign.sum(axis=1))

    def counted(flags):
        return np.stack([(flags & 1).sum(axis=1), (flags >> 1).sum(axis=1)], axis=1)

    if case["spread"] is not None:
        zone, sstats, *rest = rest
        np.testing.assert_array_equal(np.asarray(zone), np.where(assign, want["zbest"], -2), err_msg="zone")
        np.testing.assert_array_equal(np.asarray(sstats), counted(want["sflags"]), err_msg="spread counters")
    if case["affinity"] is not None:
        astats, *rest = rest
        np.testing.assert_array_equal(np.asarray(astats), counted(want["aflags"]), err_msg="label filter counters")
    if "kflags" in want:
        kstats, *rest = rest
        np.testing.assert_array_equal(np.asarray(kstats), counted(want["kflags"]), err_msg="label score counters")
        assert 0 < want["kflags"].sum()
    assert not rest


# The scan path reduces the whole node axis with pipeline's composed functions
# (integer_scores with its own `most`, exact_best_node, soft_honoured at
# axis 1) and must lower the text it lowered before the kernels took the axis
# in blocks: sha256 of a toy build's window program without Pallas
# (window_program_digest.lowered_window_program), read on the parent of PR 51.
SCAN_PROGRAMS = {
    "kube_default": ("integer", "c2180dee44ea72c1eb03cb73262489f507e861dca185bef9442c5f24ee8ab583"),
    "node_pools": ("exact", "d7abf88db0a8c576cd0e023a7e364e3dd5c37de17feea8e283de4d86ffa4eca9"),
    "default": ("exact", "491691a55e743a75489b6db59dcdb047e0890972855778959d3e48e6f3fe7e37"),
}


@pytest.mark.parametrize("profile", SCAN_PROGRAMS)
def test_the_scan_path_lowers_the_text_it_lowered(profile):
    import dataclasses
    import hashlib

    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubescore_traces import kubescore_traces
    from pools_traces import pools_traces
    from test_pending_free import config_with
    from window_program_digest import lowered_window_program

    cluster, workload = (kubescore_traces if profile == "kube_default" else pools_traces)(3, 20, 60)
    sim = build_batched_from_traces(
        dataclasses.replace(config_with("zero"), scheduler_profile=profile),
        cluster.convert_to_simulator_events(), workload.convert_to_simulator_events(), n_clusters=2,
    )
    try:
        ranking, pinned = SCAN_PROGRAMS[profile]
        formulation = sim.kernel_formulation()
        assert (formulation["cycle"], formulation["ranking"]) == ("scan", ranking)
        assert hashlib.sha256(lowered_window_program(sim).encode()).hexdigest() == pinned
    finally:
        sim.close()
