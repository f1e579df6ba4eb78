"""Pallas TPU kernels for the batched simulation's hot loop.

Five kernels, one layout: everything works TRANSPOSED — clusters ride the
128-wide lane dimension (one grid program per 128-cluster tile) and
node/pod/candidate slots ride sublanes, because Mosaic only allows dynamic
slicing (`pl.ds(k, 1)`) on sublane dimensions, and per-lane one-hot
compares replace data-dependent scatters (TPU scatter cost is per-index).
Every kernel carries a data-dependent early exit at the tile's actual work
count, which lax.scan formulations cannot express.

- `_cycle_kernel` (fused_schedule_cycle): the K-pod scheduling loop — pod
  k's compiled-profile filter mask + weighted score (batched/pipeline.py;
  the default profile is Fit + LeastAllocatedResources, reference:
  src/core/scheduler/plugin.rs:33-63) + last-wins argmax
  (kube_scheduler.rs:140-150) must see the allocatable updates of pods
  0..k-1; the node tile stays pinned in VMEM across the loop (one HBM
  round-trip per cycle instead of K). The profile is a kernel static —
  each profile compiles its own kernel, selected at engine build.
- `_select_cycle_kernel` (fused_select_schedule_cycle): the same loop with
  candidate EXTRACTION in-kernel via an iterated per-lane lexicographic
  argmin over the queue keys — the dense-batch default, eliminating the
  (C, P) 3-key sort.
- `_free_kernel` (fused_free_resources): freed pods' requests returned to
  their nodes via one-hot adds + the finished pods' duration-estimator fold.
- `_event_kernel` (fused_event_scatter): one chunk of due trace events
  applied to the per-slot accumulators (five XLA scatters replaced).
- `_commit_kernel` (fused_commit_scatter): the cycle's decisions scattered
  back into the (P,) pod arrays.

The decision kernels return per-candidate outputs; the cheap (C,)-shaped
timing/metric mechanics stay in step.py where they replicate the scan
path's float-op ordering bit for bit. Parity: interpret-mode unit tests +
full-sim equivalence in tests/test_pallas_kernel.py, on-hardware check
against the lax.scan engine in chip_smoke.py.
"""

from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# The compiled scheduler-profile pipeline: profiles lower into the decision
# kernels as statics. pipeline.py imports only core.scheduler (NOT
# batched/state.py), so kernel-only users still dodge the x64 config flip.
from kubernetriks_tpu.batched.pipeline import (
    DEFAULT_PROFILE,
    SPREAD_ZONE_TILE,
    exact_best_node,
    exact_least_allocated_key,
    profile_fit_mask,
    profile_fit_score,
    spread_alive_tile,
    spread_node_mask,
    spread_place,
    spread_tiles,
    spread_zone_ok,
)

_NEG_INF = float(np.float32(-np.inf))

_LANE = 128  # clusters per grid program (lane tile)
_SUB = 8  # f32/i32 sublane tile



def default_enabled() -> bool:
    """Use the kernel when running on a real TPU backend unless overridden
    via KUBERNETRIKS_PALLAS=0/1."""
    from kubernetriks_tpu.flags import flag_tristate

    env = flag_tristate("KUBERNETRIKS_PALLAS")
    if env is not None:
        return env
    return jax.default_backend() == "tpu"


# Conservative per-core VMEM budget for the kernel's resident blocks; real
# v5e VMEM is ~128 MiB but leave headroom for Mosaic's own buffers and the
# surrounding fusion.
_VMEM_BUDGET_BYTES = 12 * 1024 * 1024

# Every pallas_call below carries name= its jitted wrapper's name. XLA names
# the custom call after the last scope of its op_name, so a device trace shows
# the kernel (the custom call alone; the wrapper's pads and transposes are ops
# of their own) as `<name>.N` — what benchmark/kernel_names.json matches. The
# explicit name keeps that event's name when a wrapper is renamed or inlined.


# Lane-major hot state (KTPU_LANE_MAJOR; state.NODE_HOT_LEAVES): every
# wrapper below historically transposed its node-shaped operands into the
# kernels' one true layout (clusters on lanes) and transposed the node
# outputs back — pallas_call pins default layouts, so XLA materializes each
# of those transposes as a copy (~1.2 ms/window of marshalling at the
# composed shape). With nodes_lane_major=True the caller already carries the
# hot node leaves as (N, C): the wrapper pads WITHOUT transposing (a no-op
# copy at tile-aligned shapes) and returns node outputs lane-major. Pod-,
# candidate- and event-shaped operands keep the row-major convention — their
# producers/consumers in step.py are row-major-shaped sorts and gathers. The
# event kernel's accumulators are the exception: they live only inside the
# event chunk loop, which carries all five (the three pod planes too) padded
# in the kernel's layout whatever nodes_lane_major says
# (event_accumulators / event_accumulators_unpack).
def _prep_node(x, lane_major: bool, n_sub: int, n_lane: int, fill):
    x = x.astype(jnp.int32)
    if not lane_major:
        x = x.T
    return _pad_axis(_pad_axis(x, 0, n_sub, fill), 1, n_lane, fill)


def _unprep_node(x, lane_major: bool, n: int, c: int):
    out = x[:n, :c]
    return out if lane_major else out.T


def _spread_table_rows(spread_shape) -> int:
    """Rows of the spread filter's small blocks in a decision kernel of a
    build of `spread_shape` = (G, Z), 0 for a build without it: the count
    table in and out and its limits (G tiles each), the live domains and a
    stats tile."""
    if spread_shape is None:
        return 0
    return (3 * spread_shape[0] + 2) * SPREAD_ZONE_TILE


def kernel_fits(n_nodes: int, k_pods: int, spread_shape=None) -> bool:
    """Whether one grid program's VMEM blocks (3 node blocks in and 2 out of
    (Np, 128), 3 candidate blocks in and 3 out of (Kp, 128), all int32; with
    the spread filter one node block, four candidate blocks and the table
    more) fit the budget; callers fall back to the lax.scan
    formulation when they don't."""
    np_pad = -(-n_nodes // _SUB) * _SUB
    kp_pad = -(-k_pods // _SUB) * _SUB
    s = int(spread_shape is not None)
    resident = (
        (5 + s) * np_pad + (6 + 4 * s) * kp_pad + _spread_table_rows(spread_shape)
    ) * _LANE * 4
    return resident <= _VMEM_BUDGET_BYTES


def _spread_tiles(ref, n_workloads: int):
    return [
        ref[g * SPREAD_ZONE_TILE : (g + 1) * SPREAD_ZONE_TILE, :]
        for g in range(n_workloads)
    ]


def _spread_in_specs(spread_shape, node_spec, side_spec, side_blocks: int):
    """BlockSpecs of the spread filter's operands, in the order every
    decision kernel takes them after its own: the domain plane, the count
    table, the limits, the live domains, then the pod's (or candidate's)
    workload and match bits."""
    rows = spread_shape[0] * SPREAD_ZONE_TILE
    table = pl.BlockSpec((rows, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    tile = pl.BlockSpec((SPREAD_ZONE_TILE, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    return [node_spec, table, table, tile] + [side_spec] * side_blocks, table, tile


def _spread_operands(spread, nodes_lane_major: bool, Np: int, Cp: int, side_rows: int, node_spec, side_spec):
    """What a decision kernel's wrapper adds to its pallas_call for the
    spread filter: ((G, Z), the padded operands, their in_specs, the table's
    and a tile's BlockSpec and the table's shape), all empty for
    `spread` None. `spread` = (domain (C, N) | (N, C), counts (C, G, Z),
    limits (C, G, Z), zone_alive (C, Z), and the pods' or candidates'
    workload and match bits, `side_rows` rows in the kernel layout). Padded
    nodes carry no key, padded domains are never alive, padded lanes hold
    nothing."""
    if spread is None:
        return None, (), [], None, None, None
    domain, counts, limits, zone_alive, group, bits = spread

    def table(x):
        return _pad_axis(jnp.concatenate(spread_tiles(x), axis=0), 1, Cp, 0)

    def side(x, fill):
        return _pad_axis(_pad_axis(x.astype(jnp.int32).T, 0, side_rows, fill), 1, Cp, fill)

    args = (
        _prep_node(domain, nodes_lane_major, Np, Cp, -1),
        table(counts),
        table(limits),
        _pad_axis(spread_alive_tile(zone_alive).astype(jnp.int32), 1, Cp, 0),
        side(group, -1),
        side(bits, 0),
    )
    shape = tuple(counts.shape[1:])
    in_specs, table_spec, tile_spec = _spread_in_specs(shape, node_spec, side_spec, 2)
    return shape, args, in_specs, table_spec, tile_spec, jax.ShapeDtypeStruct(args[1].shape, jnp.int32)


def _fit_score_place(profile, alive, node_ok, iota_n, cpu, ram, rc, rr, valid, spread=None):
    """ONE in-kernel definition of the per-candidate decision core shared by
    _cycle_kernel, _select_cycle_kernel and _select_cycle_commit_kernel:
    the compiled profile's filter mask + weighted score
    (batched/pipeline.py — the default profile is Fit +
    LeastAllocatedResources, reference plugin.rs:33-63) + last-max-wins
    argmax (ties resolve to the highest node slot, matching the
    reference's `>=` sweep over name-sorted nodes) + the allocatable
    update for the placed node. `profile` is a kernel STATIC (a
    pipeline.CompiledProfile closed over via functools.partial); its
    expressions inline into the kernel body like the shape statics do.
    Inputs: (Np, LC) node tiles, (1, LC) candidate requests/validity.
    Returns (assign (1, LC) bool, any_fit (1, LC) bool, best (1, LC) i32,
    new_cpu (Np, LC), new_ram (Np, LC)).

    `spread` (a build whose pods are held to topology-spread constraints;
    pipeline.spread_*) = (domain (Np, LC) node plane, the count table's G
    tiles, the limits' G tiles, zone_alive (8, LC) bool, n_domains, the
    candidate's workload and match bits (1, LC)): the SECOND thing the core
    carries across a cycle's placements. The table is read by this
    candidate's filter and returned with its placement added; a sixth result
    then follows the five: (new tiles, the placed node's domain (1, LC),
    assigned & constrained, assigned & a live domain was closed)."""
    i0 = jnp.int32(0)
    neg1 = jnp.int32(-1)

    spread_ok = None
    if spread is not None:
        domain, tiles, limits, zone_alive, n_domains, group, bits = spread
        zone_ok, constrained, closed = spread_zone_ok(tiles, limits, zone_alive, group, bits)
        spread_ok = spread_node_mask(domain, zone_ok, constrained, n_domains)
    if profile.exact_bits:
        fit = profile_fit_mask(profile, alive, cpu, ram, rc, rr, spread_ok)
        hi, lo = exact_least_allocated_key(fit, cpu, ram, rc, rr, profile.exact_bits)
        best = exact_best_node(hi, lo, node_ok, iota_n, axis=0)
    else:
        fit, score = profile_fit_score(profile, alive, cpu, ram, rc, rr, spread_ok)
        max_score = jnp.max(score, axis=0, keepdims=True)
        best = jnp.max(
            jnp.where((score == max_score) & node_ok, iota_n, neg1),
            axis=0,
            keepdims=True,
        )
    # any() lowers to an i1 reduction Mosaic rejects; reduce in i32. Padded
    # slots never fit (alive is 0 there).
    any_fit = jnp.max(fit.astype(jnp.int32), axis=0, keepdims=True) > i0
    assign = valid & any_fit
    upd = assign & (iota_n == best)
    new_cpu = cpu - jnp.where(upd, rc, i0)
    new_ram = ram - jnp.where(upd, rr, i0)
    if spread is None:
        return assign, any_fit, best, new_cpu, new_ram
    zbest = jnp.max(jnp.where(upd, domain, neg1), axis=0, keepdims=True)
    placed = (spread_place(tiles, zbest, assign, bits), zbest, assign & constrained, assign & closed)
    return assign, any_fit, best, new_cpu, new_ram, placed


def _spread_step(refs, n_workloads: int, n_domains: int, group, bits):
    """The `spread` argument of _fit_score_place from a kernel's refs
    (domain, the carried table, limits, live domains) and the candidate's
    workload and bits."""
    domain_ref, table_ref, limit_ref, zalive_ref = refs
    return (
        domain_ref[:],
        _spread_tiles(table_ref, n_workloads),
        _spread_tiles(limit_ref, n_workloads),
        zalive_ref[:] != jnp.int32(0),
        n_domains,
        group,
        bits,
    )


def _spread_store(table_ref, tiles) -> None:
    for g, tile in enumerate(tiles):
        table_ref[g * SPREAD_ZONE_TILE : (g + 1) * SPREAD_ZONE_TILE, :] = tile


def _spread_store_decision(table_ref, zbest_out, sflag_out, k, placed) -> None:
    """Candidate k's spread results of _fit_score_place into the kernel's
    outputs: the table, the placed domain, and the two facts as one int32
    (bit 0 the assigned pod carried a constraint, bit 1 the skew had closed
    a live domain for it)."""
    tiles, zbest, constrained, closed = placed
    _spread_store(table_ref, tiles)
    zbest_out[pl.ds(k, 1), :] = zbest
    sflag_out[pl.ds(k, 1), :] = constrained.astype(jnp.int32) + jnp.int32(2) * closed.astype(
        jnp.int32
    )


def _cycle_kernel(
    n_real: int,
    k_pods: int,
    profile,        # pipeline.CompiledProfile (kernel static)
    spread_shape,   # (G, Z) static, None without the spread filter
    alive_ref,      # (Np, LC) int32
    alloc_cpu_ref,  # (Np, LC) int32
    alloc_ram_ref,  # (Np, LC) int32
    valid_ref,      # (Kp, LC) int32
    req_cpu_ref,    # (Kp, LC) int32
    req_ram_ref,    # (Kp, LC) int32
    *refs,
):
    # refs: with the spread filter the inputs domain (Np, LC), table and
    # limits (G*8, LC), live domains (8, LC), the candidates' workload and
    # match bits (Kp, LC); then the outputs cpu, ram (Np, LC), assign,
    # fitany, best (Kp, LC); with the filter the carried table (G*8, LC) and
    # the candidates' placed domain and flags (Kp, LC).
    if spread_shape is not None:
        domain_ref, table_in, limit_ref, zalive_ref, cgroup_ref, cbits_ref = refs[:6]
        refs = refs[6:]
        table_out, zbest_out, sflag_out = refs[5:]
    cpu_out, ram_out, assign_out, fitany_out, best_out = refs[:5]
    # All literals are explicitly typed: with jax_enable_x64 on (the batched
    # path's time arrays are f64), bare Python scalars trace as weak i64/f64
    # constants, which Mosaic cannot lower inside the kernel.
    i0 = jnp.int32(0)

    cpu_out[:] = alloc_cpu_ref[:]
    ram_out[:] = alloc_ram_ref[:]
    alive = alive_ref[:] != i0  # (Np, LC)
    iota = jax.lax.broadcasted_iota(jnp.int32, alive.shape, 0)
    node_ok = iota < jnp.int32(n_real)  # padded sublanes are never real nodes

    # Outputs must be fully initialized even for skipped iterations.
    assign_out[:] = jnp.zeros_like(assign_out)
    fitany_out[:] = jnp.zeros_like(fitany_out)
    best_out[:] = jnp.zeros_like(best_out)
    if spread_shape is not None:
        table_out[:] = table_in[:]
        zbest_out[:] = jnp.zeros_like(zbest_out)
        sflag_out[:] = jnp.zeros_like(sflag_out)

    # The loop only needs to reach the tile's last valid candidate — a
    # data-dependent early exit the lax.scan formulation cannot express.
    # prepare_cycle sorts eligible pods first, so valid is a per-cluster
    # prefix and typical cycles have far fewer pending pods than the static
    # K budget. Skipped iterations leave assign/fitany/best zeroed, which the
    # callers never read (they gate every consumer on `valid`).
    iota_k = jax.lax.broadcasted_iota(jnp.int32, (valid_ref.shape[0], valid_ref.shape[1]), 0)
    k_live = jnp.max(jnp.where(valid_ref[:] != i0, iota_k + jnp.int32(1), i0))
    k_bound = jnp.minimum(k_live, jnp.int32(k_pods))

    def body(k):
        req_cpu = req_cpu_ref[pl.ds(k, 1), :]  # (1, LC) int32
        req_ram = req_ram_ref[pl.ds(k, 1), :]
        valid = valid_ref[pl.ds(k, 1), :] != i0

        spread = None
        if spread_shape is not None:
            spread = _spread_step(
                (domain_ref, table_out, limit_ref, zalive_ref), *spread_shape,
                cgroup_ref[pl.ds(k, 1), :], cbits_ref[pl.ds(k, 1), :],
            )
        assign, any_fit, best, new_cpu, new_ram, *placed = _fit_score_place(
            profile, alive, node_ok, iota, cpu_out[:], ram_out[:],
            req_cpu, req_ram, valid, spread,
        )
        if placed:
            _spread_store_decision(table_out, zbest_out, sflag_out, k, placed[0])
        cpu_out[:] = new_cpu
        ram_out[:] = new_ram
        assign_out[pl.ds(k, 1), :] = assign.astype(jnp.int32)
        fitany_out[pl.ds(k, 1), :] = any_fit.astype(jnp.int32)
        best_out[pl.ds(k, 1), :] = best

    # An explicit i32-carried while loop: with jax_enable_x64 on, fori_loop
    # canonicalizes its induction variable to i64, which Mosaic cannot return
    # from the loop-body region.
    def loop_body(k):
        body(k)
        return k + jnp.int32(1)

    jax.lax.while_loop(lambda k: k < k_bound, loop_body, jnp.int32(0))


# The selection kernel asks Mosaic for a raised scoped-VMEM limit; its
# fits-check budget must stay at ~40% of that because Mosaic double-buffers
# the grid blocks.
_SELECT_VMEM_LIMIT = 100 * 1024 * 1024


def select_kernel_fits(n_nodes: int, n_pods: int, k_pods: int, spread_shape=None) -> bool:
    """Whether the selection+cycle kernel's VMEM blocks fit: 6 pod blocks of
    (Pp, 128) in + 1 pod scratch, 3 node blocks in + 2 out, 5 candidate
    output blocks, all int32, double-buffered across grid programs by
    Mosaic; with the spread filter one node block, two pod blocks, two
    candidate output blocks and the table more. The pod blocks dominate; the budget is more
    generous than the candidate kernel's because this kernel REPLACES the
    (C, P) lexsort and gathers, so its win grows with P (v5e VMEM is
    ~128 MiB/core)."""
    np_pad = -(-n_nodes // _SUB) * _SUB
    pp_pad = -(-n_pods // _SUB) * _SUB
    kp_pad = -(-k_pods // _SUB) * _SUB
    s = int(spread_shape is not None)
    resident = (
        (5 + s) * np_pad + (7 + 2 * s) * pp_pad + (5 + 2 * s) * kp_pad
        + _spread_table_rows(spread_shape)
    ) * _LANE * 4
    return 2 * resident <= int(0.8 * _SELECT_VMEM_LIMIT)


def _select_cycle_kernel(
    n_nodes: int,
    k_pods: int,
    profile,        # pipeline.CompiledProfile (kernel static)
    spread_shape,   # (G, Z) static, None without the spread filter
    alive_ref,      # (Np, LC) int32
    alloc_cpu_ref,  # (Np, LC) int32
    alloc_ram_ref,  # (Np, LC) int32
    elig_ref,       # (Pp, LC) int32 0/1
    qwin_ref,       # (Pp, LC) int32 queue_ts.win
    qoff_ref,       # (Pp, LC) int32 BITCAST of queue_ts.off (non-negative
                    #  f32, so the bit pattern orders identically to the float)
    qseq_ref,       # (Pp, LC) int32
    preq_cpu_ref,   # (Pp, LC) int32
    preq_ram_ref,   # (Pp, LC) int32
    *refs,
):
    # refs: with the spread filter the inputs domain (Np, LC), table and
    # limits (G*8, LC), live domains (8, LC), the pods' workload and match
    # bits (Pp, LC); then the outputs cpu, ram (Np, LC), cand (the selected
    # pod slot), valid, assign, fitany, best (Kp, LC); with the filter the
    # carried table (G*8, LC) and the decisions' placed domain and flags
    # (Kp, LC); last the scratch rem (Pp, LC): not-yet-selected eligible pods.
    if spread_shape is not None:
        domain_ref, table_in, limit_ref, zalive_ref, pgroup_ref, pbits_ref = refs[:6]
        refs = refs[6:]
        table_out, zbest_out, sflag_out = refs[7:10]
    cpu_out, ram_out, cand_out, valid_out, assign_out, fitany_out, best_out = refs[:7]
    rem_ref = refs[-1]
    """Fused queue selection + scheduling cycle: candidate k is extracted
    IN-KERNEL by an iterated per-lane lexicographic argmin over
    (queue win, off, seq) — exactly the sorted order of the batched
    ActiveQueue (step.lexsort_time_i32), seq unique per cluster, so the
    extraction is deterministic — then scheduled against the VMEM-resident
    node tile like _cycle_kernel. Replaces the (C, P) 3-key sort + top-K
    compaction gathers of prepare_cycle with O(live-queue-depth) passes,
    which is where dense shapes spend their fixed per-window cost."""
    i0 = jnp.int32(0)
    i1 = jnp.int32(1)
    neg1 = jnp.int32(-1)
    bigi = jnp.int32(np.iinfo(np.int32).max)

    cpu_out[:] = alloc_cpu_ref[:]
    ram_out[:] = alloc_ram_ref[:]
    alive = alive_ref[:] != i0
    iota_n = jax.lax.broadcasted_iota(jnp.int32, alive.shape, 0)
    node_ok = iota_n < jnp.int32(n_nodes)

    cand_out[:] = jnp.zeros_like(cand_out)
    valid_out[:] = jnp.zeros_like(valid_out)
    assign_out[:] = jnp.zeros_like(assign_out)
    fitany_out[:] = jnp.zeros_like(fitany_out)
    best_out[:] = jnp.zeros_like(best_out)
    rem_ref[:] = elig_ref[:]
    if spread_shape is not None:
        table_out[:] = table_in[:]
        zbest_out[:] = jnp.zeros_like(zbest_out)
        sflag_out[:] = jnp.zeros_like(sflag_out)

    iota_p = jax.lax.broadcasted_iota(jnp.int32, elig_ref.shape, 0)
    # Early exit: the deepest per-lane queue in this tile bounds the loop.
    depth = jnp.max(jnp.sum(elig_ref[:], axis=0, keepdims=True))
    k_bound = jnp.minimum(depth, jnp.int32(k_pods))

    def body(k):
        rem = rem_ref[:] != i0  # (Pp, LC)
        # Per-lane lexicographic argmin over (win, off-bits, seq).
        w = jnp.where(rem, qwin_ref[:], bigi)
        minw = jnp.min(w, axis=0, keepdims=True)
        m1 = rem & (qwin_ref[:] == minw)
        o = jnp.where(m1, qoff_ref[:], bigi)
        mino = jnp.min(o, axis=0, keepdims=True)
        m2 = m1 & (qoff_ref[:] == mino)
        s = jnp.where(m2, qseq_ref[:], bigi)
        mins = jnp.min(s, axis=0, keepdims=True)
        sel = m2 & (qseq_ref[:] == mins)  # exactly one row per non-empty lane

        seli = sel.astype(jnp.int32)
        slot = jnp.max(jnp.where(sel, iota_p, neg1), axis=0, keepdims=True)
        valid = slot >= i0  # (1, LC)
        rc = jnp.max(seli * preq_cpu_ref[:], axis=0, keepdims=True)
        rr = jnp.max(seli * preq_ram_ref[:], axis=0, keepdims=True)

        spread = None
        if spread_shape is not None:
            spread = _spread_step(
                (domain_ref, table_out, limit_ref, zalive_ref), *spread_shape,
                jnp.max(jnp.where(sel, pgroup_ref[:], neg1), axis=0, keepdims=True),
                jnp.max(seli * pbits_ref[:], axis=0, keepdims=True),
            )
        assign, any_fit, best, new_cpu, new_ram, *placed = _fit_score_place(
            profile, alive, node_ok, iota_n, cpu_out[:], ram_out[:],
            rc, rr, valid, spread,
        )
        if placed:
            _spread_store_decision(table_out, zbest_out, sflag_out, k, placed[0])
        cpu_out[:] = new_cpu
        ram_out[:] = new_ram
        cand_out[pl.ds(k, 1), :] = jnp.where(valid, slot, i0)
        valid_out[pl.ds(k, 1), :] = valid.astype(jnp.int32)
        assign_out[pl.ds(k, 1), :] = assign.astype(jnp.int32)
        fitany_out[pl.ds(k, 1), :] = any_fit.astype(jnp.int32)
        best_out[pl.ds(k, 1), :] = best
        rem_ref[:] = jnp.where(sel, i0, rem_ref[:])

    def loop_body(k):
        body(k)
        return k + i1

    jax.lax.while_loop(lambda k: k < k_bound, loop_body, jnp.int32(0))


@functools.partial(
    jax.jit,
    static_argnames=("k_pods", "interpret", "nodes_lane_major", "profile"),
)
def fused_select_schedule_cycle(
    alive: jnp.ndarray,      # (C, N) bool — (N, C) when nodes_lane_major
    alloc_cpu: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    alloc_ram: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    eligible: jnp.ndarray,   # (C, P) bool
    qwin: jnp.ndarray,       # (C, P) int32
    qoff: jnp.ndarray,       # (C, P) float32 (non-negative)
    qseq: jnp.ndarray,       # (C, P) int32
    pod_req_cpu: jnp.ndarray,  # (C, P) int32
    pod_req_ram: jnp.ndarray,  # (C, P) int32
    k_pods: int,
    interpret: bool = False,
    nodes_lane_major: bool = False,
    profile=None,  # pipeline.CompiledProfile; None = the default profile
    spread=None,  # (domain, counts, limits, zone_alive, pod group, pod bits)
):
    """Fused selection + scheduling loop in VMEM.

    Returns (cand (C,K) int32 pod slots, valid (C,K) bool, assign (C,K) bool,
    fit_any (C,K) bool, best (C,K) int32, new_alloc_cpu, new_alloc_ram) —
    valid rows identical to prepare_cycle's sorted top-K compaction followed
    by the lax.scan/_cycle_kernel loop (invalid rows are zeroed; every
    consumer gates on valid). With nodes_lane_major the node operands arrive
    and the allocatables return in (N, C) lane-major layout (no transposes
    at this boundary — see _prep_node). With `spread` (_spread_operands: the
    filter's four operands and the pods' (C, P) workload and match bits) two
    more follow:
    each decision's placed domain and spread flags, (C, K) int32."""
    C, P = eligible.shape
    N = alloc_cpu.shape[0] if nodes_lane_major else alloc_cpu.shape[1]
    K = k_pods
    Cp = -(-C // _LANE) * _LANE
    Np = -(-N // _SUB) * _SUB
    Pp = -(-P // _SUB) * _SUB
    Kp = -(-K // _SUB) * _SUB

    def prep(x, n_sub, fill):
        return _pad_axis(_pad_axis(x.astype(jnp.int32).T, 0, n_sub, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        alive_p = _prep_node(alive, nodes_lane_major, Np, Cp, 0)
        cpu_p = _prep_node(alloc_cpu, nodes_lane_major, Np, Cp, 0)
        ram_p = _prep_node(alloc_ram, nodes_lane_major, Np, Cp, 0)
        elig_p = prep(eligible, Pp, 0)
        qwin_p = prep(qwin, Pp, 0)
        # Non-negative f32 bit patterns sort like the floats; move them through
        # the kernel as i32 so every block shares one dtype.
        qoff_p = prep(jax.lax.bitcast_convert_type(qoff, jnp.int32), Pp, 0)
        qseq_p = prep(qseq, Pp, 0)
        reqc_p = prep(pod_req_cpu, Pp, 0)
        reqr_p = prep(pod_req_ram, Pp, 0)

    node_spec = pl.BlockSpec((Np, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    pod_spec = pl.BlockSpec((Pp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    cand_spec = pl.BlockSpec((Kp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.named_scope("kernel_io"):
        spread_shape, spread_args, spread_in, table_spec, _, table_shape = _spread_operands(
            spread, nodes_lane_major, Np, Cp, Pp, node_spec, pod_spec
        )
    spread_out, spread_shapes = [], []
    if spread is not None:
        spread_out = [table_spec, cand_spec, cand_spec]
        spread_shapes = [table_shape] + [jax.ShapeDtypeStruct((Kp, Cp), jnp.int32)] * 2
    kernel = functools.partial(
        _select_cycle_kernel, N, K, profile or DEFAULT_PROFILE, spread_shape
    )
    with jax.enable_x64(False):
        cpu_o, ram_o, cand_o, valid_o, assign_o, fitany_o, best_o, *spread_o = pl.pallas_call(
            kernel,
            name="fused_select_schedule_cycle",
            grid=(Cp // _LANE,),
            in_specs=[node_spec] * 3 + [pod_spec] * 6 + spread_in,
            out_specs=[node_spec] * 2 + [cand_spec] * 5 + spread_out,
            out_shape=[
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
            ]
            + spread_shapes,
            scratch_shapes=[pltpu.VMEM((Pp, _LANE), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_SELECT_VMEM_LIMIT
            ),
            interpret=interpret,
        )(alive_p, cpu_p, ram_p, elig_p, qwin_p, qoff_p, qseq_p, reqc_p, reqr_p, *spread_args)

    with jax.named_scope("kernel_io"):
        return (
            cand_o[:K, :C].T,
            valid_o[:K, :C].T != 0,
            assign_o[:K, :C].T != 0,
            fitany_o[:K, :C].T != 0,
            best_o[:K, :C].T,
            _unprep_node(cpu_o, nodes_lane_major, N, C),
            _unprep_node(ram_o, nodes_lane_major, N, C),
            *(x[:K, :C].T for x in spread_o[1:]),
        )


# --- live row tiles: what a step of a serial pod-block kernel sweeps --------
#
# The serial kernels pick ONE pod row per lane per step out of a (Pp, LC)
# block. The rows a launch can ever pick are known before its loop starts
# (the eligible / freed mask only loses members during the loop) and are
# few: arrivals keep slot order, so a cycle's eligible rows are a narrow
# band. Each grid program therefore lists, once per launch, the row tiles
# that hold any candidate of any of its lanes, and every step sweeps those
# tiles only. The list is read from the mask itself — every tile when the
# mask is full, two runs of tiles when it has two bands (plain arrivals and
# an HPA group's ring slots), none when it is empty — so one algorithm
# serves every shape. Rows outside the list hold no candidate, so the
# selections, their order and every one-hot write equal a whole-block
# sweep's bit for bit.
#
# Tile height: 16 vregs of rows, chosen on the chip and not a knob (PERF.md
# section 6, PR 27: on a band-shaped mask 32 to 256 rows time alike, on a
# full mask 128 is the fastest and the only one that matches a whole-block
# sweep).
_ROW_TILE = 128


def _row_tiles(n_rows: int) -> Tuple[int, int]:
    """(tile height, tiles) of a block of n_rows sublane-padded rows."""
    tile = min(_ROW_TILE, n_rows)
    return tile, -(-n_rows // tile)


def _tile_start(t, n_rows: int):
    """First row of tile t. Where the tile height does not divide the block
    the last tile starts early and overlaps its neighbour: every sweep is
    idempotent (a strict minimum, a one-hot write), so a row seen twice
    changes nothing."""
    tile, _ = _row_tiles(n_rows)
    return pl.multiple_of(jnp.minimum(t * jnp.int32(tile), jnp.int32(n_rows - tile)), _SUB)


def _while_i32(lo, hi, body, init):
    """fori_loop with an explicit int32 induction variable (see the note in
    _cycle_kernel: under x64 fori_loop's own is i64, which Mosaic refuses)."""

    def step(c):
        return c[0] + jnp.int32(1), body(c[0], c[1])

    return jax.lax.while_loop(lambda c: c[0] < hi, step, (lo, init))[1]


def _list_live_tiles(mask_ref, live_ref):
    """Write into live_ref (SMEM, one int32 per tile) the indices of the row
    tiles of mask_ref (int32 0/1) that hold a set entry in any lane, in
    rising order; return how many there are."""
    n_rows = mask_ref.shape[0]
    tile, n_tiles = _row_tiles(n_rows)

    def body(t, n_live):
        rows = pl.ds(_tile_start(t, n_rows), tile)
        live_ref[n_live] = t  # kept only if the count moves past it
        return n_live + (jnp.max(mask_ref[rows, :]) != jnp.int32(0)).astype(jnp.int32)

    return _while_i32(jnp.int32(0), jnp.int32(n_tiles), body, jnp.int32(0))


def _sweep_live_tiles(n_live, live_ref, n_rows: int, body, init=None):
    """body(first row of the tile, carry) -> carry, over the listed tiles."""
    return _while_i32(
        jnp.int32(0),
        n_live,
        lambda i, c: body(_tile_start(live_ref[i], n_rows), c),
        init,
    )


def _select_first(n_live, live_ref, n_rows: int, remaining, key_refs, carried):
    """Per lane, the remaining row that is first in the lexicographic order
    of key_refs (int32, below INT32_MAX on remaining rows), ties to the
    lowest slot — with no keys, the first remaining row — found in ONE pass
    over the live tiles. remaining(rows, slots) says which of 8 rows
    (a pl.ds and their (8, LC) slot numbers) still compete. `carried` lists
    (ref, fill) pairs whose value at the chosen row comes back with it
    (fill where the lane has no remaining row), so the sweep reads each
    block once and no gather follows.

    The pass keeps a running best per (sublane, lane) position in vregs —
    row r competes with the rows congruent to it mod 8, elementwise, no
    cross-sublane traffic — and the 8 survivors of a lane are reduced once
    at the end. A strict comparison keeps the earlier row on a tie and the
    sweep visits rows in rising order, so the survivor of a position is its
    lowest-slot minimum. Returns (slot (1, LC) int32, -1 for a lane with
    nothing left; [value (1, LC) per carried ref])."""
    tile, _ = _row_tiles(n_rows)
    i0 = jnp.int32(0)
    neg1 = jnp.int32(-1)
    bigi = jnp.int32(np.iinfo(np.int32).max)
    shape = (_SUB, _LANE)
    iota8 = jax.lax.broadcasted_iota(jnp.int32, shape, 0)

    def tile_body(start, best):
        for g in range(tile // _SUB):
            first = start + jnp.int32(g * _SUB)
            rows = pl.ds(first, _SUB)
            slot, keys, vals = best
            slots = iota8 + first
            better = slot < i0  # no key: the first remaining row wins
            for ref, key in zip(reversed(key_refs), reversed(keys)):
                k = ref[rows, :]
                better = (k < key) | ((k == key) & better)
            better = better & remaining(rows, slots)
            best = (
                jnp.where(better, slots, slot),
                tuple(
                    jnp.where(better, ref[rows, :], key)
                    for ref, key in zip(key_refs, keys)
                ),
                tuple(
                    jnp.where(better, ref[rows, :], val)
                    for (ref, _), val in zip(carried, vals)
                ),
            )
        return best

    slot, keys, vals = _sweep_live_tiles(
        n_live,
        live_ref,
        n_rows,
        tile_body,
        (
            jnp.full(shape, neg1),
            tuple(jnp.full(shape, bigi) for _ in key_refs),
            tuple(jnp.full(shape, fill) for _, fill in carried),
        ),
    )
    # The 8 survivors of a lane: the staged minimum a whole-block sweep
    # makes over all rows, here over one vreg.
    sel = slot >= i0
    for key in keys:
        m = jnp.min(jnp.where(sel, key, bigi), axis=0, keepdims=True)
        sel = sel & (key == m)
    first = jnp.min(jnp.where(sel, slot, bigi), axis=0, keepdims=True)
    sel = sel & (slot == first)
    return (
        jnp.where(first == bigi, neg1, first),
        [
            jnp.max(jnp.where(sel, val, fill), axis=0, keepdims=True)
            for (_, fill), val in zip(carried, vals)
        ],
    )


def free_kernel_fits(n_nodes: int, n_pods: int) -> bool:
    """VMEM fits-check for the freed-resource kernel: 6 pod blocks (incl.
    finish mask and estimator values) + 4 node blocks, double-buffered by
    Mosaic, and a seventh pod block's worth of room for the loop body's
    temporaries — the kernel raises the scoped limit to
    _SELECT_VMEM_LIMIT, the check keeps ~40% headroom."""
    np_pad = -(-n_nodes // _SUB) * _SUB
    pp_pad = -(-n_pods // _SUB) * _SUB
    resident = (7 * pp_pad + 4 * np_pad) * _LANE * 4
    return 2 * resident <= int(0.8 * _SELECT_VMEM_LIMIT)


def _free_kernel(
    freed_ref,     # (Pp, LC) int32 0/1
    node_ref,      # (Pp, LC) int32 assigned node slot
    reqc_ref,      # (Pp, LC) int32
    reqr_ref,      # (Pp, LC) int32
    finish_ref,    # (Pp, LC) int32 0/1 (finishes subset of freed)
    value_ref,     # (Pp, LC) float32 estimator sample (pod duration seconds)
    acpu_ref,      # (Np, LC) int32
    aram_ref,      # (Np, LC) int32
    acpu_out,      # (Np, LC) int32
    aram_out,      # (Np, LC) int32
    stats_out,     # (8, LC) float32: rows count/total/total_sq/min/max
    live_ref,      # SMEM (row tiles,) int32 scratch: the live tile list
):
    """Return freed pods' requests to their nodes' allocatable — the batched
    analog of the per-event resource release (reference:
    src/core/node_component.rs finish/removal handling). Replaces the XLA
    top_k-compaction loop of _apply_window_events, whose per-round
    lax.top_k lowers to a FULL (C, P) sort on TPU (~4 ms/window at dense
    shapes); here each freed pod is extracted by a per-lane first-set-bit
    pass and added via a node one-hot, with a data-dependent early exit at
    the deepest lane's freed count. Integer adds commute, so the result is
    bit-identical to the XLA loop.

    What a step sweeps: ONE pass over the block's live row tiles (those
    holding a freed row of any lane, _list_live_tiles) that finds the
    lane's next freed row — the first above the row it took the step
    before, so no remaining-mask is kept or cleared — and brings its node,
    requests, finish bit and sample along (_select_first); then the two
    whole-tile node one-hot adds.

    The same iteration also folds the pod-duration estimator samples of the
    FINISHED subset (stats_out rows 0..4: count/total/total_sq/min/max) —
    replacing the five (C, P) masked reductions of _est_add_reduced, whose
    unfused passes cost ~1.5 ms/window at dense shapes. The float32 sums
    accumulate in a different order than XLA's tiled reduction: within the
    documented metric-accumulator tolerance (docs/PARITY.md)."""
    i0 = jnp.int32(0)
    neg1 = jnp.int32(-1)
    f0 = jnp.float32(0.0)
    f1 = jnp.float32(1.0)
    finf = jnp.float32(np.inf)

    acpu_out[:] = acpu_ref[:]
    aram_out[:] = aram_ref[:]
    stats_out[:] = jnp.zeros_like(stats_out)
    stats_out[3:4, :] = stats_out[3:4, :] + finf
    stats_out[4:5, :] = stats_out[4:5, :] - finf
    iota_n = jax.lax.broadcasted_iota(jnp.int32, acpu_ref.shape, 0)
    k_bound = jnp.max(jnp.sum(freed_ref[:], axis=0, keepdims=True))
    n_live = _list_live_tiles(freed_ref, live_ref)

    def body(carry):
        k, taken = carry  # taken: (1, LC) the slot each lane freed last
        slot, (node, rc, rr, fin, v) = _select_first(
            n_live,
            live_ref,
            freed_ref.shape[0],
            lambda rows, slots: (freed_ref[rows, :] != i0) & (slots > taken),
            (),
            (
                (node_ref, neg1),
                (reqc_ref, i0),
                (reqr_ref, i0),
                (finish_ref, i0),
                (value_ref, -finf),
            ),
        )
        oh = iota_n == node  # node == -1 (empty lane) matches nothing
        acpu_out[:] = acpu_out[:] + jnp.where(oh, rc, i0)
        aram_out[:] = aram_out[:] + jnp.where(oh, rr, i0)

        fin = fin > i0
        stats_out[0:1, :] = stats_out[0:1, :] + jnp.where(fin, f1, f0)
        stats_out[1:2, :] = stats_out[1:2, :] + jnp.where(fin, v, f0)
        stats_out[2:3, :] = stats_out[2:3, :] + jnp.where(fin, v * v, f0)
        stats_out[3:4, :] = jnp.minimum(stats_out[3:4, :], jnp.where(fin, v, finf))
        stats_out[4:5, :] = jnp.maximum(stats_out[4:5, :], jnp.where(fin, v, -finf))
        # A lane with nothing left keeps its mark: slot is -1 there.
        return k + jnp.int32(1), jnp.maximum(taken, slot)

    jax.lax.while_loop(
        lambda c: c[0] < k_bound,
        body,
        (jnp.int32(0), jnp.full((1, freed_ref.shape[1]), neg1)),
    )


@functools.partial(
    jax.jit, static_argnames=("interpret", "nodes_lane_major")
)
def fused_free_resources(
    freed: jnp.ndarray,      # (C, P) bool
    node: jnp.ndarray,       # (C, P) int32 (>= 0 for freed pods)
    req_cpu: jnp.ndarray,    # (C, P) int32
    req_ram: jnp.ndarray,    # (C, P) int32
    finishes: jnp.ndarray,   # (C, P) bool (the estimator subset of freed)
    value: jnp.ndarray,      # (C, P) float32 estimator sample per pod
    alloc_cpu: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    alloc_ram: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    interpret: bool = False,
    nodes_lane_major: bool = False,
):
    """(new_alloc_cpu, new_alloc_ram, stats (C, 5)) — the allocatables with
    every freed pod's requests added back (bit-identical to the
    top_k-compaction loop) and the finished pods' estimator fold
    (count/total/total_sq/min/max of `value`). With nodes_lane_major the
    allocatables arrive and return (N, C) lane-major (no transposes)."""
    C, P = freed.shape
    N = alloc_cpu.shape[0] if nodes_lane_major else alloc_cpu.shape[1]
    Cp = -(-C // _LANE) * _LANE
    Np = -(-N // _SUB) * _SUB
    Pp = -(-P // _SUB) * _SUB

    def prep(x, n_sub, fill):
        return _pad_axis(_pad_axis(x.T, 0, n_sub, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        freed_p = prep(freed.astype(jnp.int32), Pp, 0)
        node_p = prep(node.astype(jnp.int32), Pp, -1)
        reqc_p = prep(req_cpu.astype(jnp.int32), Pp, 0)
        reqr_p = prep(req_ram.astype(jnp.int32), Pp, 0)
        fin_p = prep(finishes.astype(jnp.int32), Pp, 0)
        val_p = prep(value.astype(jnp.float32), Pp, 0.0)
        acpu_p = _prep_node(alloc_cpu, nodes_lane_major, Np, Cp, 0)
        aram_p = _prep_node(alloc_ram, nodes_lane_major, Np, Cp, 0)

    node_spec = pl.BlockSpec((Np, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    pod_spec = pl.BlockSpec((Pp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    stats_spec = pl.BlockSpec((8, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.enable_x64(False):
        acpu_o, aram_o, stats_o = pl.pallas_call(
            _free_kernel,
            name="fused_free_resources",
            grid=(Cp // _LANE,),
            in_specs=[pod_spec] * 6 + [node_spec] * 2,
            out_specs=[node_spec] * 2 + [stats_spec],
            out_shape=[
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((8, Cp), jnp.float32),
            ],
            scratch_shapes=[pltpu.SMEM((_row_tiles(Pp)[1],), jnp.int32)],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_SELECT_VMEM_LIMIT
            ),
            interpret=interpret,
        )(freed_p, node_p, reqc_p, reqr_p, fin_p, val_p, acpu_p, aram_p)

    with jax.named_scope("kernel_io"):
        return (
            _unprep_node(acpu_o, nodes_lane_major, N, C),
            _unprep_node(aram_o, nodes_lane_major, N, C),
            stats_o[:5, :C].T,
        )


def event_kernel_fits(n_nodes: int, n_pods: int, n_events: int) -> bool:
    """VMEM fits-check for the event-scatter kernel: 3 pod in + 3 pod out,
    2 node in + 2 node out, 5 event blocks, int32/f32, double-buffered,
    plus loop-body temporaries (the kernel raises the scoped limit)."""
    np_pad = -(-n_nodes // _SUB) * _SUB
    pp_pad = -(-n_pods // _SUB) * _SUB
    ep_pad = -(-n_events // _SUB) * _SUB
    resident = (6 * pp_pad + 4 * np_pad + 5 * ep_pad) * _LANE * 4
    return 2 * resident <= int(0.8 * _SELECT_VMEM_LIMIT)


# Event kinds, duplicated from batched/state.py (importing it here would pull
# the x64 config flip into kernel-only users).
_EV_CREATE_NODE = 1
_EV_REMOVE_NODE = 2
_EV_CREATE_POD = 3
_EV_REMOVE_POD = 4


def _event_kernel(
    kind_ref,     # (Ep, LC) int32
    slot_ref,     # (Ep, LC) int32 (device coords; out-of-range = drop)
    rel_ref,      # (Ep, LC) float32 effect time rel-seconds
    seq_ref,      # (Ep, LC) int32 queue sequence for creates
    valid_ref,    # (Ep, LC) int32 0/1 (per-lane prefix)
    created_ref,  # (Np, LC) int32
    nrm_ref,      # (Np, LC) float32 node-removal time accumulator (min)
    pcr_ref,      # (Pp, LC) float32 pod-create time accumulator (min)
    pseq_ref,     # (Pp, LC) int32 pod-create seq accumulator (max)
    prm_ref,      # (Pp, LC) float32 pod-removal time accumulator (min)
    created_out,
    nrm_out,
    pcr_out,
    pseq_out,
    prm_out,
    live_ref,     # SMEM (row tiles,) int32 scratch: the live tile list
):
    """Apply one chunk of due trace events to the per-slot accumulators —
    the Pallas replacement for the five (C, E)-indexed XLA scatters in
    _apply_window_events' chunk body (measured ~5 ms/window at dense
    shapes). Event k is applied across all cluster lanes simultaneously via
    slot one-hots; min/max combiners match the scatter semantics exactly,
    and out-of-range slots (shifted-out sliding-window pods) match no
    one-hot row, reproducing mode='drop'.

    What a step sweeps: the two node accumulators whole, the three pod
    accumulators only over the row tiles between the lowest and the highest
    pod slot any valid event of the chunk names (a window's creates are
    neighbours in slot order) — no other row can match a one-hot."""
    i0 = jnp.int32(0)
    i1 = jnp.int32(1)
    neg1 = jnp.int32(-1)
    bigi = jnp.int32(np.iinfo(np.int32).max)

    created_out[:] = created_ref[:]
    nrm_out[:] = nrm_ref[:]
    pcr_out[:] = pcr_ref[:]
    pseq_out[:] = pseq_ref[:]
    prm_out[:] = prm_ref[:]

    iota_n = jax.lax.broadcasted_iota(jnp.int32, created_ref.shape, 0)
    k_bound = jnp.max(jnp.sum(valid_ref[:], axis=0, keepdims=True))

    n_rows = pcr_ref.shape[0]
    tile, _ = _row_tiles(n_rows)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (tile, pcr_ref.shape[1]), 0)
    kinds, slots = kind_ref[:], slot_ref[:]
    pod_ev = (
        (valid_ref[:] != i0)
        & ((kinds == jnp.int32(_EV_CREATE_POD)) | (kinds == jnp.int32(_EV_REMOVE_POD)))
        & (slots >= i0)
        & (slots < jnp.int32(n_rows))
    )
    # No pod event: lowest is INT32_MAX and the list stays empty.
    lowest = jax.lax.div(jnp.min(jnp.where(pod_ev, slots, bigi)), jnp.int32(tile))
    highest = jax.lax.div(jnp.max(jnp.where(pod_ev, slots, neg1)), jnp.int32(tile))
    n_live = jnp.maximum(highest + i1 - lowest, i0)

    def list_tile(i, _):
        live_ref[i] = lowest + i

    _while_i32(i0, n_live, list_tile, None)

    def body(k):
        kind = kind_ref[pl.ds(k, 1), :]
        slot = slot_ref[pl.ds(k, 1), :]
        rel = rel_ref[pl.ds(k, 1), :]
        seq = seq_ref[pl.ds(k, 1), :]
        v = valid_ref[pl.ds(k, 1), :] != i0

        is_cn = v & (kind == jnp.int32(_EV_CREATE_NODE))
        is_rn = v & (kind == jnp.int32(_EV_REMOVE_NODE))
        is_cp = v & (kind == jnp.int32(_EV_CREATE_POD))
        is_rp = v & (kind == jnp.int32(_EV_REMOVE_POD))

        oh_n = iota_n == slot
        created_out[:] = jnp.where(oh_n & is_cn, i1, created_out[:])
        nrm_out[:] = jnp.where(
            oh_n & is_rn, jnp.minimum(nrm_out[:], rel), nrm_out[:]
        )

        def scatter(start, _):
            rows = pl.ds(start, tile)
            oh_p = (iota_t + start) == slot
            pcr_out[rows, :] = jnp.where(
                oh_p & is_cp, jnp.minimum(pcr_out[rows, :], rel), pcr_out[rows, :]
            )
            pseq_out[rows, :] = jnp.where(
                oh_p & is_cp, jnp.maximum(pseq_out[rows, :], seq), pseq_out[rows, :]
            )
            prm_out[rows, :] = jnp.where(
                oh_p & is_rp, jnp.minimum(prm_out[rows, :], rel), prm_out[rows, :]
            )

        _sweep_live_tiles(n_live, live_ref, n_rows, scatter)

    def loop_body(k):
        body(k)
        return k + i1

    jax.lax.while_loop(lambda k: k < k_bound, loop_body, jnp.int32(0))


def event_accumulators(n_clusters: int, n_nodes: int, n_pods: int):
    """The event loop's five accumulators, empty, in the layout
    fused_event_scatter takes and returns them in: slots on sublanes padded
    to 8, clusters on lanes padded to 128. created (Np, Cp) int32 0,
    node_removal (Np, Cp) float32 +inf, pod_create (Pp, Cp) float32 +inf,
    pod_create_seq (Pp, Cp) int32 0, pod_removal (Pp, Cp) float32 +inf.
    Constants, so a loop that carries them pays no pad and no transpose on
    the way in; pad rows match no in-range slot and pad lanes carry no valid
    event, and event_accumulators_unpack cuts both off."""
    Cp = -(-n_clusters // _LANE) * _LANE
    Np = -(-n_nodes // _SUB) * _SUB
    Pp = -(-n_pods // _SUB) * _SUB
    f32inf = jnp.float32(np.inf)
    return (
        jnp.zeros((Np, Cp), jnp.int32),
        jnp.full((Np, Cp), f32inf, jnp.float32),
        jnp.full((Pp, Cp), f32inf, jnp.float32),
        jnp.zeros((Pp, Cp), jnp.int32),
        jnp.full((Pp, Cp), f32inf, jnp.float32),
    )


def event_accumulators_unpack(
    acc, n_clusters: int, n_nodes: int, n_pods: int, nodes_lane_major: bool
):
    """The five accumulators as batched/step.py's row-major consumers read
    them, ONCE after the loop: created as bool and node_removal (C, N), or
    (N, C) with nodes_lane_major (a slice, no transpose); the three pod
    planes (C, P)."""
    created, node_removal, pod_create, pod_create_seq, pod_removal = acc
    with jax.named_scope("kernel_io"):
        return (
            _unprep_node(created, nodes_lane_major, n_nodes, n_clusters) != 0,
            _unprep_node(node_removal, nodes_lane_major, n_nodes, n_clusters),
            _unprep_node(pod_create, False, n_pods, n_clusters),
            _unprep_node(pod_create_seq, False, n_pods, n_clusters),
            _unprep_node(pod_removal, False, n_pods, n_clusters),
        )


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_event_scatter(
    ev_kind: jnp.ndarray,   # (C, E) int32
    ev_slot: jnp.ndarray,   # (C, E) int32 device coords
    ev_rel: jnp.ndarray,    # (C, E) float32
    ev_seq: jnp.ndarray,    # (C, E) int32
    ev_valid: jnp.ndarray,  # (C, E) bool (per-lane prefix)
    created: jnp.ndarray,       # (Np, Cp) int32
    node_removal: jnp.ndarray,  # (Np, Cp) float32
    pod_create: jnp.ndarray,    # (Pp, Cp) float32
    pod_create_seq: jnp.ndarray,  # (Pp, Cp) int32
    pod_removal: jnp.ndarray,   # (Pp, Cp) float32
    interpret: bool = False,
):
    """Returns the five accumulators with this chunk's events applied,
    bit-identical to the XLA scatter formulation. The accumulators arrive
    and return in the kernel's own layout (event_accumulators: padded,
    clusters on lanes): the event chunk loop carries all five that way
    across its passes, so a pass pays no pad, transpose, slice or bool
    round trip at this boundary, and the caller leaves the layout once
    after the loop (event_accumulators_unpack). The event columns are
    per-chunk data and keep the row-major convention: five (E, Cp) planes
    transposed and padded a pass."""
    C, E = ev_kind.shape
    Np, Cp = created.shape
    Pp = pod_create.shape[0]
    Ep = -(-E // _SUB) * _SUB
    assert Cp == -(-C // _LANE) * _LANE and Np % _SUB == 0 and Pp % _SUB == 0, (
        "accumulators not in the kernel's layout (event_accumulators)"
    )

    def prep(x, fill):
        return _pad_axis(_pad_axis(x.T, 0, Ep, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        args = (
            prep(ev_kind.astype(jnp.int32), 0),
            prep(ev_slot.astype(jnp.int32), -1),
            prep(ev_rel.astype(jnp.float32), 0.0),
            prep(ev_seq.astype(jnp.int32), 0),
            prep(ev_valid.astype(jnp.int32), 0),
            created,
            node_removal,
            pod_create,
            pod_create_seq,
            pod_removal,
        )

    def spec(n_sub):
        return pl.BlockSpec((n_sub, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.enable_x64(False):
        return tuple(
            pl.pallas_call(
                _event_kernel,
                name="fused_event_scatter",
                grid=(Cp // _LANE,),
                in_specs=[spec(Ep)] * 5 + [spec(Np)] * 2 + [spec(Pp)] * 3,
                out_specs=[spec(Np)] * 2 + [spec(Pp)] * 3,
                out_shape=[
                    jax.ShapeDtypeStruct(a.shape, a.dtype) for a in args[5:]
                ],
                scratch_shapes=[pltpu.SMEM((_row_tiles(Pp)[1],), jnp.int32)],
                # Each accumulator is updated in place: the loop's carry is
                # the kernel's operand AND its result, so XLA copies no
                # plane between passes. A grid program reads its own lane
                # tile whole before it writes it, and no other's.
                input_output_aliases={5 + i: i for i in range(5)},
                compiler_params=pltpu.CompilerParams(
                    vmem_limit_bytes=_SELECT_VMEM_LIMIT
                ),
                interpret=interpret,
            )(*args)
        )


def commit_kernel_fits(n_pods: int, k_pods: int) -> bool:
    """VMEM fits-check for the commit-scatter kernel: 2 pod in + 4 pod out +
    6 candidate blocks, double-buffered, plus loop temporaries (the kernel
    raises the scoped limit)."""
    pp_pad = -(-n_pods // _SUB) * _SUB
    kp_pad = -(-k_pods // _SUB) * _SUB
    resident = (6 * pp_pad + 6 * kp_pad) * _LANE * 4
    return 2 * resident <= int(0.8 * _SELECT_VMEM_LIMIT)


# Pod phases, duplicated from batched/state.py (see _EV_* note above).
_PHASE_UNSCHEDULABLE = 2
_PHASE_RUNNING = 3


def _commit_kernel(
    cand_ref,     # (Kp, LC) int32 pod slot
    assign_ref,   # (Kp, LC) int32 0/1
    park_ref,     # (Kp, LC) int32 0/1
    best_ref,     # (Kp, LC) int32 node slot
    start_ref,    # (Kp, LC) float32 start offset rel-seconds
    parks_ref,    # (Kp, LC) float32 park offset rel-seconds
    phase_ref,    # (Pp, LC) int32
    node_ref,     # (Pp, LC) int32
    phase_out,    # (Pp, LC) int32
    node_out,     # (Pp, LC) int32
    start_out,    # (Pp, LC) float32 (+inf = untouched)
    park_out,     # (Pp, LC) float32 (+inf = untouched)
):
    """Scatter the cycle's K per-lane decisions back into the (P,) pod
    arrays — the Pallas replacement for commit_cycle's four (C, K)-indexed
    XLA scatters. Candidate slots are unique within a cycle, so the one-hot
    writes are order-independent and bit-identical to the scatters."""
    i0 = jnp.int32(0)
    i1 = jnp.int32(1)
    inf = jnp.float32(np.inf)

    phase_out[:] = phase_ref[:]
    node_out[:] = node_ref[:]
    start_out[:] = jnp.full_like(start_out, inf)
    park_out[:] = jnp.full_like(park_out, inf)

    iota_p = jax.lax.broadcasted_iota(jnp.int32, phase_ref.shape, 0)
    # touched == assign | park == the valid prefix (assign = valid & fit,
    # park = valid & ~fit), so its per-lane count bounds the loop.
    touched_all = (assign_ref[:] + park_ref[:]) > i0
    k_bound = jnp.max(
        jnp.sum(touched_all.astype(jnp.int32), axis=0, keepdims=True)
    )

    def body(k):
        cand = cand_ref[pl.ds(k, 1), :]
        assign = assign_ref[pl.ds(k, 1), :] != i0
        park = park_ref[pl.ds(k, 1), :] != i0
        best = best_ref[pl.ds(k, 1), :]
        start_s = start_ref[pl.ds(k, 1), :]
        park_s = parks_ref[pl.ds(k, 1), :]
        touched = assign | park

        oh = iota_p == cand
        new_phase = jnp.where(
            assign, jnp.int32(_PHASE_RUNNING), jnp.int32(_PHASE_UNSCHEDULABLE)
        )
        phase_out[:] = jnp.where(oh & touched, new_phase, phase_out[:])
        node_out[:] = jnp.where(oh & assign, best, node_out[:])
        start_out[:] = jnp.where(oh & assign, start_s, start_out[:])
        park_out[:] = jnp.where(oh & park, park_s, park_out[:])

    def loop_body(k):
        body(k)
        return k + i1

    jax.lax.while_loop(lambda k: k < k_bound, loop_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("interpret",))
def fused_commit_scatter(
    cand: jnp.ndarray,     # (C, K) int32
    assign: jnp.ndarray,   # (C, K) bool
    park: jnp.ndarray,     # (C, K) bool
    best: jnp.ndarray,     # (C, K) int32
    start_s: jnp.ndarray,  # (C, K) float32
    park_s: jnp.ndarray,   # (C, K) float32
    phase: jnp.ndarray,    # (C, P) int32
    node: jnp.ndarray,     # (C, P) int32
    interpret: bool = False,
):
    """Returns (phase, node, start_tmp, park_tmp) with the decisions
    applied; start_tmp/park_tmp are +inf where untouched, matching the XLA
    formulation in commit_cycle."""
    C, P = phase.shape
    K = cand.shape[1]
    Cp = -(-C // _LANE) * _LANE
    Pp = -(-P // _SUB) * _SUB
    Kp = -(-K // _SUB) * _SUB

    def prep(x, n_sub, fill):
        return _pad_axis(_pad_axis(x.T, 0, n_sub, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        args = (
            prep(cand.astype(jnp.int32), Kp, -1),
            prep(assign.astype(jnp.int32), Kp, 0),
            prep(park.astype(jnp.int32), Kp, 0),
            prep(best.astype(jnp.int32), Kp, 0),
            prep(start_s.astype(jnp.float32), Kp, 0.0),
            prep(park_s.astype(jnp.float32), Kp, 0.0),
            prep(phase.astype(jnp.int32), Pp, 0),
            prep(node.astype(jnp.int32), Pp, 0),
        )

    def spec(n_sub):
        return pl.BlockSpec((n_sub, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.enable_x64(False):
        phase_o, node_o, start_o, park_o = pl.pallas_call(
            _commit_kernel,
            name="fused_commit_scatter",
            grid=(Cp // _LANE,),
            in_specs=[spec(Kp)] * 6 + [spec(Pp)] * 2,
            out_specs=[spec(Pp)] * 4,
            out_shape=[
                jax.ShapeDtypeStruct((Pp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.float32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.float32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_SELECT_VMEM_LIMIT
            ),
            interpret=interpret,
        )(*args)

    with jax.named_scope("kernel_io"):
        return (
            phase_o[:P, :C].T,
            node_o[:P, :C].T,
            start_o[:P, :C].T,
            park_o[:P, :C].T,
        )


def _pad_axis(x: jnp.ndarray, axis: int, to: int, value) -> jnp.ndarray:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(
    jax.jit, static_argnames=("interpret", "nodes_lane_major", "profile")
)
def fused_schedule_cycle(
    alive: jnp.ndarray,      # (C, N) bool — (N, C) when nodes_lane_major
    alloc_cpu: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    alloc_ram: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    valid: jnp.ndarray,      # (C, K) bool
    req_cpu: jnp.ndarray,    # (C, K) int32
    req_ram: jnp.ndarray,    # (C, K) int32
    interpret: bool = False,
    nodes_lane_major: bool = False,
    profile=None,  # pipeline.CompiledProfile; None = the default profile
    spread=None,  # (domain, counts, limits, zone_alive, cand group, cand bits)
):
    """Run the K-pod scheduling loop in VMEM.

    Returns (assign (C,K) bool, fit_any (C,K) bool, best (C,K) int32,
    new_alloc_cpu, new_alloc_ram), identical to the lax.scan formulation in
    batched/step.py. With nodes_lane_major the node operands arrive and the
    allocatables return (N, C) lane-major (no transposes). With `spread`
    (_spread_operands: the topology-spread filter's four operands and the
    candidates' (C, K) workload and match bits) two more follow: the placed
    node's domain and the decision's spread flags, (C, K) int32 each.
    """
    C, K = valid.shape
    N = alloc_cpu.shape[0] if nodes_lane_major else alloc_cpu.shape[1]
    Cp = -(-C // _LANE) * _LANE
    Np = -(-N // _SUB) * _SUB
    Kp = -(-K // _SUB) * _SUB

    def prep(x, n_sub, fill):
        # (C, n) -> padded transposed (n_sub, Cp) with clusters on lanes.
        return _pad_axis(_pad_axis(x.astype(jnp.int32).T, 0, n_sub, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        alive_p = _prep_node(alive, nodes_lane_major, Np, Cp, 0)
        cpu_p = _prep_node(alloc_cpu, nodes_lane_major, Np, Cp, 0)
        ram_p = _prep_node(alloc_ram, nodes_lane_major, Np, Cp, 0)
        valid_p = prep(valid, Kp, 0)
        reqc_p = prep(req_cpu, Kp, 0)
        reqr_p = prep(req_ram, Kp, 0)

    node_spec = pl.BlockSpec((Np, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    cand_spec = pl.BlockSpec((Kp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.named_scope("kernel_io"):
        spread_shape, spread_args, spread_in, table_spec, _, table_shape = _spread_operands(
            spread, nodes_lane_major, Np, Cp, Kp, node_spec, cand_spec
        )
    spread_out, spread_shapes = [], []
    if spread is not None:
        spread_out = [table_spec, cand_spec, cand_spec]
        spread_shapes = [table_shape] + [jax.ShapeDtypeStruct((Kp, Cp), jnp.int32)] * 2
    kernel = functools.partial(_cycle_kernel, N, K, profile or DEFAULT_PROFILE, spread_shape)
    # Trace the kernel with x64 semantics OFF: the batched path enables
    # jax_enable_x64 for its f64 time arrays, but under x64 pallas_call's own
    # index bookkeeping traces as i64, which Mosaic fails to legalize
    # (func.return). Everything crossing this boundary is i32/bool.
    with jax.enable_x64(False):
        cpu_o, ram_o, assign_o, fitany_o, best_o, *spread_o = pl.pallas_call(
            kernel,
            name="fused_schedule_cycle",
            grid=(Cp // _LANE,),
            in_specs=[node_spec, node_spec, node_spec, cand_spec, cand_spec, cand_spec] + spread_in,
            out_specs=[node_spec, node_spec, cand_spec, cand_spec, cand_spec] + spread_out,
            out_shape=[
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Kp, Cp), jnp.int32),
            ]
            + spread_shapes,
            interpret=interpret,
        )(alive_p, cpu_p, ram_p, valid_p, reqc_p, reqr_p, *spread_args)

    with jax.named_scope("kernel_io"):
        return (
            assign_o[:K, :C].T != 0,
            fitany_o[:K, :C].T != 0,
            best_o[:K, :C].T,
            _unprep_node(cpu_o, nodes_lane_major, N, C),
            _unprep_node(ram_o, nodes_lane_major, N, C),
            *(x[:K, :C].T for x in spread_o[1:]),
        )


# --- round-4 megakernel: selection + cycle + commit in ONE launch -----------

def select_commit_kernel_fits(n_nodes: int, n_pods: int, k_pods: int, spread_shape=None) -> bool:
    """VMEM budget for the megakernel: 3 node blocks in + 2 out, 9 pod
    blocks in + 4 out + 1 scratch, 3 K-shaped blocks and the (8, LANE) stats
    block; with the spread filter one node block, two pod blocks in and one
    out, and the table, its limits, the live domains and a stats tile more;
    double-buffered by Mosaic (~2x block bytes)."""
    Np = -(-n_nodes // _SUB) * _SUB
    Pp = -(-n_pods // _SUB) * _SUB
    Kp = -(-k_pods // _SUB) * _SUB
    s = int(spread_shape is not None)
    per_lane_bytes = (
        2 * ((5 + s) * Np + (14 + 3 * s) * Pp + 3 * Kp + 8 + _spread_table_rows(spread_shape))
        * 4 * _LANE
    )
    return per_lane_bytes <= int(_SELECT_VMEM_LIMIT * 0.8)


def _argmin_select(rem, qwin_ref, qoff_ref, qseq_ref, iota_p):
    """ONE in-kernel definition of the per-lane lexicographic argmin over
    (queue win, off-bits, seq) — the batched ActiveQueue's sorted order —
    for _select_cycle_kernel, the two-kernel fallback, which sweeps its
    whole pod block every step (the megakernel selects over its live row
    tiles with _select_first instead).
    Returns (sel one-hot (Pp, LC), seli int, slot (1, LC), valid (1, LC))."""
    i0 = jnp.int32(0)
    neg1 = jnp.int32(-1)
    bigi = jnp.int32(np.iinfo(np.int32).max)
    w = jnp.where(rem, qwin_ref[:], bigi)
    minw = jnp.min(w, axis=0, keepdims=True)
    m1 = rem & (qwin_ref[:] == minw)
    o = jnp.where(m1, qoff_ref[:], bigi)
    mino = jnp.min(o, axis=0, keepdims=True)
    m2 = m1 & (qoff_ref[:] == mino)
    sq = jnp.where(m2, qseq_ref[:], bigi)
    mins = jnp.min(sq, axis=0, keepdims=True)
    sel = m2 & (qseq_ref[:] == mins)  # exactly one row per non-empty lane
    seli = sel.astype(jnp.int32)
    slot = jnp.max(jnp.where(sel, iota_p, neg1), axis=0, keepdims=True)
    valid = slot >= i0
    return sel, seli, slot, valid


def _select_cycle_commit_kernel(
    n_nodes: int,
    k_pods: int,
    profile,        # pipeline.CompiledProfile (kernel static)
    spread_shape,   # (G, Z) static, None without the spread filter
    alive_ref,      # (Np, LC) int32
    alloc_cpu_ref,  # (Np, LC) int32
    alloc_ram_ref,  # (Np, LC) int32
    elig_ref,       # (Pp, LC) int32 0/1
    qwin_ref,       # (Pp, LC) int32
    qoff_ref,       # (Pp, LC) int32 (bitcast f32, non-negative)
    qseq_ref,       # (Pp, LC) int32
    preq_cpu_ref,   # (Pp, LC) int32
    preq_ram_ref,   # (Pp, LC) int32
    waited_ref,     # (Pp, LC) float32 queue wait at cycle start
    phase_ref,      # (Pp, LC) int32
    node_ref,       # (Pp, LC) int32
    qpre_ref,       # (Kp, LC) float32 positional cd_pre table
    start_ref,      # (Kp, LC) float32 positional start-offset table
    park_ref,       # (Kp, LC) float32 positional park-offset table
    *refs,
):
    # refs: with the spread filter the inputs domain (Np, LC), table and
    # limits (G*8, LC), live domains (8, LC), the pods' workload and match
    # bits (Pp, LC); then the outputs
    #   cpu_out, ram_out      (Np, LC) int32
    #   phase_out, node_out   (Pp, LC) int32
    #   start_out, park_out   (Pp, LC) float32 (+inf = untouched)
    #   stats_out             (8, LC) float32: rows 0-4 count/total/
    #                         total_sq/min/max of queue-time samples over
    #                         assigned decisions; rows 5-7 the sweep counter
    # with the filter the carried table (G*8, LC), zone_out (Pp, LC) int32
    # the placed node's domain (-2 = untouched) and sstats_out (8, LC) int32
    # (row 0 assignments of constrained pods, row 1 those with a live domain
    # closed); last the scratches rem (Pp, LC) and live (SMEM, row tiles).
    if spread_shape is not None:
        domain_ref, table_in, limit_ref, zalive_ref, pgroup_ref, pbits_ref = refs[:6]
        refs = refs[6:]
        table_out, zone_out, sstats_out = refs[7:10]
    cpu_out, ram_out, phase_out, node_out, start_out, park_out, stats_out = refs[:7]
    rem_ref, live_ref = refs[-2:]
    """The whole-window scheduling megakernel (VERDICT r3 item 2): queue
    SELECTION (iterated 3-key argmin, _select_cycle_kernel), the
    fit/score/place CYCLE, and the decision COMMIT (the per-pod phase/node/
    start/park writes of _commit_kernel) run in one Pallas launch, plus the
    queue-time estimator fold (the free kernel's stats pattern). Replaces
    two kernel launches and the (C, K) timing/metric XLA glue between them.

    What a step sweeps: the pod side only over the block's LIVE row tiles
    (the tiles holding an eligible row of any lane, listed once per launch:
    see _list_live_tiles) — one pass reading rem, the three queue keys, the
    two requests and waited to pick the lane's next pod with its values
    (_select_first), and one pass writing phase/node/start/park/rem where
    the row is the chosen slot. The node side (_fit_score_place) sweeps the
    whole (Np, LC) tile as before. The copies and +inf fills before the
    loop are whole-block, once a launch. stats_out rows 5/6/7 report, per
    lane of the program: live tiles x steps (the row tiles swept), steps,
    and the block's tiles — the ring's cycle_rows_swept_share.

    Timing bit-exactness: the positional tables qpre/start/park are
    computed OUTSIDE with the same cumsum cycle_timing uses on an all-valid
    mask; valid decisions always form a position prefix, and cumsum outputs
    depend only on their input prefix, so table values at valid positions
    are bit-identical to cycle_timing's. waited is precomputed per pod with
    candidates_from_slots' exact expression. Only the estimator SUMS
    accumulate in loop order instead of XLA's tiled reduction — the
    documented ulp-level metric tolerance (docs/PARITY.md)."""
    i0 = jnp.int32(0)
    i1 = jnp.int32(1)
    f0 = jnp.float32(0.0)
    f1 = jnp.float32(1.0)
    finf = jnp.float32(np.inf)

    cpu_out[:] = alloc_cpu_ref[:]
    ram_out[:] = alloc_ram_ref[:]
    phase_out[:] = phase_ref[:]
    node_out[:] = node_ref[:]
    start_out[:] = jnp.full_like(start_out, finf)
    park_out[:] = jnp.full_like(park_out, finf)
    stats_out[:] = jnp.zeros_like(stats_out)
    stats_out[3:4, :] = stats_out[3:4, :] + finf
    stats_out[4:5, :] = stats_out[4:5, :] - finf
    if spread_shape is not None:
        table_out[:] = table_in[:]
        zone_out[:] = jnp.full_like(zone_out, jnp.int32(-2))
        sstats_out[:] = jnp.zeros_like(sstats_out)

    alive = alive_ref[:] != i0
    iota_n = jax.lax.broadcasted_iota(jnp.int32, alive.shape, 0)
    node_ok = iota_n < jnp.int32(n_nodes)
    rem_ref[:] = elig_ref[:]
    depth = jnp.max(jnp.sum(elig_ref[:], axis=0, keepdims=True))
    k_bound = jnp.minimum(depth, jnp.int32(k_pods))

    n_rows = elig_ref.shape[0]
    tile, n_tiles = _row_tiles(n_rows)
    n_live = _list_live_tiles(elig_ref, live_ref)
    iota_t = jax.lax.broadcasted_iota(jnp.int32, (tile, elig_ref.shape[1]), 0)
    stats_out[5:6, :] = stats_out[5:6, :] + (n_live * k_bound).astype(jnp.float32)
    stats_out[6:7, :] = stats_out[6:7, :] + k_bound.astype(jnp.float32)
    stats_out[7:8, :] = stats_out[7:8, :] + jnp.float32(n_tiles)

    carried = ((preq_cpu_ref, i0), (preq_ram_ref, i0), (waited_ref, -finf))
    if spread_shape is not None:
        # The chosen pod's workload and match bits come back with its
        # requests: the same sweep, two more blocks read.
        carried += ((pgroup_ref, jnp.int32(-1)), (pbits_ref, i0))

    def body(k):
        slot, (rc, rr, waited, *pod_spread) = _select_first(
            n_live,
            live_ref,
            n_rows,
            lambda rows, _: rem_ref[rows, :] != i0,
            (qwin_ref, qoff_ref, qseq_ref),
            carried,
        )
        valid = slot >= i0

        spread = None
        if spread_shape is not None:
            spread = _spread_step(
                (domain_ref, table_out, limit_ref, zalive_ref), *spread_shape, *pod_spread
            )
        assign, any_fit, best, new_cpu, new_ram, *placed = _fit_score_place(
            profile, alive, node_ok, iota_n, cpu_out[:], ram_out[:],
            rc, rr, valid, spread,
        )
        if placed:
            tiles, zbest, constrained, closed = placed[0]
            _spread_store(table_out, tiles)
            sstats_out[0:1, :] = sstats_out[0:1, :] + constrained.astype(jnp.int32)
            sstats_out[1:2, :] = sstats_out[1:2, :] + closed.astype(jnp.int32)
        cpu_out[:] = new_cpu
        ram_out[:] = new_ram
        park = valid & ~any_fit

        # COMMIT: the chosen slot's row is the scatter mask (slot -1, a
        # lane with nothing left, matches no row).
        new_phase = jnp.where(
            assign, jnp.int32(_PHASE_RUNNING), jnp.int32(_PHASE_UNSCHEDULABLE)
        )
        touched = assign | park
        start_s = start_ref[pl.ds(k, 1), :]
        park_s = park_ref[pl.ds(k, 1), :]

        def commit(start, _):
            rows = pl.ds(start, tile)
            sel = (iota_t + start) == slot
            phase_out[rows, :] = jnp.where(sel & touched, new_phase, phase_out[rows, :])
            node_out[rows, :] = jnp.where(sel & assign, best, node_out[rows, :])
            start_out[rows, :] = jnp.where(sel & assign, start_s, start_out[rows, :])
            park_out[rows, :] = jnp.where(sel & park, park_s, park_out[rows, :])
            rem_ref[rows, :] = jnp.where(sel, i0, rem_ref[rows, :])
            if placed:
                zone_out[rows, :] = jnp.where(sel & assign, zbest, zone_out[rows, :])

        _sweep_live_tiles(n_live, live_ref, n_rows, commit)

        # Queue-time estimator fold over assigned decisions.
        qtime = waited + qpre_ref[pl.ds(k, 1), :]
        stats_out[0:1, :] = stats_out[0:1, :] + jnp.where(assign, f1, f0)
        stats_out[1:2, :] = stats_out[1:2, :] + jnp.where(assign, qtime, f0)
        stats_out[2:3, :] = stats_out[2:3, :] + jnp.where(
            assign, qtime * qtime, f0
        )
        stats_out[3:4, :] = jnp.minimum(
            stats_out[3:4, :], jnp.where(assign, qtime, finf)
        )
        stats_out[4:5, :] = jnp.maximum(
            stats_out[4:5, :], jnp.where(assign, qtime, -finf)
        )

    def loop_body(k):
        body(k)
        return k + i1

    jax.lax.while_loop(lambda k: k < k_bound, loop_body, jnp.int32(0))


@functools.partial(
    jax.jit,
    static_argnames=("k_pods", "interpret", "nodes_lane_major", "profile"),
)
def fused_select_cycle_commit(
    alive: jnp.ndarray,      # (C, N) bool — (N, C) when nodes_lane_major
    alloc_cpu: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    alloc_ram: jnp.ndarray,  # (C, N) int32 — (N, C) when nodes_lane_major
    eligible: jnp.ndarray,   # (C, P) bool
    qwin: jnp.ndarray,       # (C, P) int32
    qoff: jnp.ndarray,       # (C, P) float32 (non-negative)
    qseq: jnp.ndarray,       # (C, P) int32
    pod_req_cpu: jnp.ndarray,   # (C, P) int32
    pod_req_ram: jnp.ndarray,   # (C, P) int32
    waited: jnp.ndarray,     # (C, P) float32
    phase: jnp.ndarray,      # (C, P) int32
    node: jnp.ndarray,       # (C, P) int32
    qpre_t: jnp.ndarray,     # (C, K) float32 positional cd_pre
    start_t: jnp.ndarray,    # (C, K) float32 positional start offsets
    park_t: jnp.ndarray,     # (C, K) float32 positional park offsets
    k_pods: int,
    interpret: bool = False,
    nodes_lane_major: bool = False,
    profile=None,  # pipeline.CompiledProfile; None = the default profile
    spread=None,  # (domain, counts, limits, zone_alive, pod group, pod bits)
):
    """Megakernel wrapper. Returns (alloc_cpu, alloc_ram, phase, node,
    start_tmp (+inf untouched), park_tmp, qstats (C, 8): the queue-time
    fold in columns 0-4, the kernel's sweep counter in 5-7, as its
    stats_out rows). With nodes_lane_major the node operands arrive and the
    allocatables return (N, C) lane-major (no transposes at this
    boundary). With `spread` (_spread_operands: the filter's four operands and
    the pods' (C, P) workload and match bits) two more follow: the placed node's
    domain a pod, (C, P) int32 with -2 where the cycle placed nothing, and
    the (C, 2) counters (assignments of constrained pods, those with a live
    domain closed)."""
    C, P = eligible.shape
    N = alloc_cpu.shape[0] if nodes_lane_major else alloc_cpu.shape[1]
    K = k_pods
    Cp = -(-C // _LANE) * _LANE
    Np = -(-N // _SUB) * _SUB
    Pp = -(-P // _SUB) * _SUB
    Kp = -(-K // _SUB) * _SUB

    def prep(x, n_sub, fill):
        return _pad_axis(_pad_axis(x.astype(jnp.int32).T, 0, n_sub, fill), 1, Cp, fill)

    def prep_f(x, n_sub, fill):
        return _pad_axis(
            _pad_axis(x.astype(jnp.float32).T, 0, n_sub, fill), 1, Cp, fill
        )

    with jax.named_scope("kernel_io"):
        alive_p = _prep_node(alive, nodes_lane_major, Np, Cp, 0)
        cpu_p = _prep_node(alloc_cpu, nodes_lane_major, Np, Cp, 0)
        ram_p = _prep_node(alloc_ram, nodes_lane_major, Np, Cp, 0)
        elig_p = prep(eligible, Pp, 0)
        qwin_p = prep(qwin, Pp, 0)
        qoff_p = prep(jax.lax.bitcast_convert_type(qoff, jnp.int32), Pp, 0)
        qseq_p = prep(qseq, Pp, 0)
        reqc_p = prep(pod_req_cpu, Pp, 0)
        reqr_p = prep(pod_req_ram, Pp, 0)
        waited_p = prep_f(waited, Pp, 0.0)
        phase_p = prep(phase, Pp, 0)
        node_p = prep(node, Pp, 0)
        qpre_p = prep_f(qpre_t, Kp, 0.0)
        start_p = prep_f(start_t, Kp, 0.0)
        park_p = prep_f(park_t, Kp, 0.0)

    node_spec = pl.BlockSpec((Np, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    pod_spec = pl.BlockSpec((Pp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    cand_spec = pl.BlockSpec((Kp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    stat_spec = pl.BlockSpec((8, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.named_scope("kernel_io"):
        spread_shape, spread_args, spread_in, table_spec, tile_spec, table_shape = _spread_operands(
            spread, nodes_lane_major, Np, Cp, Pp, node_spec, pod_spec
        )
    spread_out, spread_shapes = [], []
    if spread is not None:
        spread_out = [table_spec, pod_spec, tile_spec]
        spread_shapes = [
            table_shape,
            jax.ShapeDtypeStruct((Pp, Cp), jnp.int32),
            jax.ShapeDtypeStruct((SPREAD_ZONE_TILE, Cp), jnp.int32),
        ]
    kernel = functools.partial(
        _select_cycle_commit_kernel, N, K, profile or DEFAULT_PROFILE, spread_shape
    )
    with jax.enable_x64(False):
        (cpu_o, ram_o, phase_o, node_o, start_o, park_o, stats_o, *spread_o) = pl.pallas_call(
            kernel,
            name="fused_select_cycle_commit",
            grid=(Cp // _LANE,),
            in_specs=[node_spec] * 3 + [pod_spec] * 9 + [cand_spec] * 3 + spread_in,
            out_specs=[node_spec] * 2 + [pod_spec] * 4 + [stat_spec] + spread_out,
            out_shape=[
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Np, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.float32),
                jax.ShapeDtypeStruct((Pp, Cp), jnp.float32),
                jax.ShapeDtypeStruct((8, Cp), jnp.float32),
            ]
            + spread_shapes,
            scratch_shapes=[
                pltpu.VMEM((Pp, _LANE), jnp.int32),
                pltpu.SMEM((_row_tiles(Pp)[1],), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_SELECT_VMEM_LIMIT
            ),
            interpret=interpret,
        )(
            alive_p, cpu_p, ram_p, elig_p, qwin_p, qoff_p, qseq_p,
            reqc_p, reqr_p, waited_p, phase_p, node_p,
            qpre_p, start_p, park_p, *spread_args,
        )

    with jax.named_scope("kernel_io"):
        return (
            _unprep_node(cpu_o, nodes_lane_major, N, C),
            _unprep_node(ram_o, nodes_lane_major, N, C),
            phase_o[:P, :C].T,
            node_o[:P, :C].T,
            start_o[:P, :C].T,
            park_o[:P, :C].T,
            stats_o[:, :C].T,
            *((spread_o[1][:P, :C].T, spread_o[2][:2, :C].T) if spread_o else ()),
        )
