"""BatchedSimulation: the user-facing driver for the vectorized path.

Compiles traces to slabs, builds the dense state, steps whole batches of
clusters through scheduling-cycle windows on-device, and reduces metrics to
the same summary shape the scalar MetricsCollector prints.

Sharding: all state arrays lead with the cluster axis C; `mesh` shards that
axis across devices (pure data parallelism over simulated clusters). There is
ONE boundary: under a mesh every window program is wrapped at its jit entry
in a single shard_map over the cluster axis (batched/sharding.py), so each
device runs the program a one-chip build of its shard runs — local C, the
kernels called directly, lane-major node state by the same tristate — and
GSPMD never partitions the window body. The only collectives in a window
program are scalar pmins where a value really spans every cluster (the
slide's shift, the superspan's capacity read and pod_base, the fast-forward's
next due window); metric reduction at readout is the rest of the
communication. The small programs between dispatches (reset, slide apply,
growth) are row-wise and stay plain jits over the sharded arrays.
"""

from __future__ import annotations

import dataclasses
import math
import os
from functools import partial
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

from kubernetriks_tpu.batched.autoscale import (
    AutoscaleStatics,
    init_autoscale_state,
)
from kubernetriks_tpu.parallel.multihost import (
    is_cross_process,
    put_global,
    to_host,
)
from kubernetriks_tpu.batched.sharding import (
    ClusterShards,
    cluster_specs,
    over_clusters,
    shard_axis_of,
)
from kubernetriks_tpu.batched.state import (
    DEFAULT_RAM_UNIT,
    PHASE_QUEUED,
    PHASE_RUNNING,
    PHASE_UNSCHEDULABLE,
    RefillStage,
    SLAB_BLOCK_EVENTS,
    TraceSlab,
    init_state,
    make_step_constants,
    slide_phase,
    swap_node_layout,
    tree_copy,
)
from kubernetriks_tpu.batched.statics import NAMES as STATIC_NAMES
from kubernetriks_tpu.batched.statics import resolve as resolve_statics
from kubernetriks_tpu.batched.timerep import TPair, from_f64_np, to_f64
from kubernetriks_tpu.batched.step import (
    _STEP_STATICS,
    _quantize_shift_device,
    _slide_apply_traced,
    _slide_shift_core,
    SUPERSPAN_GROW,
    SUPERSPAN_RUN,
    SUPERSPAN_STAGE,
    event_path,
    run_superspan,
    run_superspan_donated,
    run_windows,
    window_step,
)
from kubernetriks_tpu.batched.trace_compile import (
    CompiledClusterTrace,
    compile_cluster_trace,
    pad_and_batch,
)
from kubernetriks_tpu.config import SimulationConfig
from kubernetriks_tpu import sanitize
from kubernetriks_tpu.flags import flag_bool, flag_str, flag_tristate
from kubernetriks_tpu.telemetry import (
    GaugeSeries,
    log_chunk_throughput,
    recorder,
)
from kubernetriks_tpu.telemetry.tracer import (
    PH_CHUNK_FENCED,
    PH_CKPT_RESTORE,
    PH_CKPT_SAVE,
    PH_FLEET_RESET,
    PH_FUSED_CHUNK_SLIDE,
    PH_PRECOMPILE,
    PH_PROGRESS_WAIT,
    PH_REFILL_PREFETCH,
    PH_SHIFT_WAIT,
    PH_SLIDE,
    PH_STAGE_ASSEMBLE,
    PH_STAGE_PREFETCH,
    PH_STAGE_PUT,
    PH_STAGE_WAIT_FEEDER,
    PH_STAGE_WAIT_UPLOAD,
    PH_STEP_UNTIL_TIME,
    PH_SUPERSPAN,
    PH_WINDOW_CHUNK,
    PH_WINDOW_GROW,
    build_span,
)


# Device-resident slide payload budget: req/ram + duration pair +
# create-win (+ name ranks under autoscalers) at (C, T + W) int32 each.
# Above this, the engine keeps the host slide path (payloads stay in RAM).
_DEVICE_SLIDE_BUDGET_BYTES = 2 << 30

# Checkpoint-meta coverage of the STRUCTURAL state leaves (= None default:
# their presence is part of the compiled program's identity, so a restore
# into a template missing them dies deep inside orbax). The stateleaf
# lint pass proves every structural ClusterBatchState/AutoscaleState leaf
# has an entry here — the value is the coverage story save_checkpoint /
# load_checkpoint implement (see those methods' guards).
CKPT_COVERED_LEAVES = {
    "auto": "presence derived from config at build; the restoring engine's "
    "own state template supplies the structure (same-config contract)",
    "telemetry": "meta['telemetry_ring'] + the armed/unarmed ring-size "
    "guard in load_checkpoint (both directions, meta-absent included)",
    "ca_alloc": "meta['reclaim'] — the follow-or-raise reclaim guard "
    "rebuilds/drops the leaf to match the checkpoint",
    "ca_total": "meta['reclaim'] (see ca_alloc)",
    "ca_reclaimed": "meta['reclaim'] (see ca_alloc)",
    "col_next": "config-derived: the collection latch arms exactly when "
    "real pod groups exist, so a same-config restore template matches",
    "col_run": "config-derived (see col_next)",
    "col_util_cpu": "config-derived (see col_next)",
    "col_util_ram": "config-derived (see col_next)",
    "spread": "derived from the profile and the traces at build (a constraint "
    "under a profile that runs PodTopologySpread); the restoring engine's own "
    "state template supplies the structure (same-build contract, as `auto`)",
    "affinity": "derived from the profile and the traces at build (a taint, a "
    "nodeSelector, a node affinity or a toleration under a profile that runs "
    "NodeAffinity or TaintToleration); same-build contract, as `spread`",
}

# Power-of-two dispatch chunk ladder for the sliding path: any span is its
# binary decomposition (popcount(span) dispatches), and at most this many
# program shapes ever compile (engine.step_until_time; precompile_chunks
# AOT-compiles them up front).
_CHUNK_LADDER = (128, 64, 32, 16, 8, 4, 2, 1)


# The slide primitives (_slide_shift_core, _quantize_shift_device,
# _slide_apply_traced) moved to batched/step.py with the superspan executor
# (run_superspan needs them and engine imports step, not vice versa); the
# engine-side jitted shift entry keeps living here for the two-dispatch path.
_slide_shift_device = jax.jit(_slide_shift_core)


# The fused program shares every window-program static (drift between the
# fused and plain programs' static sets would make a new kwarg traced in one
# of them) plus the slide's window width.
_FUSED_STATICS = _STEP_STATICS + ("W",)


def _out_fused(axis, _statics):
    """(state, windowed pod-name ranks | None, the shift)."""
    return PartitionSpec(axis), PartitionSpec(axis), PartitionSpec()


@over_clusters(_FUSED_STATICS, _out_fused, replicated=("window_idxs",))
def _fused_chunk_slide_impl(
    state,
    slab,
    window_idxs,
    consts,
    payload,
    base,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    use_pallas: bool = False,
    pallas_interpret: bool = False,
    conditional_move: bool = False,
    shards=None,
    use_pallas_select: bool = False,
    use_megakernel: bool = True,
    hpa_seg=None,
    fault_params=None,
    name_ranks=None,
    lane_major: bool = False,
    window_razor: bool = True,
    reclaim: bool = False,
    profile=None,
    W: int = 0,
):
    """The composed path's steady-state MEGASTEP: one device program runs a
    whole window chunk (scheduling cycles + the in-trace HPA/CA passes of
    _window_body) AND the following pod-window slide — shift computation,
    quantization, block-move apply — with a traced shift amount. The engine
    dispatches this for the LAST ladder chunk of every slide span, so a span
    costs exactly popcount(span) dispatches and its only host sync is the
    asynchronous 4-byte readback of the returned shift (0 = no slide was
    possible; grow the window). Returns (state, new_pod_name_rank | None,
    shift)."""
    from kubernetriks_tpu.batched.step import _window_body

    shard_axis = shard_axis_of(shards)
    if lane_major:
        # Hot node leaves flip to the kernels' (N, C) layout for the whole
        # chunk+slide program; state at rest stays row-major
        # (state.swap_node_layout). The slide itself is pod-side only.
        state = swap_node_layout(state)

    def body(carry, w):
        new = _window_body(
            carry,
            slab,
            w,
            consts,
            max_events_per_window,
            max_pods_per_cycle,
            autoscale_statics,
            max_ca_pods_per_cycle,
            max_pods_per_scale_down,
            use_pallas,
            pallas_interpret,
            conditional_move,
            use_pallas_select,
            use_megakernel=use_megakernel,
            hpa_seg=hpa_seg,
            fault_params=fault_params,
            name_ranks=name_ranks,
            lane_major=lane_major,
            window_razor=window_razor,
            reclaim=reclaim,
            profile=profile,
            shard_axis=shard_axis,
        )
        return new, None

    state, _ = jax.lax.scan(body, state, jnp.asarray(window_idxs, jnp.int32))
    if lane_major:
        state = swap_node_layout(state)
    with jax.named_scope("slide"):
        base = jnp.asarray(base, jnp.int32)
        s0 = _slide_shift_core(
            slide_phase(state.pods, consts)[:, :W], payload["create_win"], base,
            shard_axis,
        )
        s = _quantize_shift_device(s0, W)
        rank = (
            autoscale_statics.pod_name_rank
            if (autoscale_statics is not None and "rank" in payload)
            else None
        )
        new_pods, new_rank = _slide_apply_traced(
            state.pods, rank, payload, base, s, W
        )
        state = state._replace(pods=new_pods, pod_base=state.pod_base + s)
    return state, new_rank, s


_fused_chunk_slide = jax.jit(
    _fused_chunk_slide_impl, static_argnames=_FUSED_STATICS
)
_fused_chunk_slide_donated = jax.jit(
    _fused_chunk_slide_impl, static_argnames=_FUSED_STATICS, donate_argnums=(0,)
)




@partial(jax.jit, static_argnames=("s", "W"))
@jax.named_scope("slide")
def _slide_apply_device(pods, rank, pay, base, s: int, W: int):
    """Apply a quantized window slide of a STATIC `s` slots entirely on
    device: slice the refill segment out of the device-resident payload at
    base + W, build pristine refill slots with the SAME constructor
    init_state uses, and concatenate — no host round-trips. Also slides
    the windowed pod-name ranks (autoscale statics) when `rank` is given.
    Mirrors the host path in _advance_pod_window leaf-for-leaf.

    One program a distinct shift: the second dispatch of the two-dispatch
    slide (fast-forward, gauge and fuse-disabled engines), which knows `s`
    on the host. The steady-state executors (the superspan, the fused
    chunk+slide) run step._slide_apply_traced, the same block move at a
    traced `s`; tests/test_slide_apply.py holds the two leaf for leaf."""
    from kubernetriks_tpu.batched.state import fresh_pod_arrays

    C = pods.phase.shape[0]
    start = (jnp.int32(0), base + jnp.int32(W))

    def sl(a):
        return jax.lax.dynamic_slice(a, start, (C, s))

    refill = fresh_pod_arrays(
        C,
        s,
        sl(pay["req_cpu"]),
        sl(pay["req_ram"]),
        TPair(win=sl(pay["dur_win"]), off=sl(pay["dur_off"])),
    )
    new_pods = jax.tree.map(
        lambda a, b: jnp.concatenate([a[:, s:W], b, a[:, W:]], axis=1),
        pods,
        refill,
    )
    new_rank = None
    if rank is not None:
        new_rank = jnp.concatenate(
            [rank[:, s:W], sl(pay["rank"]), rank[:, W:]], axis=1
        )
    return new_pods, new_rank


def _makes_objects_at_run_time(config, compiled_traces) -> bool:
    """Whether the build's autoscalers make pods or nodes at run time, which
    would need labels, taints and planes of their own."""
    return (
        config.horizontal_pod_autoscaler.enabled
        or config.cluster_autoscaler.enabled
        or any(c.pod_groups for c in compiled_traces)
    )


def _global_pod_plane_width(compiled_traces, n_pods: int, pod_window) -> int:
    """Width of a pod plane kept in GLOBAL pod coordinates (the spread and
    affinity states'): the device pod axis where that holds the whole trace;
    under a sliding pod window, which cuts its columns out of the plane at
    pod_base (step.spread_window_view), room for the widest window (the whole
    trace) at the highest base."""
    T = max(max((c.n_pods for c in compiled_traces), default=0), 1)
    return n_pods if pod_window is None else 2 * T


def _build_spread(profile, compiled_traces, config, n_nodes: int, n_pods: int, pod_window):
    """Host arrays of the build's state.SpreadState vocabulary leaves, or
    None where no pod is held to a topology-spread constraint: the profile
    does not run PodTopologySpread (the constraints are then inert, as
    upstream's are with the plugin off), or no trace carries one. Each
    cluster keeps its own interned workloads and domains; the table is as
    wide as the widest. Refuses, by name, what the batched filter does not
    cover."""
    from kubernetriks_tpu.batched.pipeline import (
        UnsupportedProfileError,
        uses_spread,
    )

    carrying = [c.spread for c in compiled_traces if c.spread is not None]
    if not carrying or not uses_spread(profile):
        return None
    if _makes_objects_at_run_time(config, compiled_traces):
        raise UnsupportedProfileError(
            "topology-spread constraints together with the horizontal pod autoscaler or the "
            "cluster autoscaler are not supported: pods and nodes made at run time would need "
            "labels of their own"
        )
    C = len(compiled_traces)
    G = max(len(sp.workloads) for sp in carrying)
    Z = max(max(len(sp.domains) for sp in carrying), 1)
    width = _global_pod_plane_width(compiled_traces, n_pods, pod_window)
    out = {
        "domain": np.full((C, n_nodes), -1, np.int32),
        "max_skew": np.full((C, G, Z), np.iinfo(np.int32).max, np.int32),
        "pod_group": np.full((C, width), -1, np.int32),
        "pod_bits": np.zeros((C, width), np.int32),
        "pod_zone": np.full((C, width), -1, np.int32),
    }
    for ci, trace in enumerate(compiled_traces):
        sp = trace.spread
        if sp is None:
            continue
        out["domain"][ci, : len(sp.node_domain)] = sp.node_domain
        out["max_skew"][ci, : len(sp.max_skew), :] = sp.max_skew[:, None]
        out["pod_group"][ci, : len(sp.pod_group)] = sp.pod_group
        out["pod_bits"][ci, : len(sp.pod_bits)] = sp.pod_bits
    return out


def event_chunk_size(ev_time: np.ndarray, interval: float) -> int:
    """The event loop's chunk (max_events_per_window) a trace asks for.

    A window's event application runs ceil(m / chunk) passes, m the largest
    count of events any cluster of the batch has due in it, and a pass
    costs about the same whether it applies 5 events or 60 (a kernel
    launch, a slab read, the loop condition's gather: PERF.md section 6,
    PR 38). So the chunk is the 90th percentile, over the windows that hold
    events, of that batch-wide maximum: the typical window takes ONE pass,
    and a burst window (1000 CreateNodes at t = 0) stays the outlier the
    while_loop absorbs in a few more. Rounded up to whole slab blocks
    (TraceSlab.read_chunk fetches whole blocks), at least one and at most
    four."""
    rows, cols = np.nonzero(np.isfinite(ev_time))
    if rows.size == 0:
        return SLAB_BLOCK_EVENTS
    win = np.floor_divide(ev_time[rows, cols], interval).astype(np.int64)
    # Counts a (window, cluster), window-major, then the maximum a window.
    keys, per_key = np.unique(win * ev_time.shape[0] + rows, return_counts=True)
    key_win = keys // ev_time.shape[0]
    starts = np.flatnonzero(np.r_[True, key_win[1:] != key_win[:-1]])
    per_window = np.maximum.reduceat(per_key, starts)
    typical = int(np.percentile(per_window, 90, method="lower"))
    blocks = -(-typical // SLAB_BLOCK_EVENTS)
    return SLAB_BLOCK_EVENTS * min(max(blocks, 1), 4)


def _build_affinity(profile, compiled_traces, config, n_nodes: int, n_pods: int, pod_window):
    """Host arrays of the build's state.AffinityState planes, or None where
    the build carries none: the profile runs neither NodeAffinity nor
    TaintToleration (taints and terms are then inert, as upstream's are with
    the plugins off), or no trace has a taint, a nodeSelector, a node
    affinity or a toleration. Each cluster keeps its own interned bits; the
    build holds as many term planes as its widest pod has terms. With the
    soft planes (`pod_soft_*`) where the profile scores by NodeAffinity or
    TaintToleration and a trace has a preferred term or a PreferNoSchedule
    taint. Refuses, by name, what the batched filters do not cover, and a
    preference the profile would ignore."""
    from kubernetriks_tpu.batched.pipeline import (
        UnsupportedProfileError,
        scores_softly,
        uses_affinity,
    )
    from kubernetriks_tpu.batched.trace_compile import AFFINITY_NO_TERM
    from kubernetriks_tpu.core.scheduler.plugins import (
        ignores_preferences,
        unscored_preferred_term,
        unscored_soft_taint,
    )

    carrying = [c.affinity for c in compiled_traces if c.affinity is not None]
    if not carrying or not uses_affinity(profile):
        return None
    no_terms, no_taints = ignores_preferences(profile.filters, [name for name, _ in profile.scores])
    for af in carrying:
        if no_terms and af.first_preferring is not None:
            raise unscored_preferred_term(af.first_preferring)
        if no_taints and af.first_soft_tainted is not None:
            raise unscored_soft_taint(af.first_soft_tainted)
    if _makes_objects_at_run_time(config, compiled_traces):
        raise UnsupportedProfileError(
            "node taints, nodeSelectors, node affinities and tolerations together with the horizontal "
            "pod autoscaler or the cluster autoscaler are not supported: pods and nodes made at run "
            "time would need labels and taints of their own"
        )
    C = len(compiled_traces)
    n_terms = max(af.pod_terms.shape[0] for af in carrying)
    width = _global_pod_plane_width(compiled_traces, n_pods, pod_window)
    out = {
        "node_bits": np.zeros((C, n_nodes), np.int32),
        "pod_terms": np.full((C, n_terms, width), AFFINITY_NO_TERM, np.int32),
        "pod_forbid": np.zeros((C, width), np.int32),
    }
    out["pod_terms"][:, 0, :] = 0  # a slot no trace names holds a pod that names no node
    soft = [af for af in carrying if af.soft_terms is not None]
    if soft and scores_softly(profile):
        n_soft = max(af.soft_terms.shape[0] for af in soft)
        out["pod_soft_terms"] = np.full((C, n_soft, width), AFFINITY_NO_TERM, np.int32)
        out["pod_soft_weights"] = np.zeros((C, width), np.int32)
        out["pod_soft_forbid"] = np.zeros((C, width), np.int32)
    for ci, trace in enumerate(compiled_traces):
        af = trace.affinity
        if af is None:
            continue
        out["node_bits"][ci, : len(af.node_bits)] = af.node_bits
        terms, pods = af.pod_terms.shape
        out["pod_terms"][ci, :terms, :pods] = af.pod_terms
        out["pod_forbid"][ci, :pods] = af.pod_forbid
        if "pod_soft_terms" in out and af.soft_terms is not None:
            out["pod_soft_terms"][ci, : af.soft_terms.shape[0], :pods] = af.soft_terms
            out["pod_soft_weights"][ci, :pods] = af.soft_weights
            out["pod_soft_forbid"][ci, :pods] = af.soft_forbid
    return out


def _integer_profile_statics(profile, compiled_traces, node_cap_cpu, node_cap_ram, config):  # ktpu: sync-ok(engine build: host numpy over the traces' capacity tables, no device values)
    """(units, soft taint bits) of a build whose profile scores in integers
    (pipeline.is_integer_profile), refusing by name what the integer scorers
    cannot hold: a capacity pair whose product, in units of the build's gcds,
    passes int32 a hundred times over; a node or pod whose RAM is not a whole
    RAM unit (the device would score the rounded number); objects made at
    run time (their shapes would have to join the gcd)."""
    from kubernetriks_tpu.batched.pipeline import (
        INTEGER_PRODUCT_LIMIT,
        UnsupportedProfileError,
        integer_score_units,
    )

    if _makes_objects_at_run_time(config, compiled_traces):
        raise UnsupportedProfileError(
            f"scheduler profile {profile.name!r} scores in integers of the build's common resource "
            "units: together with the horizontal pod autoscaler or the cluster autoscaler it is not "
            "supported (pods and nodes made at run time would have to join the units)"
        )
    distinct = list({id(c): c for c in compiled_traces}.values())
    for c in distinct:
        if c.inexact_ram is not None:
            raise UnsupportedProfileError(
                f"{c.inexact_ram}: its RAM is not a whole number of RAM units, and profile "
                f"{profile.name!r} scores in integers: the device holds the rounded number, which "
                "would score otherwise than the bytes do (give whole units, or a smaller ram_unit)"
            )
    units = integer_score_units(
        [(c.pod_req_cpu, c.pod_req_ram) for c in distinct], [(node_cap_cpu, node_cap_ram)]
    )
    whole = (np.asarray(node_cap_cpu, np.int64) // units[0]) * (
        np.asarray(node_cap_ram, np.int64) // units[1]
    )
    if whole.size and int(whole.max()) > INTEGER_PRODUCT_LIMIT:
        ci, slot = np.unravel_index(int(whole.argmax()), whole.shape)
        names = compiled_traces[ci].node_names
        name = names[slot] if slot < len(names) else f"node slot {slot}"
        raise UnsupportedProfileError(
            f"node {name!r}: capacity {int(node_cap_cpu[ci, slot])} x {int(node_cap_ram[ci, slot])} is "
            f"{int(whole.max())} in units of the build's gcds {units}, and 100 times that passes int32: "
            f"profile {profile.name!r} cannot score it in integers"
        )
    soft_taints = max(
        (len(c.affinity.soft_taints) for c in distinct if c.affinity is not None), default=0
    )
    return units, soft_taints


def _lex_name_ranks(names) -> np.ndarray:  # ktpu: sync-ok(host-side name-rank table builder over python name lists, no device values)
    """Rank of each slot's name in the stable lexicographic sort of
    `names` — THE scalar-parity ordering primitive (the scalar storage
    walks name-sorted snapshots). Used by both the autoscale statics and
    the standalone fault-run rank tables; keep them on this one
    implementation so the rank rules can't drift apart."""
    order = np.argsort(np.asarray(names, dtype=object), kind="stable")
    out = np.empty(len(names), np.int32)
    out[order] = np.arange(len(names), dtype=np.int32)
    return out


def _reclaim_class_tables(
    compiled_traces,
    group_names,
    reserves,
    n_trace_nodes: int,
    S: int,
):
    """Static name-CLASS tables for the CA slot-reclaim orders
    (autoscale.ca_name_order): one class per trace node (a singleton
    name) and one per CA node group (the decimal name FAMILY
    "{group}_{d}", d >= 1 — the scalar's total_allocated naming, which
    occupies the lexicographic interval ["{group}_1", "{group}_:") since
    every suffix starts with a digit 1-9 and ':' is the character after
    '9'). The global name order then decomposes into a static cross-class
    order plus the dynamic decimal-suffix order within a group — but ONLY
    if no class interleaves another. This verifies exactly that, per
    cluster, and returns (ca_slot_class (C, S), ca_class_start (C, Gn),
    node_class_key (C, N_total), None) on success or (None, None, None,
    reason) when the name sets make reclaim's order decomposition
    unsound (the engine then refuses or falls back, loudly).
    """
    C = len(compiled_traces)
    Gn = len(group_names)
    fams = [(f"{name}_1", f"{name}_:") for name in group_names]
    for i in range(Gn):
        for j in range(i + 1, Gn):
            lo_i, hi_i = fams[i]
            lo_j, hi_j = fams[j]
            if lo_i < hi_j and lo_j < hi_i:
                return None, None, None, (
                    f"CA node-group name families {group_names[i]!r} and "
                    f"{group_names[j]!r} interleave lexicographically"
                )
    PAD_KEY = np.int32(1 << 30)
    ca_slot_class = np.zeros((C, S), np.int32)
    ca_class_start = np.zeros((C, Gn), np.int32)
    node_class_key = np.full((C, n_trace_nodes + S), PAD_KEY, np.int32)
    memo: dict = {}
    for ci, trace in enumerate(compiled_traces):
        names = list(trace.node_names[:n_trace_nodes])
        key = id(trace)
        got = memo.get(key)
        if got is None:
            for t in names:
                for gi, (lo, hi) in enumerate(fams):
                    if lo <= t < hi:
                        return None, None, None, (
                            f"trace node name {t!r} falls inside CA "
                            f"group {group_names[gi]!r}'s name family"
                        )
            # Total class order: singletons by their name, families by
            # their interval start (disjoint intervals make this the
            # global lexicographic order of every current & future name).
            entries = [(t, ("t", slot)) for slot, t in enumerate(names)]
            entries += [
                (fams[gi][0], ("f", gi)) for gi in range(Gn)
            ]
            entries.sort(key=lambda e: e[0])
            n_classes = len(entries)
            if n_classes * (S + 1) >= (1 << 31) - (S + 1):
                return None, None, None, (
                    f"{n_classes} name classes x (S + 1 = {S + 1}) "
                    "overflows the int32 name-key space"
                )
            trace_rank = np.full(n_trace_nodes, -1, np.int64)
            fam_rank = np.zeros(Gn, np.int64)
            for rank, (_, tag) in enumerate(entries):
                if tag[0] == "t":
                    trace_rank[tag[1]] = rank
                else:
                    fam_rank[tag[1]] = rank
            got = memo[key] = (trace_rank, fam_rank)
        trace_rank, fam_rank = got
        nk = node_class_key[ci]
        named = trace_rank >= 0
        nk[:n_trace_nodes][named] = (trace_rank[named] * (S + 1)).astype(
            np.int32
        )
        cursor = 0
        for gi, reserve in enumerate(reserves):
            ca_slot_class[ci, cursor : cursor + reserve] = np.int32(
                fam_rank[gi]
            )
            nk[n_trace_nodes + cursor : n_trace_nodes + cursor + reserve] = (
                np.int32(fam_rank[gi] * (S + 1))
            )
            cursor += reserve
        # First class-sorted slot position of each group's reserve: the
        # groups in family-class order, cumulative reserve widths.
        order = np.argsort(fam_rank, kind="stable")
        pos = 0
        for gi in order:
            ca_class_start[ci, gi] = pos
            pos += reserves[gi]
    return ca_slot_class, ca_class_start, node_class_key, None


def build_autoscale_statics(
    config: SimulationConfig,
    compiled_traces,
    n_pods: int,
    n_trace_nodes: int,
    ram_unit: int,
    ca_slot_multiplier: int = 2,
    pod_slot_offset: int = 0,
    sliding: bool = False,
    scenario=None,
):
    """Host-side compilation of pod-group (HPA) and node-group (CA) tables.
    pod_slot_offset: global-to-device pod-slot shift for the resident
    pod-group segment under a sliding pod window (0 = full-resident); the
    HPA tables live entirely in DEVICE coordinates.

    scenario: optional per-lane override vectors (fleet.SCENARIO_KEYS,
    each (C,)) — the scenario-bearing control-law parameters (scan
    intervals, thresholds, CA period, autoscaler-chain delays, per-lane
    enables/quotas) are ALWAYS composed per-cluster through
    fleet.scenario_leaves and land as (C,)-shaped traced leaves, so one
    compiled program serves any scenario mix; with scenario=None every
    lane carries the base config's values (value-identical to the
    pre-fleet scalar fold).

    Returns (statics, extra_node_cap_cpu (S,), extra_node_cap_ram (S,),
    extra_node_names, aux); the extra node slots are the CA's reserved slots,
    appended after the trace's node slots (the batched analog of pre-sizing the
    component pool with the autoscaler max, reference: src/simulator.rs:212-230;
    slots are never reused, hence the churn multiplier). aux carries the
    host-side tables engine.update_scenario needs to recompose leaves
    without rebuilding (pg_active_when_on: (C, Gp) f64 activation times
    as if the HPA were on everywhere; +inf on padding groups)."""
    from kubernetriks_tpu.batched.fleet import scenario_leaves

    C = len(compiled_traces)
    ca_on = config.cluster_autoscaler.enabled
    leaves = scenario_leaves(config, C, scenario)

    # --- HPA pod groups -----------------------------------------------------
    Gp = max((len(c.pod_groups) for c in compiled_traces), default=0) or 1
    U = 1
    for c in compiled_traces:
        for g in c.pod_groups:
            U = max(U, len(g.cpu_units), len(g.ram_units))

    pg_slot_start = np.zeros((C, Gp), np.int32)
    pg_slot_count = np.zeros((C, Gp), np.int32)
    pg_initial = np.zeros((C, Gp), np.int32)
    pg_max_pods = np.zeros((C, Gp), np.int32)
    pg_target_cpu = np.zeros((C, Gp), np.float32)
    pg_target_ram = np.zeros((C, Gp), np.float32)
    pg_active_from = np.full((C, Gp), np.inf, np.float64)
    pg_active_when_on = np.full((C, Gp), np.inf, np.float64)
    pg_creation_s = np.zeros((C, Gp), np.float64)
    pg_cpu_dur = np.zeros((C, Gp, U), np.float32)
    pg_cpu_load = np.zeros((C, Gp, U), np.float32)
    pg_cpu_const = np.zeros((C, Gp), bool)
    pg_ram_dur = np.zeros((C, Gp, U), np.float32)
    pg_ram_load = np.zeros((C, Gp, U), np.float32)
    pg_ram_const = np.zeros((C, Gp), bool)
    pod_group_id = np.full((C, n_pods), -1, np.int32)

    for ci, c in enumerate(compiled_traces):
        for gi, g in enumerate(c.pod_groups):
            pg_slot_start[ci, gi] = g.slot_start - pod_slot_offset
            pg_slot_count[ci, gi] = g.slot_count
            pg_initial[ci, gi] = g.initial
            pg_max_pods[ci, gi] = g.max_pods
            pg_target_cpu[ci, gi] = g.target_cpu
            pg_target_ram[ci, gi] = g.target_ram
            # With HPA disabled the group's initial pods still run (the
            # api-server expansion is unconditional) but no cycle ever acts.
            # active_from = creation + register delay (the first HPA tick that
            # sees the group, reference: horizontal_pod_autoscaler.rs:187-198).
            # Per-LANE enable (scenario vector): a disabled lane parks its
            # groups at +inf — the data encoding of "HPA off" the fleet's
            # lane configs use.
            pg_creation_s[ci, gi] = g.creation_time
            pg_active_when_on[ci, gi] = (
                g.creation_time + config.as_to_hpa_network_delay
            )
            pg_active_from[ci, gi] = (
                pg_active_when_on[ci, gi]
                if leaves["hpa_enabled"][ci]
                else np.inf
            )
            for ui, (dur, load) in enumerate(g.cpu_units):
                pg_cpu_dur[ci, gi, ui] = dur
                pg_cpu_load[ci, gi, ui] = load
            pg_cpu_const[ci, gi] = g.cpu_const
            for ui, (dur, load) in enumerate(g.ram_units):
                pg_ram_dur[ci, gi, ui] = dur
                pg_ram_load[ci, gi, ui] = load
            pg_ram_const[ci, gi] = g.ram_const
            dev_start = g.slot_start - pod_slot_offset
            pod_group_id[ci, dev_start : dev_start + g.slot_count] = gi

    # --- CA node groups -----------------------------------------------------
    ca_config = config.cluster_autoscaler
    groups = (
        sorted(
            ca_config.node_groups, key=lambda g: g.node_template.metadata.name
        )
        if ca_on
        else []
    )
    Gn = len(groups) or 1
    reserves = []
    for g in groups:
        per_group_cap = g.max_count if g.max_count is not None else ca_config.max_node_count
        reserves.append(min(per_group_cap, ca_config.max_node_count) * ca_slot_multiplier)
    S = sum(reserves) or 1

    ng_ca_start = np.zeros((C, Gn), np.int32)
    ng_slot_count = np.zeros((C, Gn), np.int32)
    ng_max_count = np.full((C, Gn), -1, np.int32)
    ng_tmpl_cpu = np.zeros((C, Gn), np.int32)
    ng_tmpl_ram = np.zeros((C, Gn), np.int32)
    ca_slots = np.full((C, S), -1, np.int32)
    ca_slot_group = np.full((C, S), -1, np.int32)
    extra_cap_cpu = np.zeros((S,), np.int32)
    extra_cap_ram = np.zeros((S,), np.int32)
    extra_node_names = []

    cursor = 0
    for gi, (g, reserve) in enumerate(zip(groups, reserves)):
        name = g.node_template.metadata.name
        assert name, "CA node templates must be named"
        cap = g.node_template.status.capacity
        ng_ca_start[:, gi] = cursor
        ng_slot_count[:, gi] = reserve
        ng_max_count[:, gi] = -1 if g.max_count is None else g.max_count
        ng_tmpl_cpu[:, gi] = int(cap.cpu)
        ng_tmpl_ram[:, gi] = int(cap.ram) // ram_unit
        for k in range(reserve):
            ca_slots[:, cursor + k] = n_trace_nodes + cursor + k
            ca_slot_group[:, cursor + k] = gi
            extra_cap_cpu[cursor + k] = int(cap.cpu)
            extra_cap_ram[cursor + k] = int(cap.ram) // ram_unit
            extra_node_names.append(f"{name}_{k + 1}")
        cursor += reserve

    interval = config.scheduling_cycle_interval

    def pair(x) -> TPair:
        """Scalar or array seconds -> device TPair (host-side f64 split)."""
        w, o = from_f64_np(np.asarray(x, np.float64), interval)
        return TPair(win=jnp.asarray(w), off=jnp.asarray(o))

    f64 = lambda x: jnp.asarray(x, jnp.float64)  # noqa: E731

    # Scenario-bearing control-law parameters (scan intervals, thresholds,
    # the drifting CA period, the autoscaler-chain delay compositions) are
    # composed per-LANE by fleet.scenario_leaves — the one owner of those
    # formulas (incl. the cluster_autoscaler.rs:256-262 overrun rule) —
    # and land below as (C,)-shaped traced leaves.

    # Lexicographic name ranks of the trace's pods (device slot coords):
    # the storage's unscheduled-cache snapshot is name-sorted
    # (persistent_storage.py scale_up_info; reference
    # persistent_storage.rs:137-146), and the CA bin-packs in that order.
    # Ranks are static only while device slots don't shift — under a
    # sliding pod window they stay BIG and the cache keeps insertion order
    # (count-exact, identity documented in docs/PARITY.md). HPA ring slots
    # beyond the trace's initial replicas get fresh names at runtime and
    # likewise stay BIG.
    BIG_RANK = np.int32(1 << 30)
    # Tiled batches repeat a handful of compiled traces across many
    # clusters; memoize the object-dtype argsorts per unique trace.
    _rank_cache: dict = {}

    def _ranks_for(names_key, names):
        got = _rank_cache.get(names_key)
        if got is None:
            got = _rank_cache[names_key] = _lex_name_ranks(names)
        return got

    pod_name_rank = np.full((C, n_pods), BIG_RANK, np.int32)
    if not sliding and pod_slot_offset == 0:
        for ci, trace in enumerate(compiled_traces):
            ranks = _ranks_for(("pod", id(trace)), trace.pod_names[:n_pods])
            pod_name_rank[ci, : len(ranks)] = ranks

    # Node-name ranks over trace nodes + CA slots (slot names are static:
    # slot k of group g is always "{g}_{k+1}", matching the scalar's
    # total_allocated naming). The CA scale-down walks candidates and
    # first-fits re-placements in NAME order (info.nodes is name-sorted,
    # persistent_storage.sorted_nodes) — slot order differs once a name set
    # straddles a digit boundary ("g_10" < "g_2") or trace names interleave.
    # The node axis only gains the S reserved CA slots when the engine
    # actually appends them (CA on with named groups) — the rank array must
    # match the axis exactly (a stale +S here broadcast-crashed HPA-only
    # configs with >1 node; N=1 configs masked it via size-1 broadcasting).
    N_total = n_trace_nodes + (S if extra_node_names else 0)
    node_name_rank = np.full((C, N_total), BIG_RANK, np.int32)
    ca_sd_order = np.tile(np.arange(S, dtype=np.int32), (C, 1))
    for ci, trace in enumerate(compiled_traces):
        names = list(trace.node_names[:n_trace_nodes]) + extra_node_names
        ranks = _ranks_for(("node", id(trace)), names)
        node_name_rank[ci, : len(ranks)] = ranks
        if extra_node_names:
            ca_ranks = node_name_rank[ci, n_trace_nodes:]
            ca_sd_order[ci] = np.argsort(ca_ranks, kind="stable").astype(
                np.int32
            )

    # Reclaim name-order tables (r14): built whenever a CA reserve exists
    # and the name classes verify non-interleaving; otherwise None with
    # the reason in aux — the engine falls back (or raises on an explicit
    # reclaim=True) instead of running an unsound order decomposition.
    rc_slot_class = rc_class_start = rc_node_key = None
    if ca_on and extra_node_names:
        rc_slot_class, rc_class_start, rc_node_key, reclaim_reason = (
            _reclaim_class_tables(
                compiled_traces,
                [g.node_template.metadata.name for g in groups],
                reserves,
                n_trace_nodes,
                S,
            )
        )
    elif ca_on:
        reclaim_reason = "the CA reserve is empty (no named node groups)"
    else:
        reclaim_reason = "the cluster autoscaler is disabled"

    # The scalar metrics collector's fixed pod-utilization pull cadence
    # (60 s), as device time for the HPA collection latch.
    from kubernetriks_tpu.metrics.collector import MetricsCollector

    statics = AutoscaleStatics(
        pg_slot_start=jnp.asarray(pg_slot_start),
        pg_slot_count=jnp.asarray(pg_slot_count),
        pg_initial=jnp.asarray(pg_initial),
        pg_max_pods=jnp.asarray(pg_max_pods),
        pg_target_cpu=jnp.asarray(pg_target_cpu),
        pg_target_ram=jnp.asarray(pg_target_ram),
        pg_active_from=pair(pg_active_from),
        pg_creation_s=jnp.asarray(pg_creation_s),
        pg_cpu_dur=jnp.asarray(pg_cpu_dur),
        pg_cpu_load=jnp.asarray(pg_cpu_load),
        pg_cpu_total=jnp.asarray(pg_cpu_dur.sum(axis=-1)),
        pg_cpu_const=jnp.asarray(pg_cpu_const),
        pg_ram_dur=jnp.asarray(pg_ram_dur),
        pg_ram_load=jnp.asarray(pg_ram_load),
        pg_ram_total=jnp.asarray(pg_ram_dur.sum(axis=-1)),
        pg_ram_const=jnp.asarray(pg_ram_const),
        pod_group_id=jnp.asarray(pod_group_id),
        ng_ca_start=jnp.asarray(ng_ca_start),
        ng_slot_count=jnp.asarray(ng_slot_count),
        ng_max_count=jnp.asarray(ng_max_count),
        ng_tmpl_cpu=jnp.asarray(ng_tmpl_cpu),
        ng_tmpl_ram=jnp.asarray(ng_tmpl_ram),
        ca_max_nodes=jnp.asarray(leaves["ca_max_nodes"], jnp.int32),
        ca_slots=jnp.asarray(ca_slots),
        ca_slot_group=jnp.asarray(ca_slot_group),
        hpa_interval=pair(leaves["hpa_interval_s"]),
        hpa_tolerance=f64(leaves["hpa_tolerance"]),
        ca_threshold=f64(leaves["ca_threshold"]),
        d_hpa_up=pair(leaves["d_hpa_up_s"]),
        d_hpa_down=pair(leaves["d_hpa_down_s"]),
        d_ca_up=pair(leaves["d_ca_up_s"]),
        d_ca_down=pair(leaves["d_ca_down_s"]),
        ca_period=pair(leaves["ca_period_s"]),
        ca_snap=pair(leaves["ca_snap_s"]),
        ca_finish_vis=pair(leaves["ca_finish_vis_s"]),
        ca_commit_vis=pair(leaves["ca_commit_vis_s"]),
        pod_name_rank=jnp.asarray(pod_name_rank),
        node_name_rank=jnp.asarray(node_name_rank),
        ca_sd_order=jnp.asarray(ca_sd_order),
        col_interval=pair(
            np.full((C,), MetricsCollector.COLLECTION_INTERVAL, np.float64)
        ),
        ca_slot_class=(
            None if rc_slot_class is None else jnp.asarray(rc_slot_class)
        ),
        ca_class_start=(
            None if rc_class_start is None else jnp.asarray(rc_class_start)
        ),
        node_class_key=(
            None if rc_node_key is None else jnp.asarray(rc_node_key)
        ),
    )
    aux = {
        "pg_active_when_on": pg_active_when_on,
        "reclaim_unsupported": reclaim_reason,
    }
    return statics, extra_cap_cpu, extra_cap_ram, extra_node_names, aux


class BatchedSimulation:
    @build_span
    def __init__(  # ktpu: sync-ok(engine build: cold-path host compilation of traces/tables, outside every timed region)
        self,
        config: SimulationConfig,
        compiled_traces: Sequence[CompiledClusterTrace],
        ram_unit: int = DEFAULT_RAM_UNIT,
        max_events_per_window: Optional[int] = None,
        max_pods_per_cycle: Optional[int] = None,
        mesh: Optional[Mesh] = None,
        batch_axis: str = "clusters",
        ca_slot_multiplier: int = 2,
        max_ca_pods_per_cycle: int = 64,
        max_pods_per_scale_down: int = 8,
        use_pallas: Optional[bool] = None,
        pallas_interpret: bool = False,
        pod_window: Optional[int] = None,
        fast_forward: Optional[bool] = None,
        donate: Optional[bool] = None,
        fuse_slide: Optional[bool] = None,
        superspan: Optional[bool] = None,
        superspan_k: Optional[int] = None,
        superspan_chunk: Optional[int] = None,
        superspan_stage_cols: Optional[int] = None,
        stream: Optional[bool] = None,
        stream_depth: Optional[int] = None,
        stream_segment: Optional[int] = None,
        sanitize_mode: Optional[bool] = None,
        telemetry: Optional[bool] = None,
        telemetry_ring: int = 1024,
        watchdog: Optional[bool] = None,
        lane_major: Optional[bool] = None,
        window_razor: Optional[bool] = None,
        reclaim: Optional[bool] = None,
        scheduler_profile=None,
        scenario=None,
        lane_async: bool = False,
    ) -> None:
        self.config = config
        self.mesh = mesh
        self._batch_axis = batch_axis
        # The window programs' sharding static (sharding.over_clusters):
        # None without a mesh, and then no program is wrapped.
        self._shards = None if mesh is None else ClusterShards(mesh, batch_axis)
        # Scenario-vector fleet (batched/fleet.py): optional per-lane
        # override vectors for the autoscaler control-law parameters.
        # Validated + normalized to (C,) numpy arrays here; the statics
        # build below composes them into the (C,)-shaped traced leaves
        # and the chaos block installs per-lane pod-fault seeds as
        # consts.fault_seed. None = every lane runs the base config
        # (value-identical leaves to the pre-fleet scalar fold).
        from kubernetriks_tpu.batched.fleet import normalize_scenario

        self._scenario = normalize_scenario(scenario, len(compiled_traces))
        # Compiled scheduler profile (batched/pipeline.py): the configured
        # Filter/Score plugin profile lowered to kernel statics. Resolution
        # order: explicit arg > config.scheduler_profile > KTPU_PROFILE env
        # (bench/CLI selection) > the reference default. compile_profile
        # RAISES (UnsupportedProfileError, naming the plugin and the
        # supported set) on anything the batched path cannot lower —
        # never a silent fallback to the hard-coded default.
        from kubernetriks_tpu.batched.pipeline import compile_profile

        if scheduler_profile is None:
            scheduler_profile = getattr(config, "scheduler_profile", None)
        if scheduler_profile is None:
            scheduler_profile = flag_str("KTPU_PROFILE")
        self.profile = compile_profile(scheduler_profile)
        # Flight recorder. The host span recorder is process-wide and
        # always on (telemetry/tracer.py); KTPU_TRACE / the telemetry arg
        # switch what changes compiled programs: the device-side
        # per-window metrics ring carried in ClusterBatchState (attached
        # below, once C is known) and the observatory. Off: the state
        # carries telemetry=None, compiling programs identical to the
        # pre-telemetry build. telemetry_ring: ring capacity in windows
        # (the engine drains before wrap at existing sync boundaries).
        if telemetry is not None:
            self._telemetry = bool(telemetry)
        else:
            self._telemetry = flag_bool("KTPU_TRACE")
        # This engine's handle on it: the shared ring, and aggregates of
        # its own for telemetry_report().
        self.tracer = recorder().handle()
        self._telemetry_ring_size = max(8, int(telemetry_ring))
        # Saturation watchdog (KTPU_WATCHDOG / watchdog arg): the capacity
        # observatory's trajectory checks over the ring's reserve-occupancy
        # columns (telemetry/observatory.py). Rides the flight recorder —
        # unset means "armed exactly when telemetry is"; an explicit
        # watchdog=True with telemetry off would silently watch nothing,
        # so it raises (the stream-without-superspan precedent).
        if watchdog is not None:
            self._watchdog = bool(watchdog)
        else:
            env = flag_tristate("KTPU_WATCHDOG")
            self._watchdog = self._telemetry if env is None else bool(env)
        if self._watchdog and not self._telemetry:
            raise ValueError(
                "watchdog=True requires the flight recorder (telemetry="
                "True / KTPU_TRACE=1): the saturation watchdog reads the "
                "device ring's reserve-occupancy columns"
            )
        # window-index -> (C, K) drained ring rows, deduped across
        # overlapping drains (telemetry/ring.py) and BOUNDED: the host
        # series keeps at most telemetry_series_windows distinct windows
        # (oldest pruned first, disclosed as ring.series_dropped_windows)
        # — without the cap the observatory's lossless mid-call drains
        # would re-grow an O(T) host term on exactly the endurance runs
        # they exist to watch. The default (64k windows ≈ 11 MB at the
        # composed shape) far exceeds any bench/test span; endurance
        # consumers stream the full series through the JSONL exporter
        # instead of holding it resident.
        self._ring_seen: dict = {}
        self.telemetry_series_windows = 1 << 16
        self._ring_series_dropped = 0
        self._ring_windows_recorded = 0  # device cursor high-water mark
        self._ring_drained_at = 0  # window cursor of the last ring drain
        self._pending_flow = 0  # tracer flow id of an in-flight readback
        # Runtime sanitizer (KTPU_SANITIZE / sanitize_mode arg): the
        # steady-state dispatch region runs under a device-to-host
        # transfer guard (waived syncs carry explicit allow scopes that
        # mirror the lint pass's sync-ok waivers), donated inputs are
        # force-deleted after donated calls so read-after-donate raises
        # even on CPU, and the KTPU_DEBUG_FINITE sweep runs at every
        # dispatch boundary. See kubernetriks_tpu/sanitize.py.
        self._sanitize = (
            bool(sanitize_mode)
            if sanitize_mode is not None
            else sanitize.sanitize_default()
        )
        # Every performance static is decided in batched/statics.py (explicit
        # kwarg > its KTPU_* flag > platform default), once, here. What
        # follows narrows the record by what only the engine knows (a
        # cross-process mesh, a lane-async build; reclaim's geometry check
        # further down) and assigns the attributes the rest of the engine
        # and its callers read.
        requested = locals()
        st = resolve_statics(
            {name: requested[name] for name in STATIC_NAMES},
            jax.default_backend(),
        )
        if mesh is not None and is_cross_process(mesh):
            # The feeder thread's uploads go through put_global, whose
            # collective ordering across hosts is only coordinated on the
            # engine thread. Single-process meshes (a whole v5e-8 included)
            # stream normally; cross-process runs keep the resident
            # device-slide payload path.
            st = dataclasses.replace(st, stream=False)
        # Lane-asynchronous fleet mode (batched/fleet.py, DESIGN §13):
        # per-lane window clocks in StepConstants (lane_clock/lane_horizon)
        # let each lane run its own virtual span inside the shared window
        # programs — a finished lane is frozen by the window body and
        # re-seeded in place (set_lane_plan + lane_reset) while neighbors
        # keep stepping. Requires a SCENARIO build (the per-lane reset
        # pristine + scenario leaves are the substrate) and the
        # full-resident dispatch path: the sliding window, superspan
        # executor, streaming feeder, fused slide and fast-forward skip all
        # assume one fleet-global clock, so composing them here would be a
        # silent correctness hazard — asked for by name they raise; their
        # flags and accelerator defaults are turned off instead.
        self.lane_async = bool(lane_async)
        if self.lane_async:
            if self._scenario is None:
                raise ValueError(
                    "lane_async=True requires a scenario build (scenario="
                    "{...} / ScenarioFleet): per-lane resets re-seed from "
                    "the scenario pristine"
                )
            if pod_window is not None:
                raise ValueError(
                    "lane_async=True requires the full-resident pod path "
                    "(pod_window=None): the sliding window's refill cursor "
                    "is fleet-global"
                )
            global_clock = ("superspan", "stream", "fuse_slide")
            named = [
                f"{name}=True"
                for name in global_clock
                if st.source[name] == "kwarg" and getattr(st, name)
            ]
            if named:
                raise ValueError(
                    f"lane_async=True is incompatible with {', '.join(named)}"
                    ": the superspan executor, the streaming feeder and the "
                    "fused slide assume one fleet-global window clock"
                )
            st = dataclasses.replace(st, **dict.fromkeys(global_clock, False))
            fast_forward = False
        self.statics = st
        self.donate = st.donate
        self._fuse_slide = st.fuse_slide
        self._superspan = st.superspan
        self._superspan_k = st.superspan_k
        self._superspan_chunk = st.superspan_chunk
        self._superspan_stage_cols = st.superspan_stage_cols
        self._stream = st.stream
        self._stream_depth = st.stream_depth
        self._stream_segment = st.stream_segment
        self.lane_major = st.lane_major
        self.window_razor = st.window_razor
        # None: reclaim was left to the platform default, so the build may
        # turn it off where it cannot hold and a restore may follow the
        # checkpoint; asked for by kwarg or flag, both raise instead.
        # Finalized after the autoscale statics are built below.
        self._reclaim_requested = (
            None if st.source["reclaim"] == "default" else st.reclaim
        )
        self.reclaim = False
        # The live feeder (stream.StreamFeeder) — built lazily at the
        # first staged dispatch, closed + rebuilt (re-seek) on window
        # growth and checkpoint restore. _feeder_produced_total carries
        # the production counter across those re-seeks so
        # dispatch_stats["feeder_slabs_produced"] is cumulative.
        self._feeder = None
        self._feeder_produced_total = 0
        # Feeder supervisor (PR 19, DESIGN §15): producer death surfaces
        # as FeederProducerError at get_stage; the supervisor rebuilds
        # the feeder with exponential backoff, carrying the dead ring's
        # retired-slab high-water mark so never-re-offer spans restarts.
        # A chaos injector (KTPU_HOST_CHAOS, or set directly by tests)
        # rides into every feeder built so the kill channel draws inside
        # the producer thread.
        self._feeder_restarts = 0
        self._feeder_restart_cap = 5
        self._feeder_backoff_s = 0.005
        self._feeder_chaos = None
        if flag_str("KTPU_HOST_CHAOS") is not None:
            from kubernetriks_tpu.batched.faults import HostChaos

            self._feeder_chaos = HostChaos.from_flag(
                flag_str("KTPU_HOST_CHAOS")
            )
        # (lo, RefillStage) staging buffers for the superspan executor when
        # the whole-trace payload exceeds the device budget: the stage the
        # next dispatch reads, and the double-buffered successor assembled
        # while the current superspan runs on device (_prefetch_stage).
        self._stage_cur = None
        self._stage_next = None
        # (shift-array, new-name-rank-or-None) of a fused slide whose host
        # resolution is still pending (step_until_time resolves it at the
        # span boundary).
        self._pending_shift = None
        # (start, width, refill pytree) prefetched for the HOST slide path
        # while a span's chunks run on device (_prefetch_refill).
        self._refill_prefetch = None
        # Dispatch accounting for the steady-state loop, asserted by the
        # dispatch-count regression test: window_chunks counts device
        # dispatches that advance windows (fused_slides of them also slid),
        # slide_dispatches counts SEPARATE shift/apply dispatches (0 when
        # fused), slide_syncs counts blocking host readbacks that gate a
        # slide decision, refill_prefetches counts host-path payload
        # prefetches that overlapped device compute.
        # superspans counts run_superspan dispatches (each is one device
        # program covering up to K slide-spans and ONE blocking progress
        # readback, also counted in slide_syncs); superspan_spans counts the
        # slide-spans those dispatches completed on device; stage_refills
        # counts staging-buffer installs (whole-trace-payload engines never
        # restage).
        # ladder_fallbacks counts step_until_time calls where a
        # superspan-selected engine dispatched the ladder instead
        # (instrumented modes, gauge collection, fast-forward): the
        # silent-fallback observable, in every telemetry_report
        # (tests/test_telemetry.py::test_ladder_fallback_counter).
        # feeder_slabs_produced mirrors the streaming feeder's production
        # counter (0 on non-streaming engines): stage_refills counts slabs
        # the dispatch loop INSTALLED, feeder_slabs_produced counts slabs
        # the producer BUILT — produced >> installed means wasted
        # production (stride too small), produced == installed with
        # feeder-not-ready stalls means a starved feeder (raise
        # stream_depth / widen segments). Both land in telemetry_report.
        self.dispatch_stats = {
            "window_chunks": 0,
            "fused_slides": 0,
            "slide_dispatches": 0,
            "slide_syncs": 0,
            "refill_prefetches": 0,
            "superspans": 0,
            "superspan_spans": 0,
            "stage_refills": 0,
            "feeder_slabs_produced": 0,
            "ladder_fallbacks": 0,
        }
        self._use_pallas_requested = use_pallas
        self.pallas_interpret = bool(pallas_interpret)
        self.use_pallas = bool(use_pallas)  # finalized after shapes are known
        self.conditional_move = bool(
            config.enable_unscheduled_pods_conditional_move
        )
        self.consts = make_step_constants(config)
        self.ram_unit = ram_unit
        compiled_traces = list(compiled_traces)
        C = len(compiled_traces)

        # Fast-forward (run_windows_skip): the skip only pays when whole
        # spans are provably empty; on dense traces every window is
        # interesting and the per-window interesting-check + while_loop
        # structure COST ~14% (measured: 8-day replay at 1.55 events/window
        # 229 s -> 261 s). Default: auto-enable below 0.25 trace events per
        # window (set after the trace is compiled, below); exactness either
        # way is pinned by tests/test_fast_forward.py.
        self._fast_forward_requested = fast_forward
        self.fast_forward = bool(fast_forward)  # finalized once density is known
        # Windows per flush period in the SAME f32 arithmetic the step uses,
        # so the skip's flush-window prediction can never disagree.
        d = 1
        while (
            np.float32(d) * np.float32(config.scheduling_cycle_interval)
            < np.float32(self.consts.flush_interval)
        ):
            d += 1
        self._flush_windows = d

        # Sliding pod window (SURVEY §5.8 host/device streaming, pod axis):
        # the device pod arrays cover only [pod_base, pod_base + pod_window)
        # of the trace's PLAIN pod slots; as old pods terminate the window
        # shifts forward, refilled from the host payload. Per-window cost is
        # then bounded by max concurrency, not trace length, so arbitrarily
        # long traces stream through fixed-size device state. HPA pod groups
        # compose with the window via the segmented slot layout
        # (trace_compile.segment_pod_slots): their reserved ring slots are
        # renumbered past every plain pod and stay device-RESIDENT after the
        # window segment, because group pods are long-running services that
        # would block the window's terminal-prefix shift.
        # 0 / negative mirror the CLI's "disabled" sentinel: full-resident.
        if pod_window is not None and pod_window <= 0:
            pod_window = None
        trace_pod_bound = None
        if any(c.pod_groups for c in compiled_traces):
            # The segmented layout is CANONICAL whenever pod groups exist,
            # windowed or not: slot order feeds order-sensitive passes (CA
            # scale-down re-placement, same-window reschedule ranking), so
            # windowed and full-resident runs must share one layout to stay
            # equivalent.
            from kubernetriks_tpu.batched.trace_compile import segment_pod_slots

            compiled_traces, trace_pod_bound = segment_pod_slots(compiled_traces)
            if trace_pod_bound == 0:
                # Pure pod-group workload: nothing for the window to slide
                # over — every slot is ring-resident; run full-resident.
                pod_window = None
        self.pod_window = pod_window
        self._pod_base = 0
        self._full_pods = None
        self._payload_source = None
        self._resident_shift = 0

        # Full-resident runs 128-align the pod axis: the Pallas wrapper pads
        # (operand copies from jnp.pad before every kernel launch) become
        # no-ops when P is already a tile multiple. Padded slots are exactly
        # batch-padding slots (req 0, duration sentinel, no create event —
        # phase stays EMPTY forever). The sliding path keeps exact widths:
        # its segmented [window | resident] layout derives device offsets
        # from the plain-slot count, and the device window W is already the
        # caller's tile-friendly choice.
        n_pods_aligned = None
        if pod_window is None and flag_bool("KTPU_ALIGN_PODS"):
            p_max = max((c.n_pods for c in compiled_traces), default=0)
            n_pods_aligned = -(-max(p_max, 1) // 128) * 128

        (
            ev_time,
            ev_kind,
            ev_slot,
            node_cap_cpu,
            node_cap_ram,
            pod_req_cpu,
            pod_req_ram,
            pod_duration,
            crash_downtime_cum,
        ) = pad_and_batch(compiled_traces, n_pods=n_pods_aligned)

        # Host-side node-event schedule for point-in-time readouts
        # (node_count_at): a slab event applies only when its WINDOW
        # executes, so a trace/chaos node transition earlier in the
        # current (unexecuted) window is visible in neither the alive
        # flags nor the pending effect pairs — the readout resolves it
        # from this table. Node events only: O(nodes + crash chains),
        # never O(T).
        from kubernetriks_tpu.batched.state import (
            EV_CREATE_NODE,
            EV_NODE_CRASH,
            EV_NODE_RECOVER,
            EV_REMOVE_NODE,
        )

        _node_kind = np.isin(
            ev_kind,
            (EV_CREATE_NODE, EV_REMOVE_NODE, EV_NODE_CRASH, EV_NODE_RECOVER),
        )
        _ev_win_all, _ = from_f64_np(ev_time, config.scheduling_cycle_interval)
        self._node_event_table = [
            (
                ev_time[ci][_node_kind[ci]],
                np.isin(
                    ev_kind[ci][_node_kind[ci]],
                    (EV_CREATE_NODE, EV_NODE_RECOVER),
                ),
                ev_slot[ci][_node_kind[ci]],
                _ev_win_all[ci][_node_kind[ci]],
            )
            for ci in range(C)
        ]

        # Chaos engine: static fault constants (None = off, identical
        # programs) and the KTPU_DEBUG_FINITE guard mode (host-side NaN/inf
        # sweep after every dispatched chunk; off by default so the donated
        # hot path is untouched).
        from kubernetriks_tpu.chaos import make_fault_params

        # Node faults are on wherever the traces carry a crash or a
        # recovery, whoever sampled them: build_batched_from_traces from
        # config.fault_injection (chaos.inject_node_faults), or a caller
        # who hands the build events it sampled itself
        # (RemoveNodeRequest(crashed=True, downtime_s=...) /
        # CreateNodeRequest(recovered=True) in the cluster trace).
        self.fault_params = make_fault_params(
            config,
            node_fault_events=bool(
                np.isin(ev_kind, (EV_NODE_CRASH, EV_NODE_RECOVER)).any()
            ),
        )
        self._debug_finite = flag_bool("KTPU_DEBUG_FINITE")
        # Per-lane pod-fault seeds (scenario vector): traced (C,) data in
        # StepConstants — each lane's attempt draws key on (seed[c],
        # cluster 0), making its fault stream a pure function of the
        # scenario (lane-permutation invariance; fleet re-seeds are data,
        # not recompiles). Installed ONLY under a scenario build so
        # scenario-less engines keep the pre-fleet consts pytree (and the
        # per-cluster keying the chaos suite pins).
        if (
            self._scenario is not None
            and self.fault_params is not None
            and self.fault_params.fail_prob > 0
        ):
            from kubernetriks_tpu.batched.fleet import scenario_leaves

            seeds = scenario_leaves(config, C, self._scenario)["fault_seed"]
            self.consts = self.consts._replace(
                fault_seed=jnp.asarray(
                    seeds.astype(np.uint32), jnp.uint32
                )
            )
        # Lane-async clocks: traced (C,) data in StepConstants, plus the
        # host-side numpy mirrors the completion arithmetic reads (the
        # traced leaves themselves are never read on the host — the
        # scenariotrace pass's compile-once contract). All lanes start
        # INACTIVE (horizon 0): the fleet arms each lane with
        # set_lane_plan when it assigns a query.
        if self.lane_async:
            self._lane_clock_np = np.zeros((C,), np.int64)
            self._lane_horizon_np = np.zeros((C,), np.int64)
            self.consts = self.consts._replace(
                lane_clock=jnp.asarray(self._lane_clock_np, jnp.int32),
                lane_horizon=jnp.asarray(self._lane_horizon_np, jnp.int32),
            )

        if pod_window is not None:
            # Cross-process meshes are supported through the device-resident
            # slide path: the shift amount is a replicated scalar (readable
            # on every process), slices/concats run SPMD, and the payload is
            # placed with put_global. Only the HOST fallback path needs
            # every shard addressable — __init__ refuses cross-process
            # builds whose payload exceeds the device budget (below).
            P_full = pod_req_cpu.shape[1]
            # T: first resident (pod-group ring) slot; the window slides over
            # plain slots [0, T) only.
            T = trace_pod_bound if trace_pod_bound is not None else P_full
            pod_window = min(pod_window, T)
            self.pod_window = pod_window
            self._resident_shift = T - pod_window
            self.consts = self.consts._replace(
                trace_pod_bound=np.int32(T),
                resident_shift=np.int32(self._resident_shift),
            )
            # Window index of each plain pod slot's create event (slots are
            # assigned in event order, so this is per-row nondecreasing) —
            # the O(1) capacity lookup for the dispatch loop. Group-slot
            # creations (initial replicas) target the resident tail and never
            # constrain the window.
            ev_win_np, _ = from_f64_np(ev_time, config.scheduling_cycle_interval)
            create_win = np.full((C, T), np.iinfo(np.int32).max, np.int32)
            rows_np = np.arange(C)[:, None]
            is_cp = (ev_kind == 3) & (ev_slot < T)  # EV_CREATE_POD, plain
            create_win[
                np.broadcast_to(rows_np, ev_kind.shape)[is_cp],
                ev_slot[is_cp],
            ] = ev_win_np[is_cp]
            self._pod_create_win = create_win
            self._full_pods = {
                "req_cpu": pod_req_cpu[:, :T],
                "req_ram": pod_req_ram[:, :T],
                "duration": pod_duration[:, :T],
            }
            # Payload seam (ROADMAP #2 host-memory bound): every refill /
            # staging consumer reads request+duration columns through
            # this source. The build default wraps the resident arrays;
            # attach_payload_source swaps in a bounded segment reader and
            # RELEASES them, making steady-state host RSS O(stage width).
            from kubernetriks_tpu.batched.trace_compile import (
                ArrayPayloadSource,
            )

            self._payload_source = ArrayPayloadSource(self._full_pods)
            # Lexicographic pod-name ranks over the WHOLE trace (global pod
            # coords): the window's device slice is refreshed on every slide
            # (statics are traced arguments, so no recompile), keeping the
            # name-ordered semantics (CA cache order, reschedule queue
            # order) identical between sliding and full-resident runs.
            BIG_RANK = np.int32(1 << 30)
            self._pod_name_rank_full = np.full((C, P_full), BIG_RANK, np.int32)
            _rank_cache: dict = {}
            for ci, trace in enumerate(compiled_traces):
                ranks = _rank_cache.get(id(trace))
                if ranks is None:
                    order_np = np.argsort(
                        np.asarray(trace.pod_names, dtype=object), kind="stable"
                    )
                    ranks = np.empty(len(trace.pod_names), np.int32)
                    ranks[order_np] = np.arange(
                        len(trace.pod_names), dtype=np.int32
                    )
                    _rank_cache[id(trace)] = ranks
                self._pod_name_rank_full[ci, : len(ranks)] = ranks
            # Device pod arrays: [window over plain slots | resident rings].
            pod_req_cpu = np.concatenate(
                [pod_req_cpu[:, :pod_window], pod_req_cpu[:, T:]], axis=1
            )
            pod_req_ram = np.concatenate(
                [pod_req_ram[:, :pod_window], pod_req_ram[:, T:]], axis=1
            )
            pod_duration = np.concatenate(
                [pod_duration[:, :pod_window], pod_duration[:, T:]], axis=1
            )

        # Autoscaler tables (HPA pod groups from the trace, CA node groups from
        # the config); the CA's reserved node slots are appended after the
        # trace's slots.
        hpa_on = config.horizontal_pod_autoscaler.enabled
        ca_on = config.cluster_autoscaler.enabled
        self.autoscale_statics = None
        self.max_ca_pods_per_cycle = max_ca_pods_per_cycle
        self.max_pods_per_scale_down = max_pods_per_scale_down
        # Per-cluster reserve capacities for the capacity observatory's
        # occupancy gauges (telemetry/observatory.py): total HPA pod-group
        # slots and total CA node slots. Host python ints, fetched ONCE
        # here at build time (cold path, before mesh placement).
        self._reserve_capacities: dict = {}
        self.pod_group_names = [[g.name for g in c.pod_groups] for c in compiled_traces]
        self._autoscale_aux: dict = {}
        if hpa_on or ca_on:
            statics, extra_cpu, extra_ram, extra_names, aux = build_autoscale_statics(
                config,
                compiled_traces,
                n_pods=pod_req_cpu.shape[1],
                n_trace_nodes=node_cap_cpu.shape[1],
                ram_unit=ram_unit,
                ca_slot_multiplier=ca_slot_multiplier,
                pod_slot_offset=self._resident_shift,
                sliding=pod_window is not None,
                scenario=self._scenario,
            )
            self.autoscale_statics = statics
            self._autoscale_aux = aux
            # Finalize the reclaim decision now that the name-order
            # tables' verification outcome is known.
            want = self.statics.reclaim
            supported = ca_on and statics.ca_slot_class is not None
            if want and not supported:
                reason = aux.get("reclaim_unsupported") or "unsupported"
                if self._reclaim_requested:
                    raise ValueError(
                        "reclaim=True (KTPU_RECLAIM) is unsupported for "
                        f"this build: {reason} — the allocation-name "
                        "order decomposition would be unsound; rename "
                        "the conflicting nodes/groups or run without "
                        "reclaim"
                    )
                if ca_on:
                    import warnings as _warnings

                    _warnings.warn(
                        "KTPU_RECLAIM default-on disabled: "
                        f"{reason}; the CA reserve stays monotone "
                        "(engine.check_autoscaler_bounds remains the "
                        "only backstop)",
                        RuntimeWarning,
                        stacklevel=2,
                    )
                want = False
            self.reclaim = bool(want and supported)
            self._reserve_capacities = {
                "hpa_reserve": [
                    int(v)
                    for v in np.asarray(statics.pg_slot_count).sum(axis=1)
                ],
                "ca_reserve": [
                    int(v)
                    for v in np.asarray(statics.ng_slot_count).sum(axis=1)
                ],
            }
            if ca_on and extra_names:
                node_cap_cpu = np.concatenate(
                    [node_cap_cpu, np.tile(extra_cpu, (C, 1))], axis=1
                )
                node_cap_ram = np.concatenate(
                    [node_cap_ram, np.tile(extra_ram, (C, 1))], axis=1
                )
        else:
            extra_names = []

        self.n_clusters = C
        self.n_nodes = node_cap_cpu.shape[1]
        self.n_pods = pod_req_cpu.shape[1]
        # The profile the cycle programs compile: the user's, with the
        # exact-ranking static the traces call for (heterogeneous requests;
        # pipeline.exact_score_bits). self.profile stays the user's own.
        from kubernetriks_tpu.batched.pipeline import exact_score_bits

        from kubernetriks_tpu.batched.pipeline import is_integer_profile

        distinct = {id(c): c for c in compiled_traces}.values()
        self._cycle_profile = self.profile._replace(
            exact_bits=exact_score_bits(
                self.profile,
                [(c.pod_req_cpu, c.pod_req_ram) for c in distinct],
                [(node_cap_cpu, node_cap_ram)],
            )
        )
        if is_integer_profile(self.profile):
            units, soft_taints = _integer_profile_statics(
                self.profile, compiled_traces, node_cap_cpu, node_cap_ram, config
            )
            self._cycle_profile = self._cycle_profile._replace(units=units, soft_taints=soft_taints)
        # Topology spread: a pod of the build is held to a constraint where
        # the profile runs PodTopologySpread AND a trace carries one. Only
        # then does the state get the filter's leaves (state.SpreadState) and
        # the programs its table; the fit gates below count its blocks.
        spread_host = _build_spread(
            self.profile, compiled_traces, config, self.n_nodes, self.n_pods, pod_window
        )
        self._spread_shape = (
            None if spread_host is None else tuple(spread_host["max_skew"].shape[1:])
        )
        # Node affinity and taints: likewise, one node plane and the pods'
        # mask planes where the profile runs NodeAffinity or TaintToleration
        # AND a trace carries a taint, a selector, an affinity or a
        # toleration (state.AffinityState); the fit gates count the blocks.
        affinity_host = _build_affinity(
            self.profile, compiled_traces, config, self.n_nodes, self.n_pods, pod_window
        )
        self._affinity_terms = (
            None if affinity_host is None else int(affinity_host["pod_terms"].shape[1])
        )
        # The integer scorers' blocks, for the same gates: None for a profile
        # that ranks otherwise, else the build's preferred-term planes (0: no
        # soft plane, the two capacity planes alone).
        self._kube_terms = None
        if is_integer_profile(self.profile):
            soft_terms = (affinity_host or {}).get("pod_soft_terms")
            self._kube_terms = 0 if soft_terms is None else int(soft_terms.shape[1])
        # Real (trace-defined) pod slots, before the 128-alignment padding
        # of the device axis — the count completion/terminal asserts want.
        self.n_real_pods = max((c.n_pods for c in compiled_traces), default=0)
        self.n_events = ev_time.shape[1]

        # Per-window event application runs in CHUNKS of this size inside a
        # while_loop until the window's due events are exhausted, so this is a
        # typical-case tile size, not a worst-case bound: a trace whose worst
        # window has thousands of events (e.g. the t=0 cluster creation burst)
        # pays a few extra loop iterations there instead of taxing every
        # window with a burst-sized gather/scatter. Sized from the trace's
        # own per-window counts (event_chunk_size) unless given: a
        # performance static only, the loop is exact at any chunk.
        if max_events_per_window is None:
            max_events_per_window = event_chunk_size(
                ev_time, config.scheduling_cycle_interval
            )
        self.max_events_per_window = max(1, max_events_per_window)
        # The size of one PASS of the scheduling cycle. The cycle drains its
        # queue as the scalar path does (reference scheduler.rs:261), in as
        # many passes as that takes (step._run_scheduling_cycle), so this
        # sizes the K-shaped kernels' blocks and changes speed, no result;
        # the megakernel, which drains in its own loop, only reads it for the
        # drain counters. (The RL policy cycle, rl/env.py, is still one pass
        # of this many candidates.)
        self.max_pods_per_cycle = max(1, max_pods_per_cycle or self.n_pods)

        # Finalize the Pallas decision now that shapes are known. Default: on
        # for real-TPU runs whose blocks fit VMEM (overridable via the
        # use_pallas arg or KUBERNETRIKS_PALLAS=0/1). Under a mesh each device
        # runs the whole window program on its shard (sharding.py), so the
        # gate is the PER-SHARD cluster count, and C must divide the mesh
        # evenly.
        from kubernetriks_tpu.ops.scheduler_kernel import (
            default_enabled,
            kernel_fits,
            select_kernel_fits,
        )

        n_shards = 1 if mesh is None else mesh.size
        assert self.n_clusters % n_shards == 0, (
            f"a mesh build needs n_clusters ({self.n_clusters}) divisible by "
            f"the mesh size ({n_shards}): every device runs an equal shard"
        )
        if self._use_pallas_requested is None:
            # Default-on whenever the blocks fit: even at C=1 (the trace-replay
            # shape, where the 128-lane cluster tile is almost all padding) the
            # kernel's data-dependent early exit over candidates beats the
            # K-step lax.scan, which pays all K sequential iterations while a
            # cycle has about ten pending pods: a whole replay job of 1,701
            # windows takes 0.502 s against 8.35 s at C=1, N=1313, P=4096,
            # K=256 with exact ranking (one v5e; PERF.md section 6, PR 28; the
            # cell `alibaba1313.replay` reports which formulation it ran).
            self.use_pallas = (
                default_enabled()
                and self.n_clusters % n_shards == 0
                and kernel_fits(
                    self.n_nodes, self.max_pods_per_cycle, self._spread_shape,
                    self._affinity_terms, self._kube_terms,
                )
            )
        # Prefer the fused selection kernel (in-kernel queue argmin instead
        # of the (C, P) lexsort) when its pod blocks fit VMEM AND the
        # 128-cluster lane tiles are mostly real: at small C the padding
        # waste loses to the sort+candidate kernel, while dense batches win
        # by dropping the sort. Since the kernels sweep only their live pod
        # rows (PR 27) the loss at C=1 is small: the same replay job takes
        # 0.609 s with the select kernel forced on and 0.515 s with the
        # megakernel, against the candidate kernel's 0.502 s (PERF.md
        # section 6, PR 28).
        self.use_pallas_select = (
            self.use_pallas
            and self.n_clusters // n_shards >= 128
            and select_kernel_fits(
                self.n_nodes, self.n_pods, self.max_pods_per_cycle,
                self._spread_shape, self._affinity_terms, self._kube_terms,
            )
        )
        # The r4 megakernel (selection + cycle + commit in one launch) is the
        # default on the dense path when its larger VMEM footprint fits;
        # KTPU_MEGAKERNEL=0 selects the two-kernel path (A/B measurement).
        # Read at BUILD time and threaded as a jit-static, so toggling the
        # env between engine builds takes effect without cache collisions.
        from kubernetriks_tpu.ops.scheduler_kernel import (
            select_commit_kernel_fits,
        )

        self.use_megakernel = (
            self.use_pallas_select
            and flag_bool("KTPU_MEGAKERNEL")
            and select_commit_kernel_fits(
                self.n_nodes, self.n_pods, self.max_pods_per_cycle,
                self._spread_shape, self._affinity_terms, self._kube_terms,
            )
        )
        # The fit gates above (and the CA kernels' in autoscale.py) degrade
        # by shape without raising; say once what they picked.
        import logging

        logging.getLogger(__name__).info(
            "kernel formulation at %d x %d nodes x %d pods: %s",
            self.n_clusters, self.n_nodes, self.n_pods,
            self.kernel_formulation(),
        )

        self.state = init_state(
            C,
            self.n_nodes,
            self.n_pods,
            node_cap_cpu,
            node_cap_ram,
            pod_req_cpu,
            pod_req_ram,
            pod_duration,
            interval=config.scheduling_cycle_interval,
        )
        if spread_host is not None:
            from kubernetriks_tpu.batched.state import SpreadState

            self.state = self.state._replace(
                spread=SpreadState(
                    **{k: jnp.asarray(v) for k, v in spread_host.items()},
                    decisions=jnp.zeros((C,), jnp.int32),
                    decisions_bound=jnp.zeros((C,), jnp.int32),
                )
            )
        if affinity_host is not None:
            from kubernetriks_tpu.batched.state import AffinityState

            self.state = self.state._replace(
                affinity=AffinityState(
                    **{k: jnp.asarray(v) for k, v in affinity_host.items()},
                    attempts=jnp.zeros((C,), jnp.int32),
                    attempts_refused=jnp.zeros((C,), jnp.int32),
                )
            )
            if self._kube_terms:
                self.state = self.state._replace(
                    metrics=self.state.metrics._replace(
                        soft_attempts=jnp.zeros((C,), jnp.int32),
                        soft_honoured=jnp.zeros((C,), jnp.int32),
                    )
                )
        # Static (lo, hi) device-slot bounds covering every pod-group slot:
        # the HPA pass only touches group slots, so its body (victim sort
        # included) and its not-due cond carry run on this slice instead of
        # the full (C, P) pod axis (autoscale.hpa_pass). (0, 0) = the HPA
        # can never act (off, no groups, or empty reserves) — the step skips
        # the pass entirely and hpa_next parks at +inf below to match.
        self._hpa_seg = (0, 0)
        if self.autoscale_statics is not None and (
            hpa_on and any(c.pod_groups for c in compiled_traces)
        ):
            starts = np.asarray(self.autoscale_statics.pg_slot_start)
            counts = np.asarray(self.autoscale_statics.pg_slot_count)
            gmask = counts > 0
            if gmask.any():
                seg_lo = max(int(starts[gmask].min()), 0)
                seg_hi = min(int((starts + counts)[gmask].max()), self.n_pods)
                self._hpa_seg = (
                    (seg_lo, seg_hi) if seg_hi > seg_lo else (0, 0)
                )
        if self.autoscale_statics is not None:
            # collect: arm the HPA collection latch (the 60 s staleness
            # fix) whenever the HPA can actually act; reclaim: arm the CA
            # slot-reclaim leaves (allocation indices + counters).
            auto = init_autoscale_state(
                self.autoscale_statics,
                reclaim=self.reclaim,
                collect=self._hpa_seg != (0, 0),
            )
            # When the step skips hpa_pass (seg == (0, 0)), park its tick at
            # +inf so everything that reads hpa_next (fast-forward's
            # _next_interesting_window, _catch_up_bookkeeping) agrees the
            # HPA never fires.
            if self._hpa_seg == (0, 0):
                from kubernetriks_tpu.batched.timerep import t_inf

                auto = auto._replace(hpa_next=t_inf((C,)))
            self.state = self.state._replace(auto=auto)
            # Seed the replica indices of the trace's INITIAL group replicas
            # (created by slab events, which don't carry hpa_idx): the i-th
            # reserved slot's first occupant is "{group}_{i}".
            gid_np = np.asarray(self.autoscale_statics.pod_group_id)
            if (gid_np >= 0).any():
                start_np = np.asarray(self.autoscale_statics.pg_slot_start)
                init_np = np.asarray(self.autoscale_statics.pg_initial)
                P_dev = gid_np.shape[1]
                gidc = np.clip(gid_np, 0, None)
                off_np = (
                    np.arange(P_dev, dtype=np.int32)[None, :]
                    - np.take_along_axis(start_np, gidc, axis=1)
                )
                seeded = (gid_np >= 0) & (
                    off_np < np.take_along_axis(init_np, gidc, axis=1)
                )
                hpa_idx0 = np.where(seeded, off_np, -1).astype(np.int32)
                self.state = self.state._replace(
                    pods=self.state.pods._replace(
                        hpa_idx=jnp.asarray(hpa_idx0)
                    )
                )
        self.observatory = None
        if self._telemetry:
            # Attach the device metrics ring BEFORE mesh placement below,
            # so its leaves pick up the state sharding like every other
            # (C, ...) array. Presence is a structural static (like
            # `auto`): telemetry-off engines compile identical programs.
            from kubernetriks_tpu.telemetry.ring import init_ring

            self.state = self.state._replace(
                telemetry=init_ring(C, self._telemetry_ring_size)
            )
            # Capacity observatory (telemetry/observatory.py): occupancy
            # series + memory watermarks + the saturation watchdog, fed
            # strictly from drained host copies at the ring's existing
            # drain points (_maybe_drain_ring / drain_telemetry).
            from kubernetriks_tpu.telemetry.observatory import Observatory

            self.observatory = Observatory(
                interval=config.scheduling_cycle_interval,
                capacities=self._reserve_capacities,
                watchdog=self._watchdog,
                counters=self.tracer.counters,
            )
        ev_win, ev_off = from_f64_np(ev_time, config.scheduling_cycle_interval)
        if (
            crash_downtime_cum is None
            and self.fault_params is not None
            and self.fault_params.node_faults
        ):
            # node faults configured, none sampled: a table of no crash
            crash_downtime_cum = np.zeros((C, 1), np.float32)
        self.slab = TraceSlab.build(
            ev_win, ev_off, ev_kind, ev_slot, crash_downtime=crash_downtime_cum
        )
        self._ev_time_np = ev_time  # host copy (f64) for completion checks
        self._lane_mux = None
        if self.lane_async:
            # Per-lane trace multiplexer (DESIGN §13): host copy of the
            # just-built packed slab (build-time fetch of a host-sourced
            # array — the cold construction boundary, not a steady-state
            # sync), plus a warm pass of the data-only row install so the
            # first RANGED query re-seeds under the sentinel without
            # compiling anything.
            from kubernetriks_tpu.batched.stream import LaneTraceMux

            self._lane_mux = LaneTraceMux(np.asarray(self.slab.rows())[:, : self.n_events])  # ktpu: sync-ok(build-time host copy of the freshly built trace slab's real rows for the lane mux — construction boundary, no steady-state device read)
            rows = self._lane_mux.offer(0)
            self._lane_mux.retire([0])
            self._install_lane_rows(
                0, rows if rows is not None else self._lane_mux._base[0]
            )
        if self._fast_forward_requested is None:
            finite = ev_time[np.isfinite(ev_time)]
            span = (
                max(1.0, float(finite.max()) / config.scheduling_cycle_interval)
                if finite.size
                else 1.0
            )
            density = finite.size / (C * span)  # trace events per window
            self.fast_forward = density < 0.25
        self.node_names = [c.node_names + extra_names for c in compiled_traces]
        self.pod_names = [c.pod_names for c in compiled_traces]
        self.next_window_idx = 0
        # Per-window gauge collection (batched analog of the scalar 5 s gauge
        # cycle): enable with collect_gauges, read via gauge_series() or
        # write_gauge_csv(). The series buffer lives in the telemetry
        # package (telemetry/gauges.py owns concat/CSV/sidecar); the
        # engine only performs the (waived) device fetches.
        self.collect_gauges = False
        self._gauges = GaugeSeries()
        # Set log_throughput for a per-chunk decisions/s +
        # cluster-windows/s log line (TPU analog of the scalar events/s
        # log, reference: src/simulator.rs:363-368). For a profile, start
        # jax.profiler round ordinary calls: every span of the recorder
        # is a `ktpu:<phase>` event on the xplane's host plane.
        self.log_throughput = False
        # Raise at readout when a documented autoscaler work bound was
        # crossed (HPA reserve clamp, CA slot-reserve exhaustion) instead of
        # silently reporting a diverged trajectory. Opt out for exploratory
        # runs with strict_autoscaler_bounds = False.
        self.strict_autoscaler_bounds = True

        self._sharding = None
        if mesh is not None:
            # Cross-process meshes (multi-host over DCN) can't device_put a
            # host-local array onto non-addressable devices; every process
            # holds the same compiled trace and contributes its shards.
            put = put_global if is_cross_process(mesh) else jax.device_put
            sharding = NamedSharding(mesh, PartitionSpec(batch_axis))
            self._sharding = sharding
            self.state = put(self.state, self._state_shardings(sharding, self.state))
            self.slab = put(self.slab, self._state_shardings(sharding, self.slab))
            if self.autoscale_statics is not None:
                self.autoscale_statics = put(
                    self.autoscale_statics,
                    self._state_shardings(sharding, self.autoscale_statics),
                )
        # Standalone name-rank tables for full-resident runs WITHOUT
        # autoscalers: same-instant reschedule batches (node crashes under
        # fault injection, but ALSO plain same-timestamp trace RemoveNode
        # events) need queue order following the scalar's sorted-name walk —
        # the slot-order fallback diverges there. Historically these tables
        # were built only for fault runs; the per-profile equivalence
        # sweeps surfaced a profile trajectory (balanced_packing, seed 101)
        # where two trace removals co-reschedule pods and slot order flips
        # the next cycle's queue, so the ranks are now built for EVERY
        # full-resident engine (two small int tables, memoized argsort).
        # With autoscalers on, the autoscale statics already carry the
        # ranks; under a sliding pod window without autoscalers the
        # slot-order stand-in remains (documented in docs/PARITY.md).
        self._fault_name_ranks = None
        if self.autoscale_statics is None and self.pod_window is None:
            BIG_RANK = np.int32(1 << 30)
            nnr = np.full((C, self.n_nodes), BIG_RANK, np.int32)
            pnr = np.full((C, self.n_pods), BIG_RANK, np.int32)
            # Workload traces are identical across clusters (only the node
            # fault schedules differ), so memoize the object-dtype argsort
            # by name tuple — the pod table is computed once for C clusters.
            memo: dict = {}

            def _ranks(names):
                key = tuple(names)
                got = memo.get(key)
                if got is None:
                    got = memo[key] = _lex_name_ranks(names)
                return got

            for ci in range(C):
                r = _ranks(self.node_names[ci])
                nnr[ci, : len(r)] = r
                r = _ranks(self.pod_names[ci])
                # The pod axis may be 128-aligned past the real names;
                # padding slots keep BIG_RANK.
                pnr[ci, : min(len(r), self.n_pods)] = r[: self.n_pods]
            ranks = (jnp.asarray(nnr), jnp.asarray(pnr))
            if self.mesh is not None:
                put = (
                    put_global if is_cross_process(self.mesh) else jax.device_put
                )
                ranks = put(ranks, self._state_shardings(self._sharding, ranks))
            self._fault_name_ranks = ranks

        # Sliding runs: install the initial windowed name-rank slice
        # (build_autoscale_statics leaves ranks BIG under sliding). Must run
        # AFTER self.mesh is assigned and the statics carry their final
        # sharding — _refresh_name_ranks re-puts with old.sharding.
        self._refresh_name_ranks()
        self._init_device_slide()
        if (
            self.pod_window is not None
            and self.mesh is not None
            and is_cross_process(self.mesh)
            and self._device_slide is None
            and not self._stream_on()
        ):
            raise ValueError(
                "pod_window on a cross-process mesh requires the "
                "device-resident slide payload, but this trace exceeds its "
                "memory budget — raise _DEVICE_SLIDE_BUDGET_BYTES, enlarge "
                "pod_window, or drop to a single-process mesh (the host "
                "slide path needs every shard addressable)"
            )

        # Scenario-vector fleets (batched/fleet.py) reset lanes against the
        # PRISTINE build state (fleet_reset's donation-friendly select
        # re-init). Snapshot it only for scenario builds — plain engines
        # must not pay a second full-state copy in device memory.
        self._pristine = None
        if self._scenario is not None:
            self._pristine = tree_copy(self.state)

    def kernel_formulation(self) -> dict:
        """What the static fit gates picked for this build: the scheduling
        cycle's formulation (scan < candidate < select < megakernel), how
        it ranks nodes (pipeline.exact_score_bits), how a mesh build is
        sharded (sharding.over_clusters) and, with the cluster
        autoscaler on, whether each CA walk runs as its
        Pallas kernel or as the XLA loop (the gates of
        autoscale._ca_scale_up / _ca_scale_down, same predicates). What
        chip_smoke.py and benchmark cells assert engagement on."""
        if self.use_megakernel:
            cycle = "megakernel"
        elif self.use_pallas_select:
            cycle = "select"
        else:
            cycle = "candidate" if self.use_pallas else "scan"
        out = {
            "cycle": cycle,
            "interpret": self.pallas_interpret,
            # how nodes are ranked for a pod: the float32 score, or the
            # exact key a trace of heterogeneous requests calls for
            # or kube-scheduler's integer scores (no float decides a rank)
            "ranking": "integer"
            if self._kube_terms is not None
            else "exact"
            if self._cycle_profile.exact_bits
            else "float32",
            # how the event chunk loop applies a chunk: fused_event_scatter,
            # or the XLA scatters that are its bit-identical fallback
            "events": event_path(
                self.use_pallas,
                self.use_pallas_select,
                self.n_nodes,
                self.n_pods,
                self.max_events_per_window,
                self.fault_params is not None and self.fault_params.node_faults,
            ),
            # how a mesh build is sharded: one shard_map round each whole
            # window program (sharding.py); None = no mesh, nothing wrapped
            "sharding": None,
        }
        if self._shards is not None:
            out.update(
                sharding="shard_map",
                shard_axis=self._shards.axis,
                shards=self._shards.mesh.size,
            )
        st = self.autoscale_statics
        if st is not None and self.config.cluster_autoscaler.enabled:
            from kubernetriks_tpu.ops.autoscale_kernel import (
                ca_down_kernel_fits,
                ca_up_kernel_fits,
            )

            S = st.ca_slots.shape[1]
            up = self.use_pallas and ca_up_kernel_fits(
                S, st.ng_ca_start.shape[1], self.max_ca_pods_per_cycle
            )
            down = self.use_pallas and ca_down_kernel_fits(
                self.n_nodes, S, self.max_pods_per_scale_down
            )
            out["ca_up"] = "kernel" if up else "xla"
            out["ca_down"] = "kernel" if down else "xla"
        return out

    def _slide_payload_fits(self, W: int) -> bool:
        """Whether the device-resident slide payload at window width W fits
        the memory budget — the ONE owner of the payload-size formula, used
        by _init_device_slide and by _grow_pod_window's pre-mutation check
        (req x2, dur pair x2, create window, + name ranks with statics)."""
        if self._full_pods is None:
            return False
        C, T = self._full_pods["req_cpu"].shape
        n_i32 = 5 + (1 if self.autoscale_statics is not None else 0)
        return C * (T + W) * 4 * n_i32 <= _DEVICE_SLIDE_BUDGET_BYTES

    def _init_device_slide(self) -> None:
        """Upload the slide payload (pod requests, durations, create
        windows, name ranks over the PLAIN trace segment) to the device so
        window slides run on-device. The host slide path's per-slide
        round-trips — the (C, W) phase fetch, the refill device_put, the
        name-rank device_put — are three array transfers per slide; the
        device path fetches one 4-byte shift.
        Falls back to the host path (payload stays None) above the memory
        budget."""
        self._device_slide = None
        if self.pod_window is None or self._full_pods is None:
            return
        if self._stream_on():
            # Streaming ingestion: the whole-trace payload is exactly what
            # the feeder exists to NOT materialize — the superspan loop
            # stages bounded slabs through the ring instead, and device
            # staging memory stays O(stream_depth x segment) regardless of
            # trace length.
            return
        full = self._full_pods
        T = full["req_cpu"].shape[1]
        W = self.pod_window
        has_rank = self.autoscale_statics is not None
        if not self._slide_payload_fits(W):
            return
        from kubernetriks_tpu.batched.state import duration_pair_np
        from kubernetriks_tpu.batched.trace_compile import stage_segment

        # The whole-trace payload is the lo = 0, width = T + W staging
        # segment — stage_segment owns the padding rules, so this payload
        # and the bounded RefillStage slabs (_make_stage) cannot drift.
        seg = stage_segment(
            self._payload_source,
            self._pod_create_win,
            self._pod_name_rank_full[:, :T] if has_rank else None,
            0,
            T + W,
        )
        dur_pair = duration_pair_np(
            seg.pop("duration"), self.config.scheduling_cycle_interval
        )
        payload = {
            "req_cpu": jnp.asarray(seg["req_cpu"]),
            "req_ram": jnp.asarray(seg["req_ram"]),
            "dur_win": dur_pair.win,
            "dur_off": dur_pair.off,
            "create_win": jnp.asarray(seg["create_win"]),
        }
        if has_rank:
            payload["rank"] = jnp.asarray(seg["rank"])
        if self._sharding is not None:
            put = (
                put_global
                if is_cross_process(self._sharding.mesh)
                else jax.device_put
            )
            payload = put(payload, self._state_shardings(self._sharding, payload))
        self._device_slide = payload

    def _state_shardings(self, sharding, tree):
        """Where a per-cluster pytree is placed: by the rule the window
        programs' shard_map reads its arguments with
        (sharding.cluster_specs)."""
        return jax.tree.map(
            lambda spec: NamedSharding(sharding.mesh, spec),
            cluster_specs(tree, sharding.spec[0]),
        )

    # --- stepping -----------------------------------------------------------

    @property
    def next_window(self) -> float:
        """Next scheduling-cycle time in seconds (windows are indexed; this is
        the float view tests and callers use)."""
        return self.next_window_idx * self.config.scheduling_cycle_interval

    @next_window.setter
    def next_window(self, t: float) -> None:
        interval = self.config.scheduling_cycle_interval
        idx = int(round(t / interval))
        assert abs(idx * interval - t) < 1e-9 * max(1.0, abs(t)), (
            f"next_window must be a multiple of the {interval}s cycle interval"
        )
        self.next_window_idx = idx

    def window_times(self, until_time: float) -> np.ndarray:
        """Scheduling-cycle times in [next_window, until_time], starting at 0
        like the scalar scheduler.start()."""
        interval = self.config.scheduling_cycle_interval
        idxs = self.window_idxs(until_time)
        return idxs.astype(np.float64) * interval

    def window_idxs(self, until_time: float) -> np.ndarray:
        interval = self.config.scheduling_cycle_interval
        first = self.next_window_idx
        count = int(math.floor(until_time / interval)) - first + 1
        return first + np.arange(max(count, 0), dtype=np.int32)

    def _window_call_kwargs(self) -> dict:
        """The window-program config kwargs shared by every dispatch and
        warm-up site (run_windows, run_windows_skip, the fused chunk+slide
        megastep). ONE owner — a new engine static added here reaches the
        warmed AND dispatched programs together, so precompile_chunks can
        never warm a program the loop then doesn't use. Callers add their
        entry-specific extras (collect_gauges, flush_windows, W)."""
        return dict(
            max_events_per_window=self.max_events_per_window,
            max_pods_per_cycle=self.max_pods_per_cycle,
            autoscale_statics=self.autoscale_statics,
            max_ca_pods_per_cycle=self.max_ca_pods_per_cycle,
            max_pods_per_scale_down=self.max_pods_per_scale_down,
            use_pallas=self.use_pallas,
            pallas_interpret=self.pallas_interpret,
            conditional_move=self.conditional_move,
            shards=self._shards,
            use_pallas_select=self.use_pallas_select,
            use_megakernel=self.use_megakernel,
            hpa_seg=self._hpa_seg,
            fault_params=self.fault_params,
            name_ranks=self._fault_name_ranks,
            lane_major=self.lane_major,
            window_razor=self.window_razor,
            reclaim=self.reclaim,
            profile=self._cycle_profile,
        )

    def _dispatch_windows(
        self,
        idxs: np.ndarray,
        fuse_slide: bool = False,
        freeze_lanes: bool = True,
    ) -> None:
        """Run one chunk of windows and fold the results into self.state
        (+ gauge accumulation). With fuse_slide, dispatch the chunk+slide
        megastep instead (_fused_chunk_slide): the returned shift's host
        readback starts immediately but is only consumed at the span
        boundary (_resolve_pending_slide), so no sync lands here."""
        self.dispatch_stats["window_chunks"] += 1
        tr = self.tracer
        tr.count(f"dispatch_chunk_{len(idxs)}")
        donated_in = self.state if (self.donate and self._sanitize) else None
        if fuse_slide:
            self.dispatch_stats["fused_slides"] += 1
            fn = _fused_chunk_slide_donated if self.donate else _fused_chunk_slide
            t0 = tr.begin(PH_FUSED_CHUNK_SLIDE)
            args = (
                self.state,
                self.slab,
                jnp.asarray(idxs, jnp.int32),
                self.consts,
                self._device_slide,
                np.int32(self._pod_base),
            )
            kwargs = dict(
                W=self.pod_window,
                **self._window_call_kwargs(),
            )
            tr.program(
                "fused_chunk_slide", (len(idxs), self.pod_window), fn, args, kwargs
            )
            state, new_rank, s = fn(*args, **kwargs)
            tr.end(PH_FUSED_CHUNK_SLIDE, t0)
            self.state = state
            if donated_in is not None:
                sanitize.consume_donated(donated_in)
            if new_rank is not None:
                # Device-to-device swap, no sync; identical values when the
                # slide turns out to be a no-op (s == 0).
                self.autoscale_statics = self.autoscale_statics._replace(
                    pod_name_rank=new_rank
                )
            if hasattr(s, "copy_to_host_async"):
                with sanitize.allow_transfer(
                    self._sanitize, "async shift prefetch"
                ):
                    s.copy_to_host_async()  # ktpu: sync-ok(async initiation of the waived 4-byte shift readback — does not block)
            self._pending_flow = tr.flow_start(PH_SHIFT_WAIT)
            self._pending_shift = s
            self.next_window_idx = int(idxs[-1]) + 1
            return
        if self.fast_forward and not self.collect_gauges:
            # Fast-forward dispatch: execute only interesting windows of the
            # span (bit-identical end state; see run_windows_skip). Gauge
            # collection needs every window's sample, so it keeps the scan.
            from kubernetriks_tpu.batched.step import (
                run_windows_skip,
                run_windows_skip_donated,
            )

            skip_fn = run_windows_skip_donated if self.donate else run_windows_skip
            t0 = tr.begin(PH_WINDOW_CHUNK)
            args = (
                self.state,
                self.slab,
                np.int32(idxs[0]),
                np.int32(idxs[-1]),
                self.consts,
            )
            kwargs = dict(
                flush_windows=self._flush_windows,
                **self._window_call_kwargs(),
            )
            tr.program(
                "run_windows_skip", (self.pod_window,), skip_fn, args, kwargs
            )
            self.state = skip_fn(*args, **kwargs)
            tr.end(PH_WINDOW_CHUNK, t0)
            if donated_in is not None:
                sanitize.consume_donated(donated_in)
            self.next_window_idx = int(idxs[-1]) + 1
            return
        from kubernetriks_tpu.batched.step import run_windows_donated

        win_fn = run_windows_donated if self.donate else run_windows
        t0 = tr.begin(PH_WINDOW_CHUNK)
        args = (
            self.state,
            self.slab,
            jnp.asarray(idxs, jnp.int32),
            self.consts,
        )
        kwargs = dict(
            collect_gauges=self.collect_gauges,
            freeze_lanes=freeze_lanes,
            **self._window_call_kwargs(),
        )
        tr.program(
            "run_windows",
            (len(idxs), freeze_lanes, self.pod_window),
            win_fn,
            args,
            kwargs,
        )
        out = win_fn(*args, **kwargs)
        tr.end(PH_WINDOW_CHUNK, t0)
        if self.collect_gauges:
            self.state, gauges = out
            with sanitize.allow_transfer(
                self._sanitize, "gauge time-series readback"
            ):
                self._gauges.append(np.asarray(idxs), to_host(gauges))  # ktpu: sync-ok(gauge instrumentation: per-chunk time-series readback, gauge runs are not the steady-state path)
        else:
            self.state = out
        if donated_in is not None:
            sanitize.consume_donated(donated_in)
        self.next_window_idx = int(idxs[-1]) + 1

    def precompile_chunks(self, max_chunk: int = 128) -> int:
        """Warm the sliding path's dispatch-chunk program shapes (the
        power-of-two ladder, plus the fused chunk+slide variants when they
        are in play) so no compile lands inside a timed region — a novel
        chunk shape costs seconds of compile.

        Each shape is dispatched once against a scratch COPY of the current
        state (so self.state survives buffer donation) with the CURRENT
        window index REPEATED chunk times: warm-up indices stay in range —
        never past the pod window's capacity — and a repeated window is
        quiet by construction (its due events, finishes and autoscaler
        ticks all resolve in the first scan iteration, leaving the rest of
        the chunk empty cycles). Per-shape warm-up compute is therefore
        bounded by ~one real window + (chunk - 1) empty cycles, instead of
        re-simulating chunk real windows per shape; idx VALUES are traced,
        so the compiled/warmed program is exactly the one the dispatch loop
        uses. Total cost: at most len(_CHUNK_LADDER) shapes (2x with the
        fused-slide variants), each one compile (seconds, cache hit
        when already warm) plus the bounded quiet
        execution. Returns the number of shapes dispatched. No-op on
        fast-forward or non-sliding engines (one program serves any span
        there). Superspan engines warm the ONE superspan program instead of
        the ladder — the steady-state loop never dispatches ladder chunks
        while the superspan path is selectable."""
        if self.pod_window is None or (
            self.fast_forward and not self.collect_gauges
        ):
            return 0
        if self._superspan_ok():
            # The superspan loop is the ONLY program the steady-state
            # dispatch will use (one shape serves every span/target), so
            # warm it instead of the ladder; a no-op progress code compiles
            # the whole while_loop without executing a window. Dispatched
            # against a scratch copy like the ladder shapes (donation).
            t_warm = self.tracer.begin(PH_PRECOMPILE)
            stage, lo = self._current_stage()
            rank = (
                self.autoscale_statics.pod_name_rank
                if self.autoscale_statics is not None
                else None
            )
            fn = run_superspan_donated if self.donate else run_superspan
            out = fn(
                tree_copy(self.state),
                rank,
                jnp.asarray(
                    [self.next_window_idx, self._pod_base, 0, SUPERSPAN_GROW],
                    jnp.int32,
                ),
                self.slab,
                self.consts,
                stage,
                jnp.int32(lo),
                jnp.int32(self.next_window_idx),
                W=self.pod_window,
                K=self._superspan_k,
                chunk=self._superspan_chunk,
                **self._window_call_kwargs(),
            )
            jax.block_until_ready(out)  # ktpu: sync-ok(warm-up: AOT compile of the superspan program, outside every timed region)
            self.tracer.end(PH_PRECOMPILE, t_warm)
            return 1
        from kubernetriks_tpu.batched.step import run_windows_donated

        win_fn = run_windows_donated if self.donate else run_windows
        n = 0
        t_warm = self.tracer.begin(PH_PRECOMPILE)
        warm_fused = self._fused_slide_ok()
        for chunk in _CHUNK_LADDER:
            if chunk > max_chunk:
                continue
            idxs = jnp.full((chunk,), self.next_window_idx, jnp.int32)
            out = win_fn(
                tree_copy(self.state),
                self.slab,
                idxs,
                self.consts,
                collect_gauges=self.collect_gauges,
                **self._window_call_kwargs(),
            )
            jax.block_until_ready(out)  # discarded: warm-up only  # ktpu: sync-ok(warm-up: AOT compile of the ladder shapes, outside every timed region)
            n += 1
            if warm_fused:
                fn = (
                    _fused_chunk_slide_donated
                    if self.donate
                    else _fused_chunk_slide
                )
                out = fn(
                    tree_copy(self.state),
                    self.slab,
                    idxs,
                    self.consts,
                    self._device_slide,
                    np.int32(self._pod_base),
                    W=self.pod_window,
                    **self._window_call_kwargs(),
                )
                jax.block_until_ready(out)  # ktpu: sync-ok(warm-up: AOT compile of the fused chunk+slide shapes, outside every timed region)
                n += 1
        self.tracer.end(PH_PRECOMPILE, t_warm)
        return n

    # --- scenario-vector fleet support (batched/fleet.py) -------------------

    def _scenario_rows(self) -> dict:
        """The scenario-bearing device leaves as HOST arrays, composed from
        `self._scenario` through fleet.scenario_leaves: the thirteen
        SCENARIO_TRACED_LEAVES of AutoscaleStatics (None without
        autoscalers) and consts.fault_seed (None where the build carries
        no seed leaf), each in its device dtype. The float64 arithmetic
        that splits a time into (win, off) runs here, on the host, so a
        device value is the same bits whichever transport carries it
        (update_scenario's per-leaf puts, admit_lanes' one buffer)."""
        from kubernetriks_tpu.batched.fleet import scenario_leaves

        leaves = scenario_leaves(self.config, self.n_clusters, self._scenario)
        interval = self.config.scheduling_cycle_interval

        def pair(seconds) -> TPair:
            win, off = from_f64_np(seconds, interval)
            return TPair(win=win, off=off)

        rows = {"statics": None, "fault_seed": None}
        if self.autoscale_statics is not None:
            pg_active_from = np.where(
                leaves["hpa_enabled"][:, None],
                self._autoscale_aux["pg_active_when_on"],
                np.inf,
            )
            rows["statics"] = dict(
                hpa_interval=pair(leaves["hpa_interval_s"]),
                hpa_tolerance=leaves["hpa_tolerance"].astype(np.float64),
                ca_threshold=leaves["ca_threshold"].astype(np.float64),
                ca_max_nodes=leaves["ca_max_nodes"].astype(np.int32),
                pg_active_from=pair(pg_active_from),
                d_hpa_up=pair(leaves["d_hpa_up_s"]),
                d_hpa_down=pair(leaves["d_hpa_down_s"]),
                d_ca_up=pair(leaves["d_ca_up_s"]),
                d_ca_down=pair(leaves["d_ca_down_s"]),
                ca_period=pair(leaves["ca_period_s"]),
                ca_snap=pair(leaves["ca_snap_s"]),
                ca_finish_vis=pair(leaves["ca_finish_vis_s"]),
                ca_commit_vis=pair(leaves["ca_commit_vis_s"]),
            )
        if self.consts.fault_seed is not None:
            rows["fault_seed"] = leaves["fault_seed"].astype(np.uint32)
        return rows

    def _install_scenario(self, rows: dict) -> None:
        """Bind DEVICE scenario leaves (the pytree of _scenario_rows) into
        the resident statics and consts."""
        if rows["statics"] is not None:
            st = self.autoscale_statics._replace(**rows["statics"])
            if self._sharding is not None:
                put = (
                    put_global
                    if is_cross_process(self._sharding.mesh)
                    else jax.device_put
                )
                st = put(st, self._state_shardings(self._sharding, st))
            self.autoscale_statics = st
        if rows["fault_seed"] is not None:
            self.consts = self.consts._replace(fault_seed=rows["fault_seed"])

    def _require_scenario_build(self, what: str) -> None:
        if self._scenario is None:
            raise ValueError(
                f"{what} requires an engine built with scenario= "
                "(the fleet build): scenario-less engines compile the "
                "pre-fleet consts pytree and a late scenario would "
                "shadow-compile next to it"
            )

    def update_scenario(self, scenario) -> None:
        """Install new per-lane scenario vectors into the RESIDENT engine:
        the scenario-bearing statics leaves (scan intervals, thresholds,
        CA period/quota, autoscaler-chain delays, per-lane HPA enables)
        and the pod-fault seed vector are all traced (C,)-shaped DATA, so
        this is one host->device put a leaf (some two dozen) and never a
        recompile (tests/test_fleet.py::test_wave_reset_and_zero_recompiles
        holds it by fleet.jit_cache_sizes). The wave boundary's transport; a
        lane-async pump round admits through admit_lanes, one put.
        Only legal on an engine built with scenario= (the fleet build):
        a scenario-less build may carry a different consts pytree
        (no fault_seed leaf), where a late update would shadow-compile."""
        from kubernetriks_tpu.batched.fleet import normalize_scenario

        self._require_scenario_build("update_scenario")
        self._scenario.update(
            normalize_scenario(scenario, self.n_clusters) or {}
        )
        self._install_scenario(jax.tree.map(jnp.asarray, self._scenario_rows()))

    def admit_lanes(self, lanes, scenario, horizons) -> None:
        """A lane-async pump round's whole admission in ONE host->device
        put and ONE donated program (fleet._admit_lanes), whatever the
        number of lanes: for the listed lanes, (a) write the scenario
        leaves of AutoscaleStatics and consts.fault_seed composed from
        `scenario` (update_scenario's values, bit for bit), (b) select
        the pristine build state into them, the telemetry ring preserved
        (lane_reset's select), (c) start their clocks at the engine's
        current global window with `horizons[i]` windows to run
        (set_lane_plan). The host rows of ALL lanes travel as one
        (C, K) float64 buffer with the lane mask as its first column
        (fleet.pack_admission: a float64 leaf as it is, a 32-bit leaf as
        two 16-bit halves, exact on any backend); the program takes them
        into the masked lanes only. The numpy mirrors
        (_scenario, the lane clocks, the trace mux's offered ranges) are
        updated as the three public calls update them."""
        from kubernetriks_tpu.batched.fleet import (
            _admit_lanes,
            normalize_scenario,
            pack_admission,
        )

        if not self.lane_async:
            raise ValueError(
                "admit_lanes requires an engine built with lane_async=True"
            )
        self._require_scenario_build("admit_lanes")
        lanes = np.asarray(list(lanes), np.int64)  # ktpu: sync-ok(python lane-index list, no device value)
        mask = np.zeros((self.n_clusters,), bool)
        mask[lanes] = True
        if self._lane_mux is not None:
            self._lane_mux.retire(lanes.tolist())
        self._lane_clock_np[lanes] = self.next_window_idx
        self._lane_horizon_np[lanes] = np.asarray(horizons, np.int64)  # ktpu: sync-ok(python horizon list into the host mirror, no device value)
        self._scenario.update(
            normalize_scenario(scenario, self.n_clusters) or {}
        )
        rows = self._scenario_rows()
        rows["lane_clock"] = self._lane_clock_np.astype(np.int32)
        rows["lane_horizon"] = self._lane_horizon_np.astype(np.int32)
        buf = pack_admission(mask, rows)
        live = {
            "statics": (
                None
                if rows["statics"] is None
                else {
                    name: getattr(self.autoscale_statics, name)
                    for name in rows["statics"]
                }
            ),
            "fault_seed": self.consts.fault_seed,
            "lane_clock": self.consts.lane_clock,
            "lane_horizon": self.consts.lane_horizon,
        }
        ring = self.state.telemetry
        state = self.state._replace(telemetry=None)
        donated_in = state if self._sanitize else None
        args = (
            state,
            self._pristine._replace(telemetry=None),
            live,
            jax.device_put(buf),
        )
        self.tracer.program(
            "admit_lanes", (self.pod_window,), _admit_lanes, args, {}
        )
        state, live = _admit_lanes(*args)
        if donated_in is not None:
            sanitize.consume_donated(donated_in)
        self.state = state._replace(telemetry=ring)
        self.consts = self.consts._replace(
            lane_clock=live["lane_clock"], lane_horizon=live["lane_horizon"]
        )
        self._install_scenario(live)

    def fleet_reset(self, lanes=None) -> None:
        """Reset cluster lanes to the PRISTINE build state in place — the
        fleet's between-query re-init. One donated select per state leaf
        against the build snapshot (device-buffer reuse, no recompile, no
        re-warm; fleet._reset_lanes). lanes=None resets EVERY lane and
        also rewinds the host-side cursors (window clock, pod-window
        position, staging ring/feeder, telemetry bookkeeping) — the wave
        boundary. An explicit lane list resets only those state rows and
        leaves the clock alone (only meaningful while the clock is at a
        wave boundary; the window clock is fleet-global)."""
        t0 = self.tracer.begin(PH_FLEET_RESET)
        try:
            self._fleet_reset_impl(lanes)
        finally:
            self.tracer.end(PH_FLEET_RESET, t0)

    def _fleet_reset_impl(self, lanes) -> None:
        from kubernetriks_tpu.batched.fleet import _reset_lanes

        if self._pristine is None:
            raise ValueError(
                "fleet_reset requires an engine built with scenario= "
                "(the fleet build keeps the pristine state snapshot)"
            )
        mask = np.zeros((self.n_clusters,), bool)
        if lanes is None:
            mask[:] = True
        else:
            mask[np.asarray(list(lanes), np.int64)] = True  # ktpu: sync-ok(fleet reset: host numpy over a python lane list, no device values)
        donated_in = self.state if self._sanitize else None
        args = (
            self.state,
            self._pristine,
            jnp.asarray(mask),
        )
        self.tracer.program(
            "reset_lanes", (self.pod_window,), _reset_lanes, args, {}
        )
        self.state = _reset_lanes(*args)
        if donated_in is not None:
            sanitize.consume_donated(donated_in)
        if lanes is not None:
            return
        # Wave boundary: rewind the host cursors to the build state.
        self.next_window_idx = 0
        self._pod_base = 0
        self._pending_shift = None
        self._refill_prefetch = None
        self._stage_cur = None
        self._stage_next = None
        self._close_feeder()
        self._refresh_name_ranks()
        if self.state.telemetry is not None:
            self._ring_seen.clear()
            self._ring_series_dropped = 0
            self._ring_windows_recorded = 0
            self._ring_drained_at = 0
            self._pending_flow = 0
        if self.observatory is not None:
            self.observatory.reset()

    # --- lane-async clock protocol (DESIGN §13) ---------------------------

    def horizon_windows(self, horizon: float) -> int:
        """Window count a fresh run of `horizon` sim-seconds executes —
        the lane_horizon a lane needs for per-query bit-identity with the
        wave-aligned path (window_idxs from cursor 0)."""
        interval = self.config.scheduling_cycle_interval
        return int(math.floor(horizon / interval)) + 1

    def set_lane_plan(self, lanes, start_window: int, horizons) -> None:
        """Arm per-lane clocks: lanes start their virtual window 0 at
        global window `start_window` and run `horizons[i]` windows. PURE
        DATA update — the (C,) consts leaves are traced, so re-seeding a
        lane never recompiles (the fleet's compile-once contract); the
        numpy mirrors keep host completion arithmetic sync-free."""
        if not self.lane_async:
            raise ValueError(
                "set_lane_plan requires an engine built with lane_async="
                "True (per-lane window clocks)"
            )
        lanes = np.asarray(list(lanes), np.int64)  # ktpu: sync-ok(python lane-index list, no device value)
        self._lane_clock_np[lanes] = int(start_window)
        self._lane_horizon_np[lanes] = np.asarray(horizons, np.int64)  # ktpu: sync-ok(python horizon list into the host mirror, no device value)
        self.consts = self.consts._replace(
            lane_clock=jnp.asarray(self._lane_clock_np, jnp.int32),
            lane_horizon=jnp.asarray(self._lane_horizon_np, jnp.int32),
        )

    def lane_windows_done(self) -> np.ndarray:
        """(C,) bool: lanes whose planned span is fully dispatched (global
        cursor past lane_clock + lane_horizon). Host arithmetic over the
        numpy clock mirrors — zero device syncs; counters for finished
        lanes are fetched by the caller at an existing host-block
        boundary (fleet._lane_rows)."""
        return (
            self._lane_clock_np + self._lane_horizon_np
            <= self.next_window_idx
        )

    def _install_lane_rows(self, lane: int, rows: np.ndarray) -> None:
        """Data-only device install of one lane's (E, 4) trace rows (blocked
        on the host, with the slab's sentinel tail: TraceSlab.block_rows)
        via dynamic_update_slice with TRACED start indices — one compiled
        program for every lane (a static `.at[lane].set` would compile
        per lane index and trip the post-warm-up sentinel)."""
        packed = jax.lax.dynamic_update_slice(
            self.slab.packed,
            jnp.asarray(TraceSlab.block_rows(rows))[None],
            (
                jnp.asarray(lane, jnp.int32),
                jnp.asarray(0, jnp.int32),
                jnp.asarray(0, jnp.int32),
            ),
        )
        self.slab = self.slab._replace(packed=packed)

    def set_lane_trace(self, lane: int, lo: int = 0, hi=None) -> bool:
        """Install a per-lane workload row-range (stream.LaneTraceMux):
        the lane replays only slab rows [lo, hi) (pod creates outside the
        range and their removes masked to EV_NONE in place — host copy,
        sort order preserved). Reseed-boundary call: the mux's never-
        re-offer invariant refuses a lane whose previous range was not
        retired by lane_reset / admit_lanes. Pure data install — zero
        recompiles, zero new steady-state syncs. Returns whether the
        range changed, that is whether rows went to the device (one put
        and one program; the mux skips an unchanged range)."""
        if not self.lane_async or self._lane_mux is None:
            raise ValueError(
                "set_lane_trace requires an engine built with "
                "lane_async=True (per-lane trace multiplexer)"
            )
        rows = self._lane_mux.offer(int(lane), lo, hi)
        if rows is not None:
            self._install_lane_rows(int(lane), rows)
        return rows is not None

    def lane_windows_remaining(self) -> np.ndarray:
        """(C,) host ints: windows left on each lane's plan from the
        global cursor (0 for idle/finished lanes) — the pump's occupancy
        ledger input. Same sync-free mirror arithmetic as
        lane_windows_done."""
        rem = (
            self._lane_clock_np + self._lane_horizon_np
            - self.next_window_idx
        )
        return np.clip(rem, 0, None)

    def step_windows(self, n_windows: int) -> None:
        """Dispatch exactly `n_windows` windows from the global cursor —
        the lane-async pump's fixed-span dispatch. The full-resident plain
        path compiles ONE program per distinct span length (program shape
        = idxs length), so a free-running fleet that always pumps the same
        span recompiles nothing after warm-up (the sweep's sentinel
        asserts it). Same guard/drain protocol as step_until_time."""
        n = int(n_windows)
        if n <= 0:
            return
        if self.pod_window is not None:
            raise ValueError(
                "step_windows requires the full-resident pod path "
                "(pod_window=None); sliding-window engines advance with "
                "step_until_time"
            )
        if self.state.telemetry is not None:
            pending = self.next_window_idx - self._ring_drained_at
            if pending > 0 and pending + n > self._telemetry_ring_size:
                self._maybe_drain_ring(force=True)
        # All-active fast path: the host clock mirrors prove every lane
        # stays inside its [clock, clock + horizon) span for the WHOLE
        # chunk, so the state-wide freeze selects (identities there) are
        # compiled out (step._window_body freeze_lanes=False). The mirrors
        # are host-authoritative (clocks only move via set_lane_plan /
        # lane_reset), so the proof costs no device read; spans touching a
        # lane boundary keep the freezing program. Two warmed variants
        # total — the pump's warm-up stream exercises both.
        start = self.next_window_idx
        freeze = True
        if self.lane_async:
            freeze = not (
                bool(np.all(self._lane_clock_np <= start))
                and bool(
                    np.all(
                        start + n
                        <= self._lane_clock_np + self._lane_horizon_np
                    )
                )
            )
        with sanitize.guard(self._sanitize):
            self._step_idxs(
                np.arange(start, start + n, dtype=np.int32),
                freeze_lanes=freeze,
            )
        self._maybe_drain_ring()

    def precompile_lane_spans(self, span: int) -> int:
        """Warm the lane-async pump's window-program variants: every
        power-of-two chunk of the pump ladder {span, span/2, ..., 1}
        plus the raw drain-tail span, each in BOTH freeze variants (the
        boundary-aligned no-freeze program and the freezing fallback).
        The pump's organic stream only exercises the variants its feed
        pattern happens to need — a burst-submitted stream runs
        boundary-aligned (no-freeze) chunks exclusively until the queue
        dries, so its first freezing dispatch would otherwise compile
        mid-stream, after the fleet declared itself warm (the armed
        sentinel in tests/test_fleet_async.py catches exactly that).
        Same scratch-copy protocol as precompile_chunks: the current
        window index repeats chunk times, so per-shape warm-up compute
        is ~one real window plus empty cycles. Returns the number of
        programs dispatched (cache hits included)."""
        if not self.lane_async or self.pod_window is not None:
            return 0
        from kubernetriks_tpu.batched.step import run_windows_donated

        win_fn = run_windows_donated if self.donate else run_windows
        sizes = []
        c = 1 << (max(int(span), 1).bit_length() - 1)
        while c >= 1:
            sizes.append(c)
            c //= 2
        if int(span) not in sizes:
            sizes.insert(0, int(span))
        n = 0
        t_warm = self.tracer.begin(PH_PRECOMPILE)
        for chunk in sizes:
            idxs = jnp.full((chunk,), self.next_window_idx, jnp.int32)
            for freeze in (False, True):
                out = win_fn(
                    tree_copy(self.state),
                    self.slab,
                    idxs,
                    self.consts,
                    collect_gauges=self.collect_gauges,
                    freeze_lanes=freeze,
                    **self._window_call_kwargs(),
                )
                jax.block_until_ready(out)  # discarded: warm-up only  # ktpu: sync-ok(warm-up: AOT compile of the lane-span variants, outside every timed region)
                n += 1
        self.tracer.end(PH_PRECOMPILE, t_warm)
        return n

    def lane_reset(self, lanes) -> None:
        """Per-lane pristine reset that PRESERVES the telemetry ring: the
        free-running engine re-seeds finished lanes mid-flight, and a
        plain fleet_reset(lanes) would tree-map the ring back to its
        pristine (cursor 0) snapshot — desynchronizing the per-lane
        cursors the uniform-window scatter relies on and dropping
        undrained rows. Strips the ring from both sides of the donated
        select (None = absent pytree node, one extra warmed program
        variant) and re-attaches the live ring after."""
        from kubernetriks_tpu.batched.fleet import _reset_lanes

        if not self.lane_async:
            raise ValueError(
                "lane_reset requires an engine built with lane_async=True"
            )
        if self._pristine is None:
            raise ValueError(
                "lane_reset requires an engine built with scenario= "
                "(the fleet build keeps the pristine state snapshot)"
            )
        mask = np.zeros((self.n_clusters,), bool)
        mask[np.asarray(list(lanes), np.int64)] = True  # ktpu: sync-ok(lane reset: host numpy over a python lane list, no device values)
        if self._lane_mux is not None:
            # The reset boundary retires the lanes' offered trace ranges:
            # the next set_lane_trace for them is legal again.
            self._lane_mux.retire(lanes)
        ring = self.state.telemetry
        state = self.state._replace(telemetry=None)
        pristine = self._pristine._replace(telemetry=None)
        donated_in = state if self._sanitize else None
        state = _reset_lanes(state, pristine, jnp.asarray(mask))
        if donated_in is not None:
            sanitize.consume_donated(donated_in)
        self.state = state._replace(telemetry=ring)

    def step_until_time(self, until_time: float) -> None:
        """Advance to `until_time`. THE steady-state dispatch region: under
        KTPU_SANITIZE it runs inside a device-to-host transfer guard — any
        sync not inside an explicit sanitize.allow_transfer scope (the
        runtime mirror of the lint pass's sync-ok waivers) raises."""
        t0 = self.tracer.begin(PH_STEP_UNTIL_TIME)
        try:
            self._step_until_time_guarded(until_time)
        finally:
            self.tracer.end(PH_STEP_UNTIL_TIME, t0)

    def _step_until_time_guarded(self, until_time: float) -> None:
        if self.state.telemetry is not None:
            # Entry-side wrap guard (host arithmetic only): the incoming
            # span's window count is known here, so drain the undrained
            # rows NOW if this call would wrap past them — loss can then
            # only happen when ONE call spans more than the ring itself
            # (disclosed via windows_recorded > windows_kept).
            pending = self.next_window_idx - self._ring_drained_at
            interval = self.config.scheduling_cycle_interval
            n_new = max(
                0,
                int(math.floor(until_time / interval))
                - self.next_window_idx
                + 1,
            )
            if pending > 0 and pending + n_new > self._telemetry_ring_size:
                self._maybe_drain_ring(force=True)
        with sanitize.guard(self._sanitize):
            self._step_until_time(until_time)
        # Telemetry ring pressure check (host-side arithmetic only): drain
        # before records wrap out. Lands OUTSIDE the transfer-guard region
        # at a boundary where callers already block (bench span fetches),
        # so telemetry-on adds no sync inside the steady-state loop.
        self._maybe_drain_ring()

    def _step_until_time(self, until_time: float) -> None:
        idxs = self.window_idxs(until_time)
        if len(idxs) == 0:
            return
        if self.pod_window is None:
            self._step_idxs(idxs)
            return
        # Sliding-window dispatch: run sub-spans up to the last window whose
        # pod creations still fit the device window, shifting past terminal
        # pods between spans. Spans are cut greedily along a power-of-two
        # chunk ladder — the binary decomposition of any span length, so a
        # span costs popcount(span) dispatches (a 20-window span is 16+4 =
        # 2 dispatches; the old coarse (128,32,8,1) ladder cut it into
        # 8+8+1+1+1+1 = 6, every dispatch paying its fixed host
        # overhead). When a slide will follow the span, the LAST
        # chunk dispatches as the fused chunk+slide megastep
        # (_fused_chunk_slide): the slide itself costs no extra dispatch,
        # and the only host sync of the span is the asynchronous 4-byte
        # shift readback at the boundary (_resolve_pending_slide). Engines
        # on the host slide path instead prefetch the refill payload while
        # the span's chunks are still running on device. At most
        # len(LADDER) program shapes compile per variant;
        # precompile_chunks() AOT-compiles them so none lands mid-bench.
        target = int(idxs[-1])
        if self._superspan_ok():
            self._run_superspans(target)
            return
        if self._superspan:
            # Superspan selected but not dispatchable (instrumented mode,
            # gauges, fast-forward, debug-finite): count the silent ladder
            # fallback so that it is observable.
            self.dispatch_stats["ladder_fallbacks"] += 1
        while self.next_window_idx <= target:
            sub = min(target, self._pod_capacity_window())
            will_slide = sub < target
            fuse = will_slide and self._fused_slide_ok()
            while self.next_window_idx <= sub:
                span = sub - self.next_window_idx + 1
                chunk = next(c for c in _CHUNK_LADDER if c <= span)
                # _step_idxs keeps the profiling/gauge instrumentation on
                # every dispatch size; chunk == span marks the span's final
                # chunk (the greedy binary decomposition ends exactly at sub).
                self._step_idxs(
                    np.arange(
                        self.next_window_idx,
                        self.next_window_idx + chunk,
                        dtype=np.int32,
                    ),
                    fuse_slide=fuse and chunk == span,
                )
            if sub >= target:
                return
            if will_slide and self._device_slide is None:
                # Host slide path: assemble the refill payload NOW, while
                # the span's dispatched chunks are still executing on device
                # (dispatches are asynchronous; the blocking phase fetch in
                # _advance_pod_window comes after).
                self._prefetch_refill()
            advanced = (
                self._resolve_pending_slide()
                if self._pending_shift is not None
                else self._advance_pod_window()
            )
            if not advanced:
                # The live-pod span outgrew the window (no leading pod is
                # terminal): grow the window in place instead of failing —
                # dense stretches of a long trace adapt automatically.
                if not self._grow_pod_window():
                    raise RuntimeError(
                        f"pod_window={self.pod_window} is too small: window "
                        f"{sub + 1} needs pod slots beyond the device window "
                        "and no leading pod is terminal yet, and the window "
                        "already covers the whole plain trace segment"
                    )
            # Ring pressure check riding the slide/grow sync that just
            # blocked (host arithmetic otherwise — no new syncs): ladder
            # spans longer than the ring stay lossless inside ONE call.
            self._maybe_drain_ring()

    def _fused_slide_ok(self) -> bool:
        """Whether spans can end in the fused chunk+slide megastep: needs
        the device-resident slide payload and the plain run_windows dispatch
        mode (fast-forward spans and gauge collection keep their own
        programs; both fall back to the two-dispatch slide)."""
        return (
            self._fuse_slide
            and self._device_slide is not None
            and not self.fast_forward
            and not self.collect_gauges
        )

    def _superspan_ok(self) -> bool:
        """Whether the steady-state loop can dispatch superspans: needs the
        sliding window and the plain run_windows dispatch mode (fast-forward
        and gauge collection keep their own programs), and steps aside for
        the per-chunk throughput log, which wants ladder-granular timings
        (the ladder is bit-identical).
        KTPU_DEBUG_FINITE keeps the ladder too: its promise is per-chunk
        NaN/inf localization, and a superspan only surfaces state once per
        up-to-K spans."""
        return (
            self._superspan
            and self.pod_window is not None
            and not self.fast_forward
            and not self.collect_gauges
            and not self.log_throughput
            and not self._debug_finite
        )

    def _stage_width(self) -> int:
        """Static column count of the superspan staging slab when the
        whole-trace payload is over budget (or streaming keeps it bounded
        unconditionally): W windows of shift headroom would starve a max
        (W/2) slide, so the default is 4W (3W of shift headroom per
        stage), clamped to the whole padded payload. A streaming engine's
        stream_segment (KTPU_STREAM_SEGMENT) overrides the default — the
        per-slab memory knob of the feeder ring."""
        W = self.pod_window
        T = int(self.consts.trace_pod_bound)
        if self._stream_on() and self._stream_segment is not None:
            want = self._stream_segment
        elif self._superspan_stage_cols is not None:
            want = self._superspan_stage_cols
        else:
            want = 4 * W
        return min(max(want, W + max(W // 2, 1)), T + W)

    def _stage_arrays(self, lo: int, width: int) -> dict:
        """Host half of staging-slab construction: the numpy segment
        payload for columns [lo, lo + width)
        (trace_compile.stage_segment owns the layout and padding rules).
        Pure host numpy — safe to call from the feeder thread."""
        from kubernetriks_tpu.batched.trace_compile import stage_segment

        return stage_segment(
            self._payload_source,
            self._pod_create_win,
            (
                self._pod_name_rank_full[:, : int(self.consts.trace_pod_bound)]
                if self.autoscale_statics is not None
                else None
            ),
            lo,
            width,
        )

    def _stage_upload(self, seg: dict) -> RefillStage:
        """Device half: pair conversion + upload + mesh placement of an
        assembled segment (mirrors _init_device_slide). Host-to-device
        only — safe from the feeder thread (the sanitizer's d2h transfer
        guard is engine-thread-local and never applies here)."""
        from kubernetriks_tpu.batched.state import duration_pair_np

        dur = duration_pair_np(
            seg.pop("duration"), self.config.scheduling_cycle_interval
        )
        stage = RefillStage(
            req_cpu=jnp.asarray(seg["req_cpu"]),
            req_ram=jnp.asarray(seg["req_ram"]),
            dur_win=dur.win,
            dur_off=dur.off,
            create_win=jnp.asarray(seg["create_win"]),
            rank=(
                jnp.asarray(seg["rank"]) if "rank" in seg else None
            ),
        )
        if self._sharding is not None:
            put = (
                put_global
                if is_cross_process(self._sharding.mesh)
                else jax.device_put
            )
            stage = put(stage, self._state_shardings(self._sharding, stage))
        return stage

    def _make_stage(self, lo: int, width: int) -> RefillStage:
        """Assemble + upload one staging slab covering payload columns
        [lo, lo + width) ON the engine thread (the non-streaming bounded
        path); the streaming feeder builds slabs through the same two
        halves off-thread."""
        ordinal = self.dispatch_stats["superspans"]
        t0 = self.tracer.begin(PH_STAGE_ASSEMBLE)
        seg = self._stage_arrays(lo, width)
        self.tracer.end(PH_STAGE_ASSEMBLE, t0, ident=ordinal)
        t0 = self.tracer.begin(PH_STAGE_PUT)
        stage = self._stage_upload(seg)
        self.tracer.end(PH_STAGE_PUT, t0, ident=ordinal)
        return stage

    # --- streaming feeder lifecycle ----------------------------------------

    def attach_payload_source(self, source) -> None:
        """Swap the resident whole-trace payload arrays (req/ram/duration,
        ~16 B/pod host memory) for a bounded segment-at-a-time
        PayloadSource (trace_compile.FeederPayloadSource over the native
        feeder's WorkloadSegmentReader, or any source honoring the
        contract) and RELEASE them — the host-memory half of the
        endurance work (ROADMAP #2): steady-state host RSS then holds
        only O(stage width) payload plus the disclosed small per-pod
        int32 tables (create windows for the O(1) capacity lookup, name
        ranks when autoscalers are on — 4 B/pod each, reported by
        _slab_accounting as host_payload_bytes).

        Requires the streaming superspan pipeline (the device-resident
        slide payload and the host slide path both want the whole trace
        resident) and a pure plain-pod payload axis (pod groups renumber
        it). The feeder is re-seeked so its producer thread never reads a
        released array."""
        from kubernetriks_tpu.batched.trace_compile import (
            ArrayPayloadSource,
            PayloadSource,
        )

        if not isinstance(source, PayloadSource):
            raise TypeError(
                f"attach_payload_source wants a trace_compile."
                f"PayloadSource, got {type(source).__name__}"
            )
        if self.pod_window is None or not self._stream_on():
            raise ValueError(
                "attach_payload_source requires the streaming superspan "
                "pipeline (pod_window + stream=True/KTPU_STREAM + "
                "superspan): the non-streaming paths keep the whole "
                "payload resident by design"
            )
        T = int(self.consts.trace_pod_bound)
        if source.total_rows < T:
            raise ValueError(
                f"payload source covers {source.total_rows} plain pod "
                f"columns; this trace has {T}"
            )
        if any(len(names) for names in self.pod_group_names):
            raise ValueError(
                "attach_payload_source does not support pod-group "
                "workloads: the resident group ring renumbers the "
                "payload axis past the plain segment, so payload column "
                "i would no longer be workload row i"
            )
        # Fidelity gate BEFORE releasing anything: the new source must
        # reproduce the engine's compiled payload bit-exactly over the
        # whole trace (chunked, one cold-path host pass). This is what
        # makes the swap safe at all — it catches a single-workload
        # FeederPayloadSource broadcast onto a HETEROGENEOUS fleet
        # (per-cluster traces differ; the reader would silently serve
        # cluster 0's pods to every lane), mismatched unit conversions,
        # or plain wrong-trace attachment, all of which would otherwise
        # produce wrong trajectories with no error.
        reference = (
            ArrayPayloadSource(self._full_pods)
            if self._full_pods is not None
            else self._payload_source
        )
        if reference is not None:
            chunk = 1 << 16
            for lo_v in range(0, T, chunk):
                w = min(chunk, T - lo_v)
                want = reference.segment(lo_v, w)
                got = source.segment(lo_v, w)
                for k in ("req_cpu", "req_ram", "duration"):
                    if not np.array_equal(want[k], got[k]):
                        diff = np.argwhere(want[k] != got[k])
                        c_bad, j_bad = (int(v) for v in diff[0])
                        raise ValueError(
                            f"attach_payload_source: source disagrees "
                            f"with the compiled trace payload at {k}"
                            f"[cluster {c_bad}, column {lo_v + j_bad}] "
                            f"({want[k][c_bad, j_bad]} != "
                            f"{got[k][c_bad, j_bad]}) — a payload source "
                            "serves the workload of EVERY cluster lane; "
                            "heterogeneous per-cluster traces need a "
                            "per-cluster-aware source (or keep the "
                            "resident payload)"
                        )
        self._close_feeder()
        self._payload_source = source
        self._full_pods = None
        self._stage_cur = None
        self._stage_next = None
        self._refill_prefetch = None

    def _stream_on(self) -> bool:
        """Whether the streaming pipeline stages this engine's slabs: the
        sliding window exists and the superspan executor is selected (the
        feeder stages for run_superspan's bounded RefillStage path; the
        ladder/instrumented fallbacks keep their own slide machinery)."""
        return (
            self._stream and self._superspan and self.pod_window is not None
        )

    def _ensure_feeder(self, retired_lo: int = -1):
        """The live StreamFeeder, built lazily at the current base and
        geometry (stage width is a jit static, so the feeder is re-built —
        re-seeked — whenever geometry or base moves non-monotonically:
        window growth, checkpoint restore). `retired_lo` is the supervisor
        restart path's carry-over: the dead ring's retired-slab
        high-water mark, so never-re-offer spans restarts."""
        if self._feeder is None:
            from kubernetriks_tpu.batched.stream import StreamFeeder

            W = self.pod_window
            self._feeder = StreamFeeder(
                self._stage_arrays,
                self._stage_upload,
                base=self._pod_base,
                width=self._stage_width(),
                window=W,
                trace_cols=int(self.consts.trace_pod_bound) + W,
                depth=self._stream_depth,
                retired_lo=retired_lo,
                chaos=self._feeder_chaos,
            )
        return self._feeder

    def _restart_feeder(self, feeder, err):
        """Supervisor restart after a producer death (FeederProducerError
        from get_stage): close the dead feeder, back off exponentially,
        rebuild at the current base carrying the retired-slab high-water
        mark (never-re-offer survives the restart — slab content is a
        pure function of (lo, width), so the rebuilt ring cannot
        diverge). Past the restart cap the error propagates — a
        persistently dying producer is a real bug, not weather — and the
        lane-async fleet above converts it to per-lane FeederErrors."""
        import logging
        import time as _time

        self._feeder_restarts += 1
        if self._feeder_restarts > self._feeder_restart_cap:
            raise err
        retired = feeder.retired_watermark()
        self._feeder_produced_total += feeder.produced
        feeder.close(timeout=1.0)
        self._feeder = None
        delay = self._feeder_backoff_s * (2 ** (self._feeder_restarts - 1))
        logging.getLogger(__name__).warning(
            "stream feeder producer died (%s); supervisor restart "
            "%d/%d after %.0f ms backoff",
            err,
            self._feeder_restarts,
            self._feeder_restart_cap,
            delay * 1e3,
        )
        _time.sleep(delay)
        return self._ensure_feeder(retired_lo=retired)

    def _close_feeder(self) -> None:
        """Stop + drop the feeder (re-seek half 1): the next staged
        dispatch rebuilds it at the then-current base and geometry. Slab
        content is a pure function of (lo, width), so a rebuilt feeder
        can never diverge from the one it replaces."""
        if self._feeder is not None:
            self._feeder_produced_total += self._feeder.produced
            self._feeder.close()
            self._feeder = None

    def _stage_covers(self, lo: int, stage: RefillStage) -> bool:
        """A stage serves a dispatch at the current pod_base iff the base
        sits inside it with the full window readable (the superspan's own
        exhaustion exit handles running out of headroom mid-flight)."""
        L = stage.req_cpu.shape[1]
        return (
            L == self._stage_width()
            and lo <= self._pod_base
            and self._pod_base - lo + self.pod_window <= L
        )

    def _current_stage(self):
        """(stage, lo) for the next superspan dispatch. Streaming engines
        draw from the feeder ring (the producer runs ahead; a not-ready
        slab blocks here with the stall split recorded); whole-trace
        payload engines wrap it directly (lo = 0, zero-copy, never
        restages); over-budget engines install the double-buffered
        successor when it covers the current base, else rebuild at the
        base."""
        if self._stream_on():
            from kubernetriks_tpu.batched.faults import FeederProducerError

            feeder = self._ensure_feeder()
            while True:
                try:
                    stage, lo, fresh = feeder.get_stage(
                        self._pod_base,
                        self.tracer,
                        ident=self.dispatch_stats["superspans"],
                    )
                    break
                except FeederProducerError as err:
                    feeder = self._restart_feeder(feeder, err)
            if fresh:
                self.dispatch_stats["stage_refills"] += 1
            self.dispatch_stats["feeder_slabs_produced"] = (
                self._feeder_produced_total + feeder.produced
            )
            return stage, lo
        if self._device_slide is not None:
            pay = self._device_slide
            return (
                RefillStage(
                    req_cpu=pay["req_cpu"],
                    req_ram=pay["req_ram"],
                    dur_win=pay["dur_win"],
                    dur_off=pay["dur_off"],
                    create_win=pay["create_win"],
                    rank=pay.get("rank"),
                ),
                0,
            )
        if self._stage_cur is not None and self._stage_covers(*self._stage_cur):
            lo, stage = self._stage_cur
            return stage, lo
        nxt, self._stage_next = self._stage_next, None
        if nxt is not None and self._stage_covers(*nxt):
            # Prefetch HIT: the double-buffered successor assembled while
            # the previous superspan ran covers the restage point.
            self.tracer.count("stage_prefetch_hit")
            self._stage_cur = nxt
        else:
            # Prefetch MISS: rebuild at the base on the span boundary's
            # critical path (the stall the tracer makes visible).
            self.tracer.count("stage_prefetch_miss")
            lo = self._pod_base
            self._stage_cur = (lo, self._make_stage(lo, self._stage_width()))
        self.dispatch_stats["stage_refills"] += 1
        lo, stage = self._stage_cur
        return stage, lo

    def _prefetch_stage(self, cur_lo: int) -> None:
        """Double-buffering: assemble + device_put the NEXT staging slab
        while the just-dispatched superspan runs on device. An
        exhaustion-exit superspan's final base b satisfies
        b > cur_lo + R - W/2 (the failed slide's shift is at most W/2 and
        its columns crossed cur_lo + L), so a successor at exactly that
        lower bound always covers the restage point — host assembly and the
        H2D transfer overlap device compute instead of serializing at the
        span boundary (the generalization of the ladder path's
        _prefetch_refill)."""
        if self._device_slide is not None or self._stream_on():
            # Streaming engines need no consumer-side prefetch nudge: the
            # feeder's producer thread runs the slab schedule ahead on its
            # own (the K-deep generalization of this 2-deep hook).
            return
        W = self.pod_window
        Lw = self._stage_width()
        lo_pred = cur_lo + (Lw - W) - W // 2
        if lo_pred <= cur_lo:
            return
        if self._stage_next is not None and self._stage_next[0] == lo_pred:
            return
        t0 = self.tracer.begin(PH_STAGE_PREFETCH)
        self._stage_next = (lo_pred, self._make_stage(lo_pred, Lw))
        self.tracer.end(
            PH_STAGE_PREFETCH, t0, ident=self.dispatch_stats["superspans"]
        )

    def _run_superspans(self, target: int) -> None:
        """The superspan dispatch loop: one device program per up-to-K
        slide-spans, one blocking (4,)-int32 progress readback per dispatch
        consumed AFTER the next stage's prefetch is in flight. Host work per
        superspan: the readback, the host-mirror updates (pod_base, window
        cursor, carried name ranks), and — over-budget engines only — the
        overlapped staging assembly."""
        fn = run_superspan_donated if self.donate else run_superspan
        tr = self.tracer
        while self.next_window_idx <= target:
            W = self.pod_window
            stage, lo = self._current_stage()
            rank = (
                self.autoscale_statics.pod_name_rank
                if self.autoscale_statics is not None
                else None
            )
            progress_in = jnp.asarray(
                [self.next_window_idx, self._pod_base, 0, SUPERSPAN_RUN],
                jnp.int32,
            )
            self.dispatch_stats["superspans"] += 1
            donated_in = (
                self.state if (self.donate and self._sanitize) else None
            )
            ordinal = self.dispatch_stats["superspans"]
            t0 = tr.begin(PH_SUPERSPAN)
            args = (
                self.state,
                rank,
                progress_in,
                self.slab,
                self.consts,
                stage,
                jnp.int32(lo),
                jnp.int32(target),
            )
            kwargs = dict(
                W=W,
                K=self._superspan_k,
                chunk=self._superspan_chunk,
                **self._window_call_kwargs(),
            )
            tr.program(
                "run_superspan", (W, stage.req_cpu.shape[1]), fn, args, kwargs
            )
            state, rank, progress = fn(*args, **kwargs)
            tr.end(PH_SUPERSPAN, t0, ident=ordinal)
            self.state = state
            if donated_in is not None:
                sanitize.consume_donated(donated_in)
            if rank is not None:
                self.autoscale_statics = self.autoscale_statics._replace(
                    pod_name_rank=rank
                )
            if hasattr(progress, "copy_to_host_async"):
                with sanitize.allow_transfer(
                    self._sanitize, "async progress prefetch"
                ):
                    progress.copy_to_host_async()  # ktpu: sync-ok(async initiation of the waived progress readback — does not block)
            fid = tr.flow_start(PH_PROGRESS_WAIT)
            # Overlap the next stage's host assembly + H2D with the device
            # program still running, BEFORE the blocking readback.
            self._prefetch_stage(lo)
            t0 = tr.begin(PH_PROGRESS_WAIT)
            with sanitize.allow_transfer(
                self._sanitize, "superspan progress readback"
            ):
                w, base, spans, code = (int(v) for v in to_host(progress))  # ktpu: sync-ok(THE steady-state sync: one async-prefetched (4,)-i32 progress readback per superspan dispatch)
            tr.end(PH_PROGRESS_WAIT, t0, ident=ordinal)
            tr.flow_end(PH_PROGRESS_WAIT, fid)
            self._check_finite()
            self.dispatch_stats["slide_syncs"] += 1
            self.dispatch_stats["superspan_spans"] += spans
            self.next_window_idx = w
            self._pod_base = base
            if code == SUPERSPAN_GROW:
                if not self._grow_pod_window():
                    raise RuntimeError(
                        f"pod_window={self.pod_window} is too small: window "
                        f"{w} needs pod slots beyond the device window "
                        "and no leading pod is terminal yet, and the window "
                        "already covers the whole plain trace segment"
                    )
            elif code == SUPERSPAN_STAGE:
                if self._device_slide is not None:
                    # Unreachable by construction (the whole-trace payload
                    # covers every refill column a slide can touch); a silent
                    # retry here would loop forever, so fail loudly instead.
                    raise RuntimeError(
                        "superspan reported staging exhaustion against the "
                        "whole-trace slide payload"
                    )
                # The stage ran out of slide headroom mid-flight. It may
                # still COVER the final base (exhaustion fires on the
                # pending slide's refill columns, not the window read), so
                # drop it — _current_stage then installs the prefetched
                # successor, or rebuilds at the new base (L - W >= W/2 of
                # fresh headroom, so the retried slide always lands and the
                # dispatch loop can't spin on an exhausted buffer). The
                # streaming ring RETIRES the slab instead: the feeder
                # asserts a retired slab is never re-offered, so the
                # spin-on-exhausted-buffer bug class is structurally
                # pinned rather than relying on this drop.
                if self._feeder is not None:
                    self._feeder.retire(lo)
                self._stage_cur = None
            # SUPERSPAN_RUN with w <= target: K-span budget hit; redispatch.
            # Telemetry ring pressure check (host arithmetic; the fetch, if
            # due, rides the progress readback that JUST blocked — still
            # zero new syncs): long single calls no longer wrap rows out
            # unless ONE dispatch retires more windows than the ring holds.
            self._maybe_drain_ring()

    def _resolve_pending_slide(self) -> bool:
        """Consume a fused slide's pending shift — the span's ONLY host
        sync, an async-prefetched 4-byte readback. The device state already
        slid (or provably could not, shift 0); this just moves the host
        mirrors. Returns False when no slide was possible (grow the window).
        """
        s_arr = self._pending_shift
        self._pending_shift = None
        self.dispatch_stats["slide_syncs"] += 1
        t0 = self.tracer.begin(PH_SHIFT_WAIT)
        with sanitize.allow_transfer(
            self._sanitize, "fused-slide shift readback"
        ):
            s = int(s_arr)  # ktpu: sync-ok(the fused span's only host sync: async-prefetched 4-byte shift readback, consumed at the span boundary)
        self.tracer.end(PH_SHIFT_WAIT, t0)
        self.tracer.flow_end(PH_SHIFT_WAIT, self._pending_flow)
        if s <= 0:
            # The fused slide was the identity (statics rank swap included);
            # nothing moved on device or host.
            return False
        self._pod_base += s
        self._refill_prefetch = None
        return True

    def _prefetch_refill(self) -> None:
        """Host slide path: build the next slide's refill payload at the
        MAXIMAL quantized width (every possible shift is a prefix of it)
        before the blocking phase fetch, overlapping the host assembly +
        device_put with the span's in-flight device chunks.
        _advance_pod_window slices it to the actual shift."""
        W = self.pod_window
        width = max(W // 2, 1)
        start = self._pod_base + W
        if (
            self._refill_prefetch is not None
            and self._refill_prefetch[:2] == (start, width)
        ):
            return
        self.dispatch_stats["refill_prefetches"] += 1
        t0 = self.tracer.begin(PH_REFILL_PREFETCH)
        self._refill_prefetch = (start, width, self._make_refill(start, width))
        self.tracer.end(PH_REFILL_PREFETCH, t0)

    def _pod_capacity_window(self) -> int:
        """Largest window index dispatchable before a pod creation would land
        beyond the device window (slots are created in event order, so the
        first overflow create's window bounds every cluster)."""
        L = self._pod_base + self.pod_window
        if L >= self._pod_create_win.shape[1]:
            return 1 << 30
        return int(self._pod_create_win[:, L].min())

    def _refresh_name_ranks(self) -> None:
        """Re-slice the windowed pod-name ranks into the autoscale statics
        after a window slide (device layout: [window over plain slots |
        resident rings])."""
        if self.autoscale_statics is None or self._payload_source is None:
            return
        W = self.pod_window
        T = int(self.consts.trace_pod_bound)
        full = self._pod_name_rank_full
        C = full.shape[0]
        BIG_RANK = np.int32(1 << 30)
        seg = full[:, self._pod_base : self._pod_base + W]
        if seg.shape[1] < W:
            seg = np.concatenate(
                [seg, np.full((C, W - seg.shape[1]), BIG_RANK, np.int32)],
                axis=1,
            )
        dev = np.concatenate([seg, full[:, T:]], axis=1)
        old = self.autoscale_statics.pod_name_rank
        put = (
            put_global
            if (self.mesh is not None and is_cross_process(self.mesh))
            else jax.device_put
        )
        new = put(jnp.asarray(dev), old.sharding)
        self.autoscale_statics = self.autoscale_statics._replace(
            pod_name_rank=new
        )

    def _advance_pod_window(self) -> bool:
        t0 = self.tracer.begin(PH_SLIDE)
        try:
            return self._advance_pod_window_impl()
        finally:
            self.tracer.end(PH_SLIDE, t0)

    def _advance_pod_window_impl(self) -> bool:
        """Shift the device pod window past the leading run of terminal pods
        (uniform shift across clusters), refilling the tail from the host
        payload. Only the window segment [0, pod_window) moves; the resident
        pod-group tail beyond it is untouched. Returns False if no shift is
        possible."""
        from kubernetriks_tpu.batched.state import (
            PHASE_EMPTY,
            PHASE_FAILED,
            PHASE_REMOVED,
            PHASE_SUCCEEDED,
        )

        def slice_pad(arr, start, width, fill):
            """arr[:, start:start+width], right-padded with fill past the
            trace's plain-pod segment."""
            seg = arr[:, start : start + width]
            if seg.shape[1] < width:
                pad = np.full(
                    (arr.shape[0], width - seg.shape[1]), fill, arr.dtype
                )
                seg = np.concatenate([seg, pad], axis=1)
            return seg

        W = self.pod_window
        win_lo = self._pod_base
        if self._device_slide is not None:
            # On-device shift computation: only the scalar reaches the
            # host (the host fetch of the full (C, W) phase array was the
            # first of the per-slide round-trips this path eliminates).
            # (The steady-state loop fuses this dispatch pair into the
            # span's last chunk instead — _fused_chunk_slide; this
            # two-dispatch path serves fast-forward/gauge/fuse-disabled
            # engines.)
            self.dispatch_stats["slide_dispatches"] += 1
            self.dispatch_stats["slide_syncs"] += 1
            with sanitize.allow_transfer(
                self._sanitize, "two-dispatch slide shift readback"
            ):
                s = int(  # ktpu: sync-ok(blocking 4-byte shift readback gating the slide decision on the two-dispatch path; the steady-state loop fuses this away)
                    _slide_shift_device(
                        slide_phase(self.state.pods, self.consts)[:, :W],
                        self._device_slide["create_win"],
                        jnp.asarray(win_lo, jnp.int32),
                    )
                )
        else:
            self.dispatch_stats["slide_syncs"] += 1
            with sanitize.allow_transfer(
                self._sanitize, "host slide path phase fetch"
            ):
                phases = to_host(slide_phase(self.state.pods, self.consts))[:, :W]  # ktpu: sync-ok(host slide path: blocking (C, W) phase fetch — the round-trip the device-resident payload eliminates)
            terminal = (
                (phases == PHASE_SUCCEEDED)
                | (phases == PHASE_REMOVED)
                | (phases == PHASE_FAILED)
            )
            # Padding slots — EMPTY with NO create event in the trace
            # (shorter clusters of a heterogeneous batch, or the padded
            # tail) — can never come alive, so they never block the shift.
            # EMPTY slots whose create event is still pending must stay.
            no_create = np.iinfo(np.int32).max
            create_win = slice_pad(self._pod_create_win, win_lo, W, no_create)
            padding = (phases == PHASE_EMPTY) & (create_win == no_create)
            blocking = ~(terminal | padding)
            first_live = np.where(
                blocking.any(axis=1), blocking.argmax(axis=1), phases.shape[1]
            )
            s = int(first_live.min())
        if s <= 0:
            return False
        # Quantize the shift to a SMALL set of values: every distinct s is a
        # distinct concatenate/refill shape, and each novel shape recompiles
        # the 17-leaf pytree concat (seconds per novel slide against a
        # millisecond window step). Three main shapes (W/2,
        # W/4, W/8) plus small powers of two as the forced-minimal fallback;
        # sliding less than possible is harmless — the capacity check just
        # triggers another slide sooner.
        quantum = max(W // 8, 1)
        if s >= W // 2 > 0:
            s = W // 2
        elif s >= W // 4 > 0:
            s = W // 4
        elif s >= quantum:
            s = quantum
        else:
            s = 1 << (s.bit_length() - 1)

        if self._device_slide is not None:
            self.dispatch_stats["slide_dispatches"] += 1
            rank = (
                self.autoscale_statics.pod_name_rank
                if self.autoscale_statics is not None
                else None
            )
            new_pods, new_rank = _slide_apply_device(
                self.state.pods,
                rank,
                self._device_slide,
                jnp.asarray(win_lo, jnp.int32),
                s,
                W,
            )
            self.state = self.state._replace(
                pods=new_pods, pod_base=self.state.pod_base + jnp.int32(s)
            )
            self._pod_base += s
            if new_rank is not None:
                self.autoscale_statics = self.autoscale_statics._replace(
                    pod_name_rank=new_rank
                )
            return True

        pf = self._refill_prefetch
        self._refill_prefetch = None
        if pf is not None and pf[0] == win_lo + W and pf[1] >= s:
            # Prefetched while the span's chunks ran on device: every
            # quantized shift is a prefix of the maximal-width payload.
            refill = jax.tree.map(lambda a: a[:, :s], pf[2])
        else:
            refill = self._make_refill(win_lo + W, s)
        new_pods = jax.tree.map(
            lambda a, b: jnp.concatenate([a[:, s:W], b, a[:, W:]], axis=1),
            self.state.pods,
            refill,
        )
        self.state = self.state._replace(
            pods=new_pods, pod_base=self.state.pod_base + jnp.int32(s)
        )
        self._pod_base += s
        self._refresh_name_ranks()
        return True

    def _make_refill(self, start: int, width: int):
        """Pristine pod slots for global plain slots [start, start + width)
        — built by the SAME constructor init_state uses (windowed,
        full-resident and grown runs can never drift on fresh-slot
        defaults), sliced from the host payload with right-padding past the
        trace, and C-sharded under a mesh so downstream concatenations
        compose shard-local slices. Shared by the host slide path and
        _grow_pod_window."""
        from kubernetriks_tpu.batched.state import (
            duration_pair_np,
            fresh_pod_arrays,
        )

        C = self._pod_create_win.shape[0]
        cols = self._payload_source.segment(start, width)
        refill = fresh_pod_arrays(
            C,
            width,
            cols["req_cpu"],
            cols["req_ram"],
            duration_pair_np(
                cols["duration"],
                self.config.scheduling_cycle_interval,
            ),
        )
        if self.mesh is not None:
            put = put_global if is_cross_process(self.mesh) else jax.device_put
            refill = put(refill, self._state_shardings(self._sharding, refill))
        return refill

    def _grow_pod_window(self) -> bool:
        t0 = self.tracer.begin(PH_WINDOW_GROW)
        try:
            return self._grow_pod_window_impl()
        finally:
            self.tracer.end(
                PH_WINDOW_GROW, t0, ident=self.dispatch_stats["superspans"]
            )

    def _grow_pod_window_impl(self) -> bool:
        """Double the sliding window IN PLACE when a dense stretch of the
        trace outgrows it (peak live-pod span > pod_window, so no slide is
        possible): insert fresh plain-pod slots between the window segment
        and the resident ring tail, re-point the segment mapping
        (consts.resident_shift moves right), and rebuild the windowed
        name-rank/group statics and the device slide payload. Bit-exact:
        window slots [0, new_W) cover global plain slots
        [pod_base, pod_base + new_W) with the SAME fresh-slot constructor
        the initial build uses, and the inserted slots' create events are
        still pending (the capacity check never dispatched a window needing
        them). Shapes change, so the step recompiles once per growth.
        Returns False when the window already spans the whole plain
        segment."""
        W = self.pod_window
        T = int(self.consts.trace_pod_bound)
        if W is None or W >= T:
            return False
        new_W = min(2 * W, T)
        insert = new_W - W
        # Re-seek half of the streaming pipeline: the stage width is keyed
        # to W, so the feeder's slabs are stale after growth — close it
        # BEFORE mutating the payload tables its assemble callback reads
        # (close joins the producer thread; the next staged dispatch
        # rebuilds at the grown geometry).
        self._close_feeder()
        # Cross-process meshes REQUIRE the device-resident slide payload
        # (the host path calls to_host on non-addressable shards) unless
        # the streaming feeder stages slabs instead; check the grown
        # payload against the budget BEFORE mutating anything, so the
        # raise leaves the engine consistent (same predicate as
        # _init_device_slide).
        if (
            self.mesh is not None
            and is_cross_process(self.mesh)
            and self._full_pods is not None
            and not self._stream_on()
            and not self._slide_payload_fits(new_W)
        ):
            raise ValueError(
                "pod_window growth on a cross-process mesh would push "
                "the device-resident slide payload past its memory "
                "budget — raise _DEVICE_SLIDE_BUDGET_BYTES, start with "
                "a larger pod_window, or drop to a single-process mesh "
                "(the host slide path needs every shard addressable)"
            )
        base = self._pod_base
        C = self._pod_create_win.shape[0]
        refill = self._make_refill(base + W, insert)

        def widen(pods, fresh):
            return jax.tree.map(
                lambda a, b: jnp.concatenate([a[:, :W], b, a[:, W:]], axis=1),
                pods,
                fresh,
            )

        self.state = self.state._replace(pods=widen(self.state.pods, refill))
        if self._pristine is not None:
            # The engine stays grown, so the snapshot fleet_reset and
            # lane_reset rewind to grows with it: its window sits at base
            # 0, where the same insert covers global slots [W, new_W) —
            # the state a build at pod_window=new_W starts from.
            self._pristine = self._pristine._replace(
                pods=widen(self._pristine.pods, self._make_refill(W, insert))
            )
        self.pod_window = new_W
        self._resident_shift = T - new_W
        self.consts = self.consts._replace(
            resident_shift=np.int32(self._resident_shift)
        )
        if self.autoscale_statics is not None:
            st = self.autoscale_statics
            # The resident ring tail moved right by `insert` device slots:
            # group ids gain `insert` no-group window slots before the tail,
            # ring start indices shift right (padding groups have
            # slot_count 0; their start is only read through real gids).
            pgi = st.pod_group_id
            gap = jnp.full((C, insert), -1, jnp.int32)
            if self.mesh is not None:
                put = (
                    put_global
                    if is_cross_process(self.mesh)
                    else jax.device_put
                )
                gap = put(gap, self._state_shardings(self._sharding, gap))
            self.autoscale_statics = st._replace(
                pod_group_id=jnp.concatenate(
                    [pgi[:, :W], gap, pgi[:, W:]], axis=1
                ),
                pg_slot_start=st.pg_slot_start + jnp.int32(insert),
            )
            if self._hpa_seg not in (None, (0, 0)):
                lo, hi = self._hpa_seg
                self._hpa_seg = (lo + insert, hi + insert)
            self._refresh_name_ranks()  # rebuilds windowed ranks at new_W
        self._init_device_slide()  # re-pad the payload to T + new_W
        # A prefetched refill payload (host slide path) is sized/positioned
        # for the OLD window width — drop it. Superspan staging slabs are
        # width-keyed too (_stage_covers rejects them anyway; free the HBM).
        self._refill_prefetch = None
        self._stage_cur = None
        self._stage_next = None
        if (
            self.mesh is not None
            and is_cross_process(self.mesh)
            and self._device_slide is None
            and not self._stream_on()
        ):
            # Not an assert: this consistency check must survive python -O —
            # silently continuing on a cross-process mesh without the
            # device payload would hit to_host on non-addressable shards
            # much later, as an opaque error.
            raise RuntimeError(
                "pre-mutation budget check above must match "
                "_init_device_slide"
            )
        # Kernel VMEM fits-gates depend on the device pod-axis width.
        self.n_pods += insert
        from kubernetriks_tpu.ops.scheduler_kernel import (
            select_commit_kernel_fits,
            select_kernel_fits,
        )

        self.use_pallas_select = (
            self.use_pallas_select
            and select_kernel_fits(
                self.n_nodes, self.n_pods, self.max_pods_per_cycle,
                self._spread_shape, self._affinity_terms, self._kube_terms,
            )
        )
        self.use_megakernel = (
            self.use_megakernel
            and self.use_pallas_select
            and select_commit_kernel_fits(
                self.n_nodes, self.n_pods, self.max_pods_per_cycle,
                self._spread_shape, self._affinity_terms, self._kube_terms,
            )
        )
        import logging

        logging.getLogger(__name__).info(
            "pod_window grew %d -> %d at window base %d (live span outgrew "
            "the window)", W, new_W, base,
        )
        return True

    # Float state fields whose +/-inf values are documented sentinels ("no
    # pending effect" pairs, estimator min/max identities) — everything else
    # must be finite after every chunk under KTPU_DEBUG_FINITE=1.
    _FINITE_EXEMPT = (
        "finish_time",
        "removal_time",
        "remove_time",
        "create_time",
        "hpa_next",
        "ca_next",
        "minimum",
        "maximum",
    )

    def _check_finite(self) -> None:
        """KTPU_DEBUG_FINITE=1 guard mode: sweep every float leaf of the
        state after a dispatched chunk — NaN anywhere, or inf outside the
        documented sentinel fields, raises with the offending field name.
        Host-side readback, so the donated hot path is untouched when off.
        KTPU_SANITIZE folds this sweep in at every dispatch boundary (on
        the superspan path: once per superspan, where the progress
        readback already syncs)."""
        if not (self._debug_finite or self._sanitize):
            return
        with sanitize.allow_transfer(self._sanitize, "finite-guard sweep"):
            self._check_finite_now()

    def _check_finite_now(self) -> None:  # ktpu: sync-ok(guard-mode state sweep body: full host readback is the point)
        flat, _ = jax.tree_util.tree_flatten_with_path(self.state)
        for path, leaf in flat:
            arr = np.asarray(to_host(leaf))
            if not np.issubdtype(arr.dtype, np.floating):
                continue
            key = jax.tree_util.keystr(path)
            if np.isnan(arr).any():
                raise FloatingPointError(
                    f"KTPU_DEBUG_FINITE: NaN in state field {key} after "
                    f"window {self.next_window_idx - 1}"
                )
            if not any(tok in key for tok in self._FINITE_EXEMPT) and not (
                np.isfinite(arr).all()
            ):
                raise FloatingPointError(
                    f"KTPU_DEBUG_FINITE: non-finite value in state field "
                    f"{key} after window {self.next_window_idx - 1}"
                )

    def _decisions_total(self) -> int:  # ktpu: sync-ok(log_throughput instrumentation: per-chunk decisions counter fetch, instrumented runs only)
        """Blocking fetch of the summed decisions counter — the ONE owner
        of the instrumented path's throughput probe (PR 8 deduped the
        before/after fetch sites onto it)."""
        with sanitize.allow_transfer(
            self._sanitize, "log_throughput decisions fetch"
        ):
            return int(to_host(self.state.metrics.scheduling_decisions).sum())

    def _step_idxs(
        self,
        idxs: np.ndarray,
        fuse_slide: bool = False,
        freeze_lanes: bool = True,
    ) -> None:
        if not self.log_throughput:
            self._dispatch_windows(
                idxs, fuse_slide=fuse_slide, freeze_lanes=freeze_lanes
            )
            self._check_finite()
            return

        # Instrumented path: a per-chunk decisions/s log line (TPU analog
        # of the scalar events/s log, reference: src/simulator.rs:363-368),
        # each chunk fenced so the clock measures device work.
        import logging
        import time

        before = self._decisions_total()
        t0 = time.perf_counter()
        with self.tracer.span(PH_CHUNK_FENCED):
            self._dispatch_windows(
                idxs, fuse_slide=fuse_slide, freeze_lanes=freeze_lanes
            )
            jax.block_until_ready(self.state.time)  # ktpu: sync-ok(instrumented path: fence so the per-chunk clock measures device work, not dispatch)
        elapsed = time.perf_counter() - t0
        self._check_finite()
        log_chunk_throughput(
            logging.getLogger(__name__),
            len(idxs),
            self.n_clusters,
            self._decisions_total() - before,
            elapsed,
        )

    def step_window(self) -> None:
        """Advance a single scheduling cycle (useful for tests)."""
        if self.pod_window is not None:
            assert self.next_window_idx <= self._pod_capacity_window(), (
                "step_window would apply a pod creation beyond the sliding "
                "pod window; use step_until_time (which shifts the window) "
                "or a larger pod_window"
            )
        self.state = window_step(
            self.state,
            self.slab,
            jnp.asarray(self.next_window_idx, jnp.int32),
            self.consts,
            **self._window_call_kwargs(),
        )
        if self.collect_gauges:
            from kubernetriks_tpu.batched.step import gauge_snapshot

            self._gauges.append(
                np.asarray([self.next_window_idx], np.int32),  # ktpu: sync-ok(single-window test helper: host-side window index, no device value)
                to_host(gauge_snapshot(self.state))[None],  # ktpu: sync-ok(gauge instrumentation in the single-window test helper)
            )
        self.next_window_idx += 1

    def run_to_completion(self, max_time: float = 1e7) -> None:
        """Step until every trace pod has terminated (scalar equivalent:
        RunUntilAllPodsAreFinishedCallbacks), bounded by max_time."""
        interval = self.config.scheduling_cycle_interval
        chunk = 64  # windows a step; how far past completion the run stops follows it
        finite = self._ev_time_np[np.isfinite(self._ev_time_np)]
        last_event_time = float(finite.max()) if finite.size else 0.0
        while True:
            self.step_until_time(self.next_window + chunk * interval)
            # Never conclude before the trace is fully applied: EMPTY slots
            # may still be waiting on future CreatePod events. An event in
            # window w is only applied when window w+1 steps, so the run must
            # have advanced strictly past last_event_time + interval.
            if self.next_window <= last_event_time + interval:
                continue
            phases = to_host(self.state.pods.phase)  # ktpu: sync-ok(completion poll at chunk boundary — the batched analog of the scalar run-until-finished callback)
            service = to_host(self.state.pods.duration.win) < 0  # ktpu: sync-ok(completion poll at chunk boundary)
            # Finite-duration pods not yet terminal?
            live = (
                ((phases == PHASE_QUEUED) | (phases == PHASE_UNSCHEDULABLE))
                | ((phases == PHASE_RUNNING) & ~service)
            )
            if not live.any():
                return
            if self.next_window > max_time:
                raise RuntimeError(
                    f"run_to_completion exceeded max_time={max_time}; "
                    f"{int(live.sum())} pods still live"
                )

    # --- readout ------------------------------------------------------------

    def check_autoscaler_bounds(self) -> None:  # ktpu: sync-ok(readout: divergence counters fetched once at summary time)
        """Raise loudly when a documented autoscaler work bound was crossed
        and the trajectory has (or is about to) diverge from the scalar
        semantics (autoscale.py "Remaining bounded deviations"):

        - HPA reserve clamp: an HPA cycle wanted more replicas than the
          group's reserve had reusable slots for. The scalar
          (kube_horizontal_pod_autoscaler.rs:157-181) would have created
          them — counts are already wrong.
        - CA reserve starvation: a scale-up cycle wanted to open a node for
          a cache pod — quota headroom and a fitting template existed — but
          the group's ca_slot_multiplier x max_count slot reserve was
          consumed (slots are never reclaimed, the batched analog of the
          reference's pre-sized component pool, src/simulator.rs:212-230 —
          but the reference RECLAIMS components on scale-down,
          node_component_pool.rs:60-77, so long churn never exhausts it
          there). The pod silently stays unscheduled where the scalar would
          have provisioned a node.

        Both are EXACT observed-divergence counters folded inside the
        passes (autoscale.py), not state heuristics: a run that merely
        consumed its reserve without unmet demand does not raise.
        """
        if self.autoscale_statics is None or not self.strict_autoscaler_bounds:
            return
        from kubernetriks_tpu.parallel.multihost import to_host

        clamped = np.asarray(to_host(self.state.metrics.hpa_reserve_clamped))
        if clamped.sum() > 0:
            worst = int(clamped.argmax())
            raise RuntimeError(
                f"HPA slot reserve exhausted: {int(clamped.sum())} wanted "
                f"replica(s) across {int((clamped > 0).sum())} cluster(s) "
                f"(worst: cluster {worst}, {int(clamped[worst])}) could not "
                "be activated because no reusable slot remained in the pod "
                "group's reserve — the scalar path would have created them, "
                "so reported replica counts have diverged. Enlarge the "
                "group's slot reserve (trace compile pg_slot_count) or "
                "lower max_pods churn; set strict_autoscaler_bounds=False "
                "to read the diverged metrics anyway."
            )
        starved = np.asarray(to_host(self.state.metrics.ca_reserve_starved))
        if starved.sum() > 0:
            worst = int(starved.argmax())
            if self.reclaim:
                hint = (
                    "slot reclaim is ON, so every fully-retired slot was "
                    "already returned — the reserve is exhausted by LIVE "
                    "demand (plus removals still inside their visibility "
                    "horizon). Raise ca_slot_multiplier (build arg) to "
                    "widen the reserve"
                )
            else:
                hint = (
                    "scaled-up slots are never reclaimed on this build — "
                    "raise ca_slot_multiplier (build arg) to widen the "
                    "reserve, or enable slot reclaim (reclaim=True / "
                    "KTPU_RECLAIM=1) so retired slots return to it"
                )
            raise RuntimeError(
                f"CA slot reserve exhausted: {int(starved.sum())} "
                f"scale-up attempt(s) across {int((starved > 0).sum())} "
                f"cluster(s) (worst: cluster {worst}, "
                f"{int(starved[worst])}) found quota headroom and a "
                "fitting node-group template but no reserved slot left — "
                "the demand silently starved where the scalar path would "
                f"have provisioned a node. {hint}; or set "
                "strict_autoscaler_bounds=False to accept the starved "
                "trajectory."
            )
        # Decimal-suffix name keys (autoscale.decimal_string_key) order
        # "{prefix}_{idx}" names exactly for idx < 10^8; past that the
        # int32 key saturates its digit bands and name-ordered walks
        # would silently drift. Endurance runs approach this only after
        # ~10^8 allocations per group — raise loudly instead of drifting.
        auto = self.state.auto
        if auto is not None:
            tail_max = int(np.asarray(to_host(auto.hpa_tail)).max())
            total_max = 0
            if auto.ca_total is not None:
                total_max = int(np.asarray(to_host(auto.ca_total)).max())
            if max(tail_max, total_max) >= 10**8:
                raise RuntimeError(
                    f"allocation-name counter overflow: hpa_tail max "
                    f"{tail_max}, ca_total max {total_max} reached the "
                    "10^8 bound of the decimal-suffix name keys "
                    "(autoscale.decimal_string_key) — name-ordered "
                    "victim/walk selection is no longer exact past it"
                )

    def resolved_statics(self) -> Dict[str, object]:
        """Every static of batched/statics.py as this build stands: the
        record the constructor resolved and narrowed, with `reclaim` as
        the geometry (or a restored checkpoint) left it."""
        return {**self.statics.as_dict(), "reclaim": self.reclaim}

    def _spread_counters(self) -> Dict[str, int]:  # ktpu: sync-ok(readout: the label filters' (C,) counters, read with the metrics)
        """The spread filter's and the node-affinity and taint filters'
        counters summed over clusters (none for a build without the filter),
        also left on this engine's tracer handle so that telemetry_report()
        carries them."""
        from kubernetriks_tpu.parallel.multihost import to_host

        leaves = {}
        spread, affinity = self.state.spread, self.state.affinity
        if spread is not None:
            leaves["spread_decisions"] = spread.decisions
            leaves["spread_decisions_bound"] = spread.decisions_bound
        if affinity is not None:
            leaves["affinity_attempts"] = affinity.attempts
            leaves["affinity_attempts_refused"] = affinity.attempts_refused
        got = {name: int(np.asarray(to_host(x)).sum()) for name, x in leaves.items()}
        self.tracer.counters.update(got)
        return got

    def metrics_summary(self) -> Dict:  # ktpu: sync-ok(readout: one-shot cross-cluster metric reduction after the run)
        """Cross-cluster reduction into the scalar printer's shape. On a
        cross-process mesh the metric arrays allgather over DCN first.
        Raises via check_autoscaler_bounds when a documented autoscaler
        work bound was crossed (divergence would otherwise be silent)."""
        from kubernetriks_tpu.parallel.multihost import to_host

        self.check_autoscaler_bounds()
        m = jax.tree.map(to_host, self.state.metrics)
        # Counters with no scalar counterpart go to the recorder and not
        # into the summary: the pending-free channel's two, and the windows
        # whose event application ran. The state's totals as they stand,
        # not a growth (a reset state reads 0).
        counters = recorder().counters
        counters["frees_total"] = int(np.asarray(m.frees_total).sum())
        counters["frees_deferred"] = int(np.asarray(m.frees_deferred).sum())
        counters["event_windows"] = int(np.asarray(m.event_windows).max())
        # The drained cycle's counters (step.fold_cycle_totals), summed over
        # clusters; the deepest cycle is the batch's deepest, and
        # cycle_passes_most the passes of the cluster that took most: never
        # more than the launches of a K-shaped decision kernel, which runs
        # once a pass in which ANY cluster has work (that count is the
        # batch's and no cluster's, so the state does not hold it).
        # cycle_deep / cycle_compacted: the cycles deeper than one pass, and
        # those of them the megakernel's second launch drained
        # (step._launch_by_depth). Also left on this engine's tracer handle,
        # so telemetry_report() carries them.
        cycle = {
            "cycle_passes": int(np.asarray(m.cycle_passes).sum()),
            "cycle_passes_most": int(np.asarray(m.cycle_passes).max(initial=0)),
            "cycle_count": int(np.asarray(m.cycle_count).sum()),
            "cycle_late_decisions": int(np.asarray(m.cycle_late_decisions).sum()),
            "cycle_decisions": int(np.asarray(m.scheduling_decisions).sum()),
            "cycle_deepest": int(np.asarray(m.cycle_deepest).max(initial=0)),
            "cycle_overruns": int(np.asarray(m.cycle_overruns).sum()),
            "cycle_deep": int(np.asarray(m.cycle_deep).sum()),
            "cycle_compacted": int(np.asarray(m.cycle_compacted).sum()),
        }
        counters.update(cycle)
        self.tracer.counters.update(cycle)
        if m.events_deep is not None:
            # The event chunk loop's two (step._apply_window_events_work; a
            # batch of more than one lane tile), summed over clusters:
            # cluster-windows with more events due than one pass applies, and
            # those of them finished in a lane tile of their own.
            events = {
                "events_deep": int(np.asarray(m.events_deep).sum()),
                "events_compacted": int(np.asarray(m.events_compacted).sum()),
            }
            counters.update(events)
            self.tracer.counters.update(events)
        if m.soft_attempts is not None:
            # The label scorers' two (pipeline.integer_scores; a build with
            # soft planes), summed over clusters: decisions a preferred term
            # or a PreferNoSchedule taint could sway, and those placed on a
            # node with the largest label score among the feasible ones.
            soft = {
                "soft_attempts": int(np.asarray(m.soft_attempts).sum()),
                "soft_honoured": int(np.asarray(m.soft_honoured).sum()),
            }
            counters.update(soft)
            self.tracer.counters.update(soft)
        counters.update(self._spread_counters())
        # The reschedule order's counters (step._stable_queue_rank), summed
        # over clusters: cluster-windows that ranked a removed node's pods,
        # and those that had more than the compacted rank holds (any of
        # them sent its window to the sort). Published by every build: the
        # rank is in every window program.
        resched = {
            "resched_rank_windows": int(np.asarray(m.resched_rank_windows).sum()),
            "resched_rank_sorted": int(np.asarray(m.resched_rank_sorted).sum()),
        }
        counters.update(resched)
        self.tracer.counters.update(resched)
        if self.fault_params is not None and self.fault_params.node_faults:
            # A build under node faults publishes the chaos counters the
            # same way (no new leaf: the metrics state has always held them).
            faults = {
                "node_crashes": int(np.asarray(m.node_crashes).sum()),
                "node_recoveries": int(np.asarray(m.node_recoveries).sum()),
                "pod_interruptions": int(np.asarray(m.pod_interruptions).sum()),
            }
            counters.update(faults)
            self.tracer.counters.update(faults)

        def est(e):
            count = np.asarray(e.count, np.int64)
            total = np.asarray(e.total, np.float64)
            total_sq = np.asarray(e.total_sq, np.float64)
            n = count.sum()
            if n == 0:
                return {"min": math.inf, "max": -math.inf, "mean": math.nan, "variance": math.nan}
            mean = total.sum() / n
            return {
                "min": float(np.asarray(e.minimum).min()),
                "max": float(np.asarray(e.maximum).max()),
                "mean": float(mean),
                "variance": float(total_sq.sum() / n - mean * mean),
            }

        return {
            "counters": {
                "pods_succeeded": int(np.asarray(m.pods_succeeded).sum()),
                "pods_removed": int(np.asarray(m.pods_removed).sum()),
                "terminated_pods": int(np.asarray(m.terminated_pods).sum()),
                "processed_nodes": int(np.asarray(m.processed_nodes).sum()),
                "scheduling_decisions": int(np.asarray(m.scheduling_decisions).sum()),
                "total_scaled_up_pods": int(np.asarray(m.scaled_up_pods).sum()),
                "total_scaled_down_pods": int(np.asarray(m.scaled_down_pods).sum()),
                "total_scaled_up_nodes": int(np.asarray(m.scaled_up_nodes).sum()),
                "total_scaled_down_nodes": int(np.asarray(m.scaled_down_nodes).sum()),
                # Chaos-engine fault counters (zero when faults are off).
                "node_crashes": int(np.asarray(m.node_crashes).sum()),
                "node_recoveries": int(np.asarray(m.node_recoveries).sum()),
                "node_downtime_s": float(
                    np.asarray(m.node_downtime_s, np.float64).sum()
                ),
                "pod_interruptions": int(np.asarray(m.pod_interruptions).sum()),
                "pod_restarts": int(np.asarray(m.pod_restarts).sum()),
                "pods_failed": int(np.asarray(m.pods_failed).sum()),
            },
            "timings": {
                "pod_duration": est(m.pod_duration),
                "pod_schedule_time": est(m.algo_latency),
                "pod_queue_time": est(m.queue_time),
            },
        }

    def cluster_metrics(self, cluster: int) -> Dict:  # ktpu: sync-ok(readout: per-cluster counters after the run)
        m = self.state.metrics
        return {
            "pods_succeeded": int(m.pods_succeeded[cluster]),
            "pods_removed": int(m.pods_removed[cluster]),
            "terminated_pods": int(m.terminated_pods[cluster]),
            "scheduling_decisions": int(m.scheduling_decisions[cluster]),
        }

    def hpa_replicas(self, cluster: int) -> Dict[str, int]:  # ktpu: sync-ok(readout: replica counts after the run)
        """Per-pod-group created replica counts (scalar equivalent:
        len(PodGroupInfo.created_pods))."""
        auto = self.state.auto
        assert auto is not None, "autoscaling is not enabled"
        head = to_host(auto.hpa_head)[cluster]
        tail = to_host(auto.hpa_tail)[cluster]
        names = self.pod_group_names[cluster]
        return {name: int(tail[i] - head[i]) for i, name in enumerate(names)}

    def ca_slots_reclaimed(self) -> np.ndarray:  # ktpu: sync-ok(readout: reclaim counter after the run)
        """(C,) CA reserve slots returned by the reclaim compaction
        (zeros when reclaim is off) — the 'reclaim actually fired'
        observable the endurance gates assert on."""
        auto = self.state.auto
        if auto is None or auto.ca_reclaimed is None:
            return np.zeros(self.n_clusters, np.int32)
        return np.asarray(to_host(auto.ca_reclaimed))

    def ca_node_counts(self, cluster: int) -> np.ndarray:  # ktpu: sync-ok(readout: node counts after the run)
        """Current cluster-autoscaler node count per node group."""
        auto = self.state.auto
        assert auto is not None, "autoscaling is not enabled"
        return to_host(auto.ca_count)[cluster]

    def node_count_at(self, t: float, cluster: int = 0) -> int:  # ktpu: sync-ok(readout: point-in-time node count query)
        """Alive node count at absolute time t, resolving pending
        create/remove effects with effect time <= t. The step applies an
        effect when it next runs a window PAST the effect's time — an
        implementation detail of the lazy window application — so a faithful
        'how many nodes exist at t' read must resolve the scheduled effects
        the state already carries (the batched equivalent of the scalar
        api_server.node_count() sampled mid-window).

        CA-slot effects carry a readout correction (r14, surfaced by the
        endurance gates at drift phases no short run reaches): the device
        pairs are the SCHEDULER/NODE-side visibility times the simulation
        semantics need (create d_ca_up = fire + 3*as_to_ca + 5*as_to_ps +
        ps_to_sched, the PS->scheduler notification; remove d_ca_down =
        fire + 3*as_to_ca + 4*as_to_ps + as_to_node, the node component
        going down), while the scalar oracle `api_server.node_count()`
        flips at the AS bookkeeping instants — `_handle_create_node` runs
        one (as_to_ps + ps_to_sched) BEFORE the scheduler hears, and
        `on_node_removed_from_cluster` one as_to_node AFTER the component
        died. Chaos never targets CA slots (their crash payload is zero
        padding), so every pending CA-slot effect is a CA-cycle effect
        and the constant shifts are exact. Effects a window already
        resolved can no longer be shifted, so boundary-exact samples keep
        a sub-delay edge — sample mid-window (the suite's boundary+5
        convention) for exact trajectories.

        Trace/chaos node events carry the complementary correction: a
        slab event earlier in the CURRENT (unexecuted) window is visible
        in neither the alive flags nor the pending pairs, so the readout
        replays the host-side node-event schedule
        (self._node_event_table) over the unapplied suffix with the same
        AS-bookkeeping shifts — a mid-window sample right after a chaos
        crash agrees with the scalar count (the r14 endurance gates
        sample exactly there)."""
        interval = self.config.scheduling_cycle_interval
        win = int(t // interval)
        off = t - win * interval
        cfg = self.config
        # The same AS-bookkeeping shifts for CA-slot pending pairs and
        # unapplied slab node events: the device times are scheduler/
        # node-side visibility, the scalar count flips at the AS
        # bookkeeping instants.
        up_shift = float(
            cfg.as_to_ps_network_delay + cfg.ps_to_sched_network_delay
        )
        down_shift = float(cfg.as_to_node_network_delay)
        nodes = self.state.nodes
        alive = to_host(nodes.alive)[cluster]
        cw = to_host(nodes.create_time.win)[cluster]
        co = to_host(nodes.create_time.off)[cluster]
        rw = to_host(nodes.remove_time.win)[cluster]
        ro = to_host(nodes.remove_time.off)[cluster]
        due_create = (cw < win) | ((cw == win) & (co <= off))
        due_remove = (rw < win) | ((rw == win) & (ro <= off))
        st = self.autoscale_statics
        if st is not None and st.ca_slots.shape[1] > 0:
            slots = np.asarray(st.ca_slots)[cluster]
            slots = slots[slots >= 0]
            if slots.size:
                ca = np.zeros(alive.shape[0], bool)
                ca[slots] = True
                abs_c = cw.astype(np.float64) * interval + co - up_shift
                abs_r = rw.astype(np.float64) * interval + ro + down_shift
                due_create = np.where(ca, abs_c <= t, due_create)
                due_remove = np.where(ca, abs_r <= t, due_remove)
        count = (alive | due_create) & ~due_remove
        # Trace/chaos slab node events the step has not APPLIED yet (their
        # window never executed — the r14 endurance gates sample mid-window
        # while a crash sits earlier in the same window): resolve them from
        # the host-side schedule, last transition at or before t wins.
        # Events in executed windows already live in the flags/pairs above.
        applied_win = int(to_host(self.state.time)[cluster])
        et, is_create, es, ew = self._node_event_table[cluster]
        eff = np.where(is_create, et - up_shift, et + down_shift)
        sel = (ew >= applied_win) & (eff <= t)
        # "Last transition wins" is defined on the EFFECTIVE (shifted)
        # times, not the slab order: a short-downtime crash/recover pair
        # inverts under the shifts (recover's -up_shift lands before
        # crash's +down_shift when downtime < up_shift + down_shift), and
        # the scalar's AS bookkeeping then processed the removal last.
        # Stable sort keeps slab FIFO order at equal effective instants.
        idx = np.nonzero(sel)[0]
        for i in idx[np.argsort(eff[idx], kind="stable")]:
            count[es[i]] = bool(is_create[i])
        return int(count.sum())

    # --- telemetry readout --------------------------------------------------

    def _maybe_drain_ring(self, force: bool = False):
        """Drain the device telemetry ring before records wrap out. The
        pressure check is pure host arithmetic (window cursor vs ring
        capacity); the blocking fetch itself lives in telemetry/ring.py
        and only ever runs at boundaries where the host already blocks —
        step_until_time entry/exit, readout, and (since the capacity
        observatory) the steady-state loop's OWN sync points, immediately
        after the superspan progress readback / slide-shift readback
        blocked anyway — never a new sync (the no-new-syncs half of the
        telemetry contract; dispatch_stats stay equal on/off). Returns the
        observatory's drain record when a drain happened, else None."""
        if self.state.telemetry is None:
            return None
        pending = self.next_window_idx - self._ring_drained_at
        if not force and pending * 2 < self._telemetry_ring_size:
            return None
        from kubernetriks_tpu.telemetry import ring as dring

        with sanitize.allow_transfer(
            self._sanitize,
            "telemetry ring drain riding an existing host-block boundary",
        ):
            buf, cursor = dring.snapshot(self.state.telemetry)
        dring.merge_snapshot(self._ring_seen, buf)
        cap = self.telemetry_series_windows
        if cap and len(self._ring_seen) > cap:
            # Prune the OLDEST windows past the series bound (disclosed
            # in telemetry_report as ring.series_dropped_windows).
            drop = sorted(self._ring_seen)[: len(self._ring_seen) - cap]
            for w in drop:
                del self._ring_seen[w]
            self._ring_series_dropped += len(drop)
        self._ring_windows_recorded = max(
            self._ring_windows_recorded, cursor
        )
        self._ring_drained_at = self.next_window_idx
        return self._observe_drain(buf)

    def _observe_drain(self, buf) -> Optional[Dict]:
        """Feed one drained ring buffer (an OWNED host copy — see
        drain_telemetry's aliasing note) to the capacity observatory:
        occupancy ingest, memory-watermark sample, watchdog pass, and the
        export hooks. Pure host work on drained copies."""
        if self.observatory is None:
            return None
        fresh = self.observatory.ingest(buf)
        feeder_rep = None
        if self._feeder is not None:
            feeder_rep = self._feeder.report()
            feeder_rep["restarts"] = self._feeder_restarts
            self.dispatch_stats["feeder_slabs_produced"] = (
                self._feeder_produced_total + feeder_rep["slabs_produced"]
            )
        stats = dict(self.dispatch_stats)
        return self.observatory.observe(
            resources=self._sample_resources(),
            dispatch_stats=stats,
            sync_budget={
                "steady_state_expected": stats["superspans"]
                + stats["fused_slides"],
                "observed_slide_syncs": stats["slide_syncs"],
            },
            feeder=feeder_rep,
            fresh=fresh,
        )

    def drain_telemetry(self) -> Dict:
        """Force a telemetry-ring drain + observatory observation NOW and
        return the drain record ({} when telemetry is off). THE explicit
        seam the watchdog/export path uses between step_until_time calls
        (PR 8 left mid-run drains riding step_until_time exits only; the
        steady-state loop now also drains under pressure at its own sync
        points, so a long single call can no longer silently exceed the
        windows_recorded > windows_kept disclosure unless ONE dispatch
        retires more than the ring holds).

        Owned-copy rule (the donated-dispatch aliasing hazard): on the
        CPU backend the drain's device fetch may ALIAS the live ring
        buffer, and the next donated dispatch mutates that buffer in
        place — telemetry/ring.snapshot therefore forces an owned
        np.array copy before anything downstream sees the rows. Rows
        returned here stay valid across later dispatches
        (tests/test_telemetry.py pins this against a donated engine)."""
        return self._maybe_drain_ring(force=True) or {}

    def attach_metrics_exporter(self, exporter) -> None:
        """Register a time-series export hook — an object with
        .emit(record: dict), e.g. telemetry/export.JsonlExporter — called
        once per ring drain with the observatory's pure-python record.
        Exports run strictly from drained host copies (the export seam
        carries the hot-path lint pragma with zero sync waivers)."""
        if self.observatory is None:
            raise ValueError(
                "telemetry is off — build with telemetry=True or "
                "KTPU_TRACE=1 to attach metrics exporters"
            )
        self.observatory.exporters.append(exporter)

    def _sample_resources(self) -> Dict:  # ktpu: sync-ok(drain-point resource sampling: backend allocator stats + host RSS + slab byte accounting — host-side reads, no simulation-state sync)
        """Host/device memory sample for the observatory's watermarks:
        host RSS (procfs), backend allocator stats where the platform
        exposes them (TPU/GPU; CPU usually returns nothing), and EXACT
        slab/ring byte accounting from the staging machinery. Runs only
        at drain points (ring pressure / explicit drain_telemetry), never
        inside a dispatch."""
        from kubernetriks_tpu.telemetry.observatory import sample_host_memory

        res: Dict = dict(sample_host_memory())
        dev_in_use = dev_peak = 0
        have_dev = False
        for d in jax.local_devices():
            try:
                ms = d.memory_stats()
            except Exception:
                ms = None
            if not ms:
                continue
            have_dev = True
            dev_in_use += int(ms.get("bytes_in_use", 0))
            dev_peak += int(ms.get("peak_bytes_in_use", 0))
        if have_dev:
            res["device_bytes_in_use"] = dev_in_use
            res["device_peak_bytes_in_use"] = dev_peak
        res["slabs"] = self._slab_accounting()
        return res

    def _slab_accounting(self) -> Dict:
        """Exact staging-memory accounting (host arithmetic over known
        geometry + buffer sizes): the device slide payload, live staging
        slabs, the feeder ring's capacity bound, and the telemetry ring
        itself. Flat numbers here across superspans ARE the bounded-memory
        claim of the streaming pipeline (tests/test_soak.py pins it)."""

        def nbytes(tree) -> int:
            total = 0
            for leaf in jax.tree_util.tree_leaves(tree):
                total += int(getattr(leaf, "nbytes", 0) or 0)
            return total

        host_payload = 0
        if self._full_pods is not None:
            host_payload += sum(
                int(a.nbytes) for a in self._full_pods.values()
            )
        for small in (
            getattr(self, "_pod_create_win", None),
            getattr(self, "_pod_name_rank_full", None),
        ):
            if small is not None:
                host_payload += int(small.nbytes)
        acct = {
            "device_slide_bytes": (
                nbytes(self._device_slide)
                if self._device_slide is not None
                else 0
            ),
            # Resident host payload: the whole-trace request/duration
            # arrays (released by attach_payload_source) plus the small
            # per-pod int32 tables the engine keeps for O(1) lookups —
            # the observable behind the bounded-host-memory claim.
            "host_payload_bytes": host_payload,
            "stage_bytes": nbytes(
                [s for s in (self._stage_cur, self._stage_next) if s is not None]
            ),
            "telemetry_ring_bytes": (
                nbytes(self.state.telemetry)
                if self.state.telemetry is not None
                else 0
            ),
        }
        if self._feeder is not None:
            n_arrays = 5 + (1 if self.autoscale_statics is not None else 0)
            per_slab = (
                self.n_clusters * self._feeder.width * 4 * n_arrays
            )
            acct["feeder_slab_bytes"] = per_slab
            acct["feeder_ring_capacity_bytes"] = per_slab * self._feeder.depth
        return acct

    def telemetry_window_series(self):
        """(windows (Wn,), records (Wn, C, K)) device-ring per-window
        series; columns follow telemetry.ring.RING_COLUMNS. Empty arrays
        when telemetry is off."""
        from kubernetriks_tpu.telemetry import ring as dring

        self._maybe_drain_ring(force=True)
        return dring.series(self._ring_seen, self.n_clusters)

    def telemetry_report(self) -> Dict:
        """Aggregated flight-recorder readout: per-phase host wall time
        and counters of THIS engine (its handle's own aggregates, exact
        when the shared span ring wrapped and whatever other engines the
        process runs), dispatch stats incl.
        ladder_fallbacks, the observed sync count vs the documented
        steady-state budget (1 progress readback per superspan + 1 shift
        readback per fused slide — the lint pass's sync-ok waiver set),
        stage-prefetch hit/miss counts, the dispatch-chunk histogram, and
        the device ring's totals. With telemetry off (enabled: False) the
        device-ring and observatory sections are absent."""
        feeder_rep = None
        if self._feeder is not None:
            # ONE snapshot under the feeder's lock: syncing dispatch_stats
            # from the same report keeps the cumulative counter a superset
            # of the section even while the producer is mid-publish.
            feeder_rep = self._feeder.report()
            feeder_rep["restarts"] = self._feeder_restarts
            self.dispatch_stats["feeder_slabs_produced"] = (
                self._feeder_produced_total + feeder_rep["slabs_produced"]
            )
        stats = dict(self.dispatch_stats)
        rep = {"enabled": self._telemetry, "dispatch_stats": stats}
        self._spread_counters()
        rep.update(self.tracer.report())
        if feeder_rep is not None:
            # Streaming-feeder section: production counters, the
            # ring-depth gauge, and the stall split (feeder-not-ready vs
            # upload-wait — the same two numbers the stage_wait_* tracer
            # spans carry, kept here so untraced runs still expose them).
            rep["feeder"] = feeder_rep
        rep["sync_budget"] = {
            "steady_state_expected": stats["superspans"]
            + stats["fused_slides"],
            "observed_slide_syncs": stats["slide_syncs"],
        }
        hits = rep["counters"].get("stage_prefetch_hit", 0)
        misses = rep["counters"].get("stage_prefetch_miss", 0)
        if hits + misses:
            rep["stage_prefetch_hit_rate"] = hits / (hits + misses)
        # Per-window cost line: the window-program DISPATCH phases plus the
        # blocking readback WAITS (progress_wait / shift_wait), divided by
        # the windows the device ring recorded. Dispatch is asynchronous,
        # so execution time surfaces in the waits — dispatch + wait
        # together bound compile + device time per window (on a warm jit
        # cache the wait share IS the device-execution proxy). Held by
        # tests/test_telemetry.py::test_report_is_the_engines_own_when_engines_interleave.
        from kubernetriks_tpu.telemetry.tracer import PHASE_NAMES as _PN

        window_phases = (
            _PN[PH_WINDOW_CHUNK],
            _PN[PH_FUSED_CHUNK_SLIDE],
            _PN[PH_SUPERSPAN],
            _PN[PH_PROGRESS_WAIT],
            _PN[PH_SHIFT_WAIT],
            # Streaming-feeder stalls block the dispatch loop exactly like
            # the readback waits, so they belong to the per-window cost
            # (zero on non-streaming runs — continuity with r7-r9 numbers).
            _PN[PH_STAGE_WAIT_FEEDER],
            _PN[PH_STAGE_WAIT_UPLOAD],
            "chunk_fenced",
        )
        win_ms = sum(
            rep["spans"][p]["total_ms"]
            for p in window_phases
            if p in rep.get("spans", {})
        )
        if self.state.telemetry is not None:
            from kubernetriks_tpu.telemetry import ring as dring

            wins, data = self.telemetry_window_series()
            rep["ring"] = {
                "columns": list(dring.RING_COLUMNS),
                "windows_recorded": self._ring_windows_recorded,
                "windows_kept": int(len(wins)),
                "series_dropped_windows": self._ring_series_dropped,
                # Sums only make sense for the per-window ACTION deltas;
                # point-in-time gauges (queue depths, alive nodes, the
                # observatory's reserve-occupancy columns) report their
                # high-water mark instead.
                "totals": {
                    name: int(data[:, :, col].sum()) if len(wins) else 0
                    for col, name in enumerate(dring.RING_COLUMNS)
                    if col > 0 and name not in dring.GAUGE_COLUMNS
                },
                "high_water": {
                    name: int(data[:, :, col].max()) if len(wins) else 0
                    for col, name in enumerate(dring.RING_COLUMNS)
                    if name in dring.GAUGE_COLUMNS
                },
            }
            # Of the pod-block row tiles whole-block sweeps would have read
            # in the megakernel's steps, the share its live-tile sweeps
            # did read (None: no megakernel step was recorded).
            totals = rep["ring"]["totals"]
            rep["ring"]["cycle_rows_swept_share"] = (
                totals["cycle_tiles_swept"] / totals["cycle_tile_steps"]
                if totals["cycle_tile_steps"]
                else None
            )
            # Slab reads a window: the event loop runs until no cluster has
            # a due event left, so a window paid its clusters' maximum.
            rep["ring"]["event_chunks_per_window"] = (
                float(
                    data[:, :, dring.RING_COLUMNS.index("event_chunks")]
                    .max(axis=1)
                    .mean()
                )
                if len(wins)
                else None
            )
        if self.observatory is not None:
            # Capacity-observatory section: occupancy (current +
            # high-water vs reserve capacity), host/device memory
            # watermarks, slab/ring accounting, watchdog verdicts. The
            # memory sample is refreshed so the report reflects NOW.
            self.observatory.update_memory(self._sample_resources())
            rep["resources"] = self.observatory.report()
            windows = int(self._ring_windows_recorded)
            if windows > 0:
                rep["per_window"] = {
                    "windows": windows,
                    "window_program_ms_total": win_ms,
                    "ms_per_window": win_ms / windows,
                }
        return rep

    def write_chrome_trace(self, path: str) -> str:
        """Write the Chrome trace-event JSON (Perfetto-loadable): the
        process-wide recorder's host spans and async-readback flow arrows
        and, with telemetry on, the device ring as sim-time counter
        tracks."""
        extra = None
        if self.state.telemetry is not None:
            from kubernetriks_tpu.telemetry import ring as dring

            wins, data = self.telemetry_window_series()
            extra = dring.counter_events(
                wins, data, self.config.scheduling_cycle_interval
            )
        return self.tracer.write_chrome_trace(path, extra)

    def close(self) -> None:
        """Release background resources — currently the streaming
        feeder's producer thread. Idempotent and optional: the producer
        is a daemon that exits with the process (and on its own once the
        final slab is published), but long-lived hosts building many
        engines should close the ones they abandon."""
        self._close_feeder()

    # --- checkpoint / resume ------------------------------------------------
    # The whole simulation state is one pytree of arrays, so checkpointing is
    # a direct orbax save (SURVEY §5.4: absent in the reference — runs are
    # seed+config+trace — but cheap here and useful for long RL training).

    def _ckpt_payload(self):
        return {
            "state": self.state,
            "next_window_idx": jnp.asarray(self.next_window_idx, jnp.int32),
        }

    def save_checkpoint(self, path: str) -> None:
        """Persist the device state + window cursor to an orbax checkpoint
        directory (overwrites), and the accumulated gauge series — whose
        length is run-dependent, unlike the fixed-shape state pytree — to a
        numpy sidecar next to it."""
        from kubernetriks_tpu.checkpoint import ckpt_save

        with self.tracer.span(PH_CKPT_SAVE):
            ckpt_save(path, self._ckpt_payload())
            # The window can GROW mid-run (_grow_pod_window), changing the
            # pod arrays' shapes — record it so load_checkpoint can grow a
            # freshly built engine to match before restoring.
            meta_path = os.path.abspath(path) + ".meta.json"
            meta = {}
            if self.pod_window is not None:
                meta["pod_window"] = int(self.pod_window)
            if self.state.telemetry is not None:
                # The telemetry ring is part of the state pytree; a
                # restore template must carry a matching ring, so record
                # its capacity for load_checkpoint's loud guard.
                meta["telemetry_ring"] = int(self._telemetry_ring_size)
            if self.reclaim:
                # Slot-reclaim leaves (ca_alloc/ca_total/...) ride the
                # state pytree; record the mode so a mismatched restore
                # raises the actionable message instead of an opaque
                # manifest diff. Reclaim-off saves write nothing,
                # keeping older checkpoints loadable.
                meta["reclaim"] = True
            from kubernetriks_tpu.batched.pipeline import DEFAULT_PROFILE

            if self.profile != DEFAULT_PROFILE:
                # The compiled scheduler profile is an engine-build static:
                # restoring this state into an engine compiled with a
                # different profile would silently continue the run under
                # different scheduling semantics — record it for
                # load_checkpoint's loud guard (default-profile saves write
                # nothing, keeping old checkpoints loadable).
                meta["scheduler_profile"] = {
                    "name": self.profile.name,
                    "filters": list(self.profile.filters),
                    "scores": [list(s) for s in self.profile.scores],
                }
            if meta:
                import json

                with open(meta_path, "w") as fh:
                    json.dump(meta, fh)
            elif os.path.exists(meta_path):
                # A plain save over a previously windowed/telemetry
                # checkpoint must not leave the stale meta to mislead a
                # later load (same shadowing rule as the gauges sidecar
                # below).
                os.remove(meta_path)
            # Gauge series sidecar (run-length-dependent shape, unlike the
            # state pytree); an empty series removes a stale file so a
            # previous save's gauges never shadow this run's on restore.
            self._gauges.save_sidecar(os.path.abspath(path) + ".gauges.npz")

    def load_checkpoint(self, path: str) -> None:  # ktpu: sync-ok(checkpoint restore: cold path)
        """Restore state saved by save_checkpoint into this simulation (which
        must have been built from the same config/traces — the current state
        pytree provides the restore structure). Restored arrays land
        unsharded; re-apply device placement for mesh runs if needed."""
        from kubernetriks_tpu.checkpoint import ckpt_restore

        meta_path = os.path.abspath(path) + ".meta.json"
        meta = {}
        if os.path.exists(meta_path):
            import json

            with open(meta_path) as fh:
                meta = json.load(fh)
        # Telemetry mismatch guard: the ring is part of the state pytree,
        # so a template without a matching ring would fail deep inside
        # ckpt_restore as an opaque structure error — raise the
        # actionable message here instead (the same treatment pod_window
        # gets below). Runs with meta absent too: a plain save writes no
        # meta at all, and restoring it into a telemetry-armed engine is
        # exactly the mismatch.
        saved_reclaim = bool(meta.get("reclaim", False))
        if saved_reclaim != self.reclaim:
            # Tristate-defaulted engines FOLLOW the checkpoint instead of
            # raising: KTPU_RECLAIM defaults on for accelerator backends,
            # so every pre-reclaim checkpoint would otherwise refuse to
            # restore on TPU/GPU until the user dug up KTPU_RECLAIM=0.
            # The swap is a cold-path mode flip: reclaim is a per-call
            # jit static (next dispatch compiles the other program) and
            # the slot-reclaim leaves are presence-only in the auto
            # pytree, so matching the TEMPLATE to the saved structure is
            # all the restore needs. Explicit reclaim=/KTPU_RECLAIM
            # requests still raise — the user asked for a specific mode.
            followable = self._reclaim_requested is None and (
                not saved_reclaim
                or (
                    self.autoscale_statics is not None
                    and self.autoscale_statics.ca_slot_class is not None
                )
            )
            if followable:
                import warnings as _warnings

                _warnings.warn(
                    f"checkpoint saved with reclaim={saved_reclaim} but "
                    f"this engine defaulted to {self.reclaim} "
                    f"(KTPU_RECLAIM tristate): following the checkpoint "
                    f"— continuing with reclaim={saved_reclaim}",
                    RuntimeWarning,
                    stacklevel=2,
                )
                self.reclaim = saved_reclaim
                auto_t = self.state.auto
                if saved_reclaim:
                    fresh = init_autoscale_state(
                        self.autoscale_statics,
                        reclaim=True,
                        collect=auto_t.col_next is not None,
                    )
                    auto_t = auto_t._replace(
                        ca_alloc=fresh.ca_alloc,
                        ca_total=fresh.ca_total,
                        ca_reclaimed=fresh.ca_reclaimed,
                    )
                else:
                    auto_t = auto_t._replace(
                        ca_alloc=None, ca_total=None, ca_reclaimed=None
                    )
                self.state = self.state._replace(auto=auto_t)
            else:
                raise ValueError(
                    f"checkpoint reclaim mismatch: saved with reclaim="
                    f"{saved_reclaim}, this engine built with "
                    f"{self.reclaim} — the slot-reclaim leaves are part "
                    "of the state pytree; build the restoring engine "
                    f"with reclaim={saved_reclaim} (KTPU_RECLAIM) to "
                    "continue the run"
                )
        saved_ring = meta.get("telemetry_ring")
        have_ring = (
            self._telemetry_ring_size
            if self.state.telemetry is not None
            else None
        )
        if saved_ring != have_ring:
            raise ValueError(
                f"checkpoint telemetry ring mismatch: saved "
                f"telemetry_ring={saved_ring}, this engine has "
                f"{have_ring} — build with telemetry="
                f"{saved_ring is not None} and telemetry_ring="
                f"{saved_ring} (or KTPU_TRACE) to restore it"
            )
        # Scheduler-profile mismatch guard: the compiled profile is a
        # build-time static, so a restore into a differently-profiled
        # engine would silently continue the run under different
        # scheduling semantics (the silent-wrong-profile failure mode).
        # Saves under the default profile write no key; absence == default.
        from kubernetriks_tpu.batched.pipeline import (
            CompiledProfile,
            DEFAULT_PROFILE,
        )

        saved_prof = meta.get("scheduler_profile")
        if saved_prof is not None:
            saved_prof = CompiledProfile(
                name=saved_prof["name"],
                filters=tuple(saved_prof["filters"]),
                scores=tuple(
                    (str(n), float(w)) for n, w in saved_prof["scores"]
                ),
            )
        if (saved_prof or DEFAULT_PROFILE) != self.profile:
            raise ValueError(
                f"checkpoint scheduler-profile mismatch: saved "
                f"{(saved_prof or DEFAULT_PROFILE).name!r} "
                f"{(saved_prof or DEFAULT_PROFILE).scores}, this engine "
                f"compiled {self.profile.name!r} {self.profile.scores} — "
                "build the restoring engine with the same "
                "scheduler_profile to continue the run"
            )
        saved_window = meta.get("pod_window")
        if saved_window is not None and self.pod_window is not None:
            while self.pod_window < saved_window:
                if not self._grow_pod_window():
                    break
            if self.pod_window != saved_window:
                # Not an assert: under python -O the mismatch would
                # surface later as an opaque ckpt_restore shape error.
                raise ValueError(
                    f"checkpoint was saved at pod_window={saved_window}; "
                    f"this engine is at {self.pod_window} and cannot match"
                )
        with self.tracer.span(PH_CKPT_RESTORE):
            restored = ckpt_restore(path, self._ckpt_payload())
            self.state = restored["state"]
            self.next_window_idx = int(restored["next_window_idx"])
            self._pod_base = int(np.asarray(self.state.pod_base)[0])
            # Re-seek the streaming feeder (and drop engine-held staging
            # slabs): the restored base may precede everything staged so
            # far, and the ring's never-re-offer invariant makes serving
            # an earlier base an assertion — the rebuilt feeder restarts
            # its slab schedule at the restored base instead of replaying
            # (slab content is position-keyed, so no replay divergence is
            # possible either way).
            self._close_feeder()
            self._stage_cur = None
            self._stage_next = None
            self._refresh_name_ranks()
            self._gauges = GaugeSeries.load_sidecar(
                os.path.abspath(path) + ".gauges.npz"
            )
            # Ring rows drained before the restore described the
            # pre-restore trajectory; the restored ring carries its own.
            self._ring_seen = {}
            self._ring_series_dropped = 0
            self._ring_windows_recorded = 0
            self._ring_drained_at = 0
            if self.observatory is not None:
                # The occupancy trajectory restarts at the restored state;
                # mixing pre-restore points would corrupt the watchdog fit.
                self.observatory.reset()

    def gauge_series(self):
        """(times (W,), samples (W, C, 7)) accumulated gauge time-series;
        columns follow the scalar GAUGE_CSV_COLUMNS after the timestamp
        (series buffer: telemetry/gauges.py)."""
        return self._gauges.series(
            self.n_clusters, self.config.scheduling_cycle_interval
        )

    def write_gauge_csv(self, path: str, cluster: int = 0) -> None:
        """Dump one cluster's gauge series in the scalar collector's 8-column
        schema (reference: src/metrics/collector.rs:216-228), so the offline
        plotting tooling consumes either backend's output unchanged."""
        self._gauges.write_csv(
            path,
            cluster,
            self.n_clusters,
            self.config.scheduling_cycle_interval,
        )

    def pod_view(self, cluster: int) -> Dict[str, Dict]:  # ktpu: sync-ok(readout: name-keyed pod states for equivalence tests)
        """Name-keyed pod states for equivalence tests against the scalar
        path. With a sliding pod window, only the currently-resident slots
        appear (shifted-out pods are terminal and already counted)."""
        phases = to_host(self.state.pods.phase)[cluster]
        nodes = to_host(self.state.pods.node)[cluster]
        start_pair = self.state.pods.start_time
        starts = to_f64(
            type(start_pair)(
                win=to_host(start_pair.win)[cluster],
                off=to_host(start_pair.off)[cluster],
            ),
            self.config.scheduling_cycle_interval,
        )
        names = self.pod_names[cluster]
        node_names = self.node_names[cluster]
        W = self.pod_window
        out = {}
        for slot in range(phases.shape[0]):
            # Device slot -> global slot: window segment shifts by pod_base,
            # the resident pod-group tail by the fixed resident_shift.
            if W is not None and slot >= W:
                g = self._resident_shift + slot
            else:
                g = self._pod_base + slot
            if g >= len(names) or not names[g]:
                continue  # batch padding (or segmented-layout filler) slot
            out[names[g]] = {
                "phase": int(phases[slot]),
                "node": node_names[nodes[slot]] if nodes[slot] >= 0 else None,
                "start_time": float(starts[slot]),
            }
        return out


def build_batched_from_traces(
    config: SimulationConfig,
    cluster_events,
    workload_events,
    n_clusters: int = 1,
    **kwargs,
) -> BatchedSimulation:
    """Replicate one (cluster trace, workload trace) pair across n_clusters —
    the homogeneous-batch benchmark shape.

    With fault injection enabled and node faults configured, each cluster
    gets its OWN crash/recover schedule (the counter PRNG keys on the
    cluster index — cluster 0 matches the scalar path), so the trace is
    compiled per cluster instead of tiled."""
    ram_unit = kwargs.pop("ram_unit", DEFAULT_RAM_UNIT)
    slot_mult = kwargs.pop("pod_group_slot_multiplier", 2)

    from kubernetriks_tpu import chaos

    fault_cfg = getattr(config, "fault_injection", None)
    if chaos.has_node_faults(fault_cfg):
        fault_seed = (
            fault_cfg.seed if fault_cfg.seed is not None else config.seed
        )
        # Scenario-vector fleet: per-LANE crash-chain seeds. The chain
        # compiler then keys every lane on cluster 0 with its own seed —
        # a lane's crash schedule becomes a pure function of its scenario
        # seed (same-seed lanes share one schedule; lane c with seed s
        # matches the scalar oracle run with seed s), instead of the
        # replicated-batch default where every lane derives a distinct
        # schedule from (shared seed, lane index). NOTE: chain events are
        # compiled into the trace slab, so node-fault seeds are fixed at
        # BUILD (per wave of lanes they are config data the fleet sets
        # once); the pod-fault seed channel stays pure traced data.
        scenario = kwargs.get("scenario")
        lane_seeds = None
        if scenario is not None:
            # ANY scenario build keys scenario-pure: the engine installs
            # consts.fault_seed for the pod channel whenever a scenario
            # is present (defaulting every lane to the config seed), so
            # the node chains must follow the same rule or the two fault
            # channels would mix per-lane and per-index keying.
            seeds = scenario.get("fault_seed")
            lane_seeds = np.broadcast_to(
                np.asarray(  # ktpu: sync-ok(engine build: host numpy over the scenario seed vector, no device values)
                    seeds if seeds is not None else fault_seed, np.int64
                ),
                (n_clusters,),
            )
        horizon = chaos.fault_horizon(
            fault_cfg, cluster_events, workload_events
        )
        # Same (seed, cluster-key) -> same chain: memoize the compile so
        # a fleet of repeated scenarios pays one chain per unique seed.
        _chain_cache: dict = {}

        def _compiled_for(c: int):
            seed = fault_seed if lane_seeds is None else int(lane_seeds[c])
            ckey = c if lane_seeds is None else 0
            got = _chain_cache.get((seed, ckey))
            if got is None:
                got = _chain_cache[(seed, ckey)] = compile_cluster_trace(
                    chaos.inject_node_faults(
                        cluster_events,
                        fault_cfg,
                        seed,
                        ckey,
                        horizon,
                        config.scheduling_cycle_interval,
                    ),
                    workload_events,
                    config,
                    ram_unit=ram_unit,
                    pod_group_slot_multiplier=slot_mult,
                )
            return got

        compiled_list = [_compiled_for(c) for c in range(n_clusters)]
        return BatchedSimulation(config, compiled_list, **kwargs)

    compiled = compile_cluster_trace(
        cluster_events,
        workload_events,
        config,
        ram_unit=ram_unit,
        pod_group_slot_multiplier=slot_mult,
    )
    return BatchedSimulation(config, [compiled] * n_clusters, **kwargs)
