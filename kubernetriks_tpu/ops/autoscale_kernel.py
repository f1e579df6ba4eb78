"""Mosaic kernel for the cluster-autoscaler scale-down walk.

The batched scale-down (`batched/autoscale.py _ca_scale_down`, reference
semantics: src/autoscalers/cluster_autoscaler/kube_cluster_autoscaler.rs:242-290)
walks CA candidate nodes in node-name order; each under-utilized candidate
tries to first-fit its (<= K_sd) pods onto OTHER alive nodes in name order,
committing the virtual-allocatable deductions on success so later candidates
see them. The dependence chain is real — but the XLA formulation is a
`while_loop` over S candidate slots with an inner K_sd-step scan: up to
S x K_sd sequential launches of tiny (C, N) ops, measured at ~29 ms/window
on the composed flagship shape (C=256, N=96, S=64, K_sd=8) — ~75% of the
whole composed window cost.

Here the walk runs INSIDE one kernel: clusters ride the 128-wide lane axis
(the house transposed layout of ops/scheduler_kernel.py), nodes ride the
sublane axis, and the sequential candidate/pod iterations are in-kernel
loops over VMEM-resident tiles with zero per-iteration dispatch cost. Pod
requirements per candidate are pre-gathered to (S*K_sd, C) tables by cheap
vectorized XLA gathers, so the kernel never touches the (C, P) pod axis.

Semantics are bit-identical to the XLA path: same one-hot candidate mask,
same lowest-index tie-break on equal name ranks, same commit/rollback per
candidate, same early bound at the last alive candidate. The utilization
threshold compare runs in float32 in both paths (autoscale.py casts
ca_threshold to f32 for the compare so kernel and XLA agree bit-for-bit).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_LANE = 128  # clusters per grid program (lane tile)
_SUB = 8  # f32/i32 sublane tile
_BIG_I32 = np.iinfo(np.int32).max
_VMEM_LIMIT = 100 * 1024 * 1024
# Both pallas_calls carry name= their jitted wrapper's name: the device trace's
# event for the kernel (see ops/scheduler_kernel.py).



def ca_down_kernel_fits(n_nodes: int, n_slots: int, k_sd: int) -> bool:
    """VMEM fits-check: 9 node tiles (7 in + 2 scratch working
    allocatables), 4 slot tiles, 3 (S*K) pod tables, meta — double-buffered
    by Mosaic, ~40% headroom against the raised scoped limit."""
    np_pad = -(-n_nodes // _SUB) * _SUB
    sp_pad = -(-n_slots // _SUB) * _SUB
    skp = -(-(n_slots * k_sd) // _SUB) * _SUB
    resident = (9 * np_pad + 4 * sp_pad + 3 * skp + _SUB) * _LANE * 4
    return 2 * resident <= int(0.8 * _VMEM_LIMIT)


def _ca_down_kernel(
    k_sd: int,
    meta_ref,        # (8, LC) f32: row0 branch(0/1), row1 threshold
    alive_ref,       # (Np, LC) int32 0/1
    notpend_ref,     # (Np, LC) int32 0/1 (no pending removal effect)
    cap_cpu_ref,     # (Np, LC) int32
    cap_ram_ref,     # (Np, LC) int32
    vcpu_ref,        # (Np, LC) int32 storage-visible virtual allocatable
    vram_ref,        # (Np, LC) int32
    rank_ref,        # (Np, LC) int32 node-name rank (BIG on padding)
    slot_ref,        # (Sp, LC) int32 global node slot per name-ordered candidate; -1 pad
    cand_alive_ref,  # (Sp, LC) int32 0/1
    cnt_ref,         # (Sp, LC) int32 pods on candidate
    prc_ref,         # (SKp, LC) int32 pod req cpu, row s*k_sd+k
    prr_ref,         # (SKp, LC) int32 pod req ram
    pv0_ref,         # (SKp, LC) int32 0/1 pod-slot valid (k < cnt)
    removed_out,     # (Sp, LC) int32
    vcpu_s,          # (Np, LC) int32 VMEM scratch: working virtual allocatable
    vram_s,          # (Np, LC) int32 VMEM scratch
):
    i0 = jnp.int32(0)
    i1 = jnp.int32(1)
    bigi = jnp.int32(_BIG_I32)
    f1 = jnp.float32(1.0)
    Ki = jnp.int32(k_sd)

    branch = meta_ref[0:1, :] != jnp.float32(0.0)  # (1, LC)
    thresh = meta_ref[1:2, :]  # (1, LC) f32

    alive = alive_ref[:] != i0  # (Np, LC)
    iota_n = jax.lax.broadcasted_iota(jnp.int32, alive.shape, 0)
    vcpu_s[:] = vcpu_ref[:]
    vram_s[:] = vram_ref[:]
    removed_out[:] = jnp.zeros_like(removed_out)

    # Walk bound: position after the LAST alive candidate in name order
    # across the tile's lanes (dead/pad candidates inside the bound no-op
    # through the eligibility gate — same bound as the XLA while_loop).
    iota_s = jax.lax.broadcasted_iota(jnp.int32, cand_alive_ref.shape, 0)
    s_bound = jnp.max(jnp.where(cand_alive_ref[:] != i0, iota_s + i1, i0))

    def candidate(s):
        slot = slot_ref[pl.ds(s, 1), :]  # (1, LC)
        oh = iota_n == slot  # (Np, LC); slot=-1 matches nothing
        ohi = oh.astype(jnp.int32)
        alive_here = (cand_alive_ref[pl.ds(s, 1), :] != i0) & branch
        not_pend = jnp.max(ohi * notpend_ref[:], axis=0, keepdims=True) > i0

        # Integer subtract THEN cast, exactly like the XLA path's
        # (cap - valloc).astype(f32) / max(cap, 1).astype(f32).
        cap_c = jnp.max(ohi * cap_cpu_ref[:], axis=0, keepdims=True)
        cap_r = jnp.max(ohi * cap_ram_ref[:], axis=0, keepdims=True)
        vc_at = jnp.max(
            jnp.where(oh, vcpu_s[:], -bigi), axis=0, keepdims=True
        )
        vr_at = jnp.max(
            jnp.where(oh, vram_s[:], -bigi), axis=0, keepdims=True
        )
        used_c = (cap_c - vc_at).astype(jnp.float32)
        used_r = (cap_r - vr_at).astype(jnp.float32)
        capc = jnp.maximum(cap_c, i1).astype(jnp.float32)
        capr = jnp.maximum(cap_r, i1).astype(jnp.float32)
        util = jnp.maximum(used_c / capc, used_r / capr)
        eligible = alive_here & not_pend & (util < thresh)

        cnt = cnt_ref[pl.ds(s, 1), :]  # (1, LC)
        attempt = eligible & (cnt <= Ki)  # overflow: conservatively skip

        vc = vcpu_s[:]
        vr = vram_s[:]
        ok = attempt
        for k in range(k_sd):  # static unroll; K_sd is small (default 8)
            row = pl.ds(s * Ki + jnp.int32(k), 1)
            rc = prc_ref[row, :]
            rr = prr_ref[row, :]
            pv = (pv0_ref[row, :] != i0) & attempt
            fit = alive & ~oh & (rc <= vc) & (rr <= vr)
            # First-fit in NODE-NAME order, lowest-index tie-break (exactly
            # lax.argmin over the masked rank in the XLA path).
            mrank = jnp.min(
                jnp.where(fit, rank_ref[:], bigi), axis=0, keepdims=True
            )
            any_fit = mrank < bigi
            mini = jnp.min(
                jnp.where(fit & (rank_ref[:] == mrank), iota_n, bigi),
                axis=0,
                keepdims=True,
            )
            place = pv & any_fit
            tgt = place & (iota_n == mini)
            vc = vc - jnp.where(tgt, rc, i0)
            vr = vr - jnp.where(tgt, rr, i0)
            ok = ok & (~pv | any_fit)

        # Commit on success, roll back otherwise; commits persist across
        # later candidates (reference :141-156).
        success = ok  # attempt folded in at init
        vcpu_s[:] = jnp.where(success, vc, vcpu_s[:])
        vram_s[:] = jnp.where(success, vr, vram_s[:])
        removed_out[pl.ds(s, 1), :] = success.astype(jnp.int32)

    def loop_body(s):
        candidate(s)
        return s + i1

    jax.lax.while_loop(lambda s: s < s_bound, loop_body, jnp.int32(0))


def _pad_axis(x: jnp.ndarray, axis: int, to: int, value) -> jnp.ndarray:
    pad = to - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.jit, static_argnames=("k_sd", "interpret"))
def fused_ca_scale_down(
    branch: jnp.ndarray,      # (C, 1) bool/int32
    thresh: jnp.ndarray,      # (C, 1) float32
    alive: jnp.ndarray,       # (C, N) bool/int32
    not_pending: jnp.ndarray, # (C, N) bool/int32
    cap_cpu: jnp.ndarray,     # (C, N) int32
    cap_ram: jnp.ndarray,     # (C, N) int32
    vcpu: jnp.ndarray,        # (C, N) int32 storage-visible virtual allocatable
    vram: jnp.ndarray,        # (C, N) int32
    name_rank: jnp.ndarray,   # (C, N) int32
    slot_perm: jnp.ndarray,   # (C, S) int32
    cand_alive: jnp.ndarray,  # (C, S) bool/int32
    cnt: jnp.ndarray,         # (C, S) int32
    pr_cpu: jnp.ndarray,      # (C, S*K) int32
    pr_ram: jnp.ndarray,      # (C, S*K) int32
    pv0: jnp.ndarray,         # (C, S*K) bool/int32
    k_sd: int = 8,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns removed_perm (C, S) bool: candidates (in name order) whose
    pods all re-placed and that the walk removes."""
    C, N = alive.shape
    S = slot_perm.shape[1]
    Cp = -(-C // _LANE) * _LANE
    Np = -(-N // _SUB) * _SUB
    Sp = -(-S // _SUB) * _SUB
    SKp = -(-(S * k_sd) // _SUB) * _SUB

    def prep(x, n_sub, fill):
        return _pad_axis(_pad_axis(x.T, 0, n_sub, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        meta = jnp.concatenate(
            [
                branch.astype(jnp.float32).T,
                jnp.broadcast_to(thresh.astype(jnp.float32).T, (1, C)),
            ],
            axis=0,
        )
        meta_p = _pad_axis(_pad_axis(meta, 0, _SUB, 0.0), 1, Cp, 0.0)
        args = (
            meta_p,
            prep(alive.astype(jnp.int32), Np, 0),
            prep(not_pending.astype(jnp.int32), Np, 0),
            prep(cap_cpu.astype(jnp.int32), Np, 0),
            prep(cap_ram.astype(jnp.int32), Np, 0),
            prep(vcpu.astype(jnp.int32), Np, 0),
            prep(vram.astype(jnp.int32), Np, 0),
            prep(name_rank.astype(jnp.int32), Np, _BIG_I32),
            prep(slot_perm.astype(jnp.int32), Sp, -1),
            prep(cand_alive.astype(jnp.int32), Sp, 0),
            prep(cnt.astype(jnp.int32), Sp, 0),
            prep(pr_cpu.astype(jnp.int32), SKp, 0),
            prep(pr_ram.astype(jnp.int32), SKp, 0),
            prep(pv0.astype(jnp.int32), SKp, 0),
        )

    meta_spec = pl.BlockSpec((_SUB, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    node_spec = pl.BlockSpec((Np, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    slot_spec = pl.BlockSpec((Sp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    sk_spec = pl.BlockSpec((SKp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.enable_x64(False):
        removed_o = pl.pallas_call(
            functools.partial(_ca_down_kernel, k_sd),
            name="fused_ca_scale_down",
            grid=(Cp // _LANE,),
            in_specs=[meta_spec] + [node_spec] * 7 + [slot_spec] * 3 + [sk_spec] * 3,
            out_specs=slot_spec,
            out_shape=jax.ShapeDtypeStruct((Sp, Cp), jnp.int32),
            scratch_shapes=[
                pltpu.VMEM((Np, _LANE), jnp.int32),
                pltpu.VMEM((Np, _LANE), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT
            ),
            interpret=interpret,
        )(*args)

    with jax.named_scope("kernel_io"):
        return removed_o[:S, :C].T != 0


def ca_up_kernel_fits(n_slots: int, n_groups: int, k_up: int) -> bool:
    """VMEM fits-check for the scale-up kernel: 4 slot tiles (planned out +
    plan_seq/alloc-cpu/alloc-ram scratch), 8 group tiles (7 in + gpl out),
    3 (K_up) candidate tables, and 3 (_SUB x _LANE) meta tiles — the meta
    input, the scal scratch, AND the starved_out output tile (added with
    the reserve-starvation counter) — double-buffered, ~40% headroom."""
    sp_pad = -(-n_slots // _SUB) * _SUB
    gp_pad = -(-n_groups // _SUB) * _SUB
    kp_pad = -(-k_up // _SUB) * _SUB
    resident = (4 * sp_pad + 8 * gp_pad + 3 * kp_pad + 3 * _SUB) * _LANE * 4
    return 2 * resident <= int(0.8 * _VMEM_LIMIT)


def _ca_up_kernel(
    meta_ref,      # (8, LC) int32: row0 ca_max_nodes
    count_ref,     # (Gp, LC) int32 live CA nodes per group
    cursor_ref,    # (Gp, LC) int32 next reserved slot offset per group
    gmax_ref,      # (Gp, LC) int32 group max count (<0 unbounded; pad 0)
    gslots_ref,    # (Gp, LC) int32 reserved slots per group (pad 0)
    tmplc_ref,     # (Gp, LC) int32 template cpu
    tmplr_ref,     # (Gp, LC) int32 template ram
    gstart_ref,    # (Gp, LC) int32 first CA slot of group
    cvalid_ref,    # (Kp, LC) int32 0/1 cache candidate valid (a prefix)
    crc_ref,       # (Kp, LC) int32 candidate req cpu
    crr_ref,       # (Kp, LC) int32 candidate req ram
    planned_out,   # (Sp, LC) int32
    gpl_out,       # (Gp, LC) int32 planned per group
    starved_out,   # (8, LC) int32 row0: reserve-starved open attempts
    seq_ref,       # (Sp, LC) int32 scratch: plan order
    pcpu_ref,      # (Sp, LC) int32 scratch: virtual allocatable cpu
    pram_ref,      # (Sp, LC) int32 scratch: virtual allocatable ram
    scal_ref,      # (8, LC) int32 scratch: row0 total, row1 counter
):
    """First-fit bin-packing scale-up over the name-ordered unscheduled
    cache (reference: kube_cluster_autoscaler.rs:190-240), one in-kernel
    loop instead of the XLA while_loop's K_up sequential (C, S) passes.
    Same decision order as the XLA body: fit into already-planned nodes in
    plan order, else open a node from the FIRST group that accepts the pod
    (min-index over the eligibility mask == lax.argmax over bool); the new
    node joins at FULL template allocatable (the triggering pod is NOT
    packed into it — reference quirk, kube_cluster_autoscaler.rs:210-218)."""
    i0 = jnp.int32(0)
    i1 = jnp.int32(1)
    bigi = jnp.int32(_BIG_I32)

    planned_out[:] = jnp.zeros_like(planned_out)
    gpl_out[:] = jnp.zeros_like(gpl_out)
    starved_out[:] = jnp.zeros_like(starved_out)
    seq_ref[:] = jnp.zeros_like(seq_ref) + bigi
    pcpu_ref[:] = jnp.zeros_like(pcpu_ref)
    pram_ref[:] = jnp.zeros_like(pram_ref)
    scal_ref[:] = jnp.zeros_like(scal_ref)
    # total0 = live CA nodes, ALL groups (max_node_count bounds CA-owned
    # nodes only — reference quirk, kube_cluster_autoscaler.rs:62-80).
    scal_ref[0:1, :] = jnp.sum(count_ref[:], axis=0, keepdims=True)

    max_nodes = meta_ref[0:1, :]
    iota_s = jax.lax.broadcasted_iota(jnp.int32, planned_out.shape, 0)
    iota_g = jax.lax.broadcasted_iota(jnp.int32, gpl_out.shape, 0)

    # Candidates are a per-lane prefix of the name-ordered cache sort, so
    # the deepest lane's count bounds the loop (same as the XLA k_bound).
    k_bound = jnp.max(jnp.sum(cvalid_ref[:], axis=0, keepdims=True))

    def candidate(k):
        row = pl.ds(k, 1)
        valid = cvalid_ref[row, :] != i0  # (1, LC)
        rc = crc_ref[row, :]
        rr = crr_ref[row, :]

        # First-fit into already-planned nodes, in plan (seq) order.
        fit = (
            (planned_out[:] != i0)
            & (rc <= pcpu_ref[:])
            & (rr <= pram_ref[:])
        )
        minseq = jnp.min(jnp.where(fit, seq_ref[:], bigi), axis=0, keepdims=True)
        any_fit = minseq < bigi
        use = valid & any_fit
        place = fit & (seq_ref[:] == minseq) & use
        pcpu_ref[:] = pcpu_ref[:] - jnp.where(place, rc, i0)
        pram_ref[:] = pram_ref[:] - jnp.where(place, rr, i0)

        # Else open a node from the first fitting group. Padding group rows
        # have gslots == 0, so cursor + gpl < gslots excludes them.
        total = scal_ref[0:1, :]
        counter = scal_ref[1:2, :]
        can_open = valid & ~any_fit & (total < max_nodes)
        gcount = count_ref[:] + gpl_out[:]
        # Base eligibility (quota headroom + template fit); g_ok adds the
        # slot-reserve cursor bound — deriving one from the other keeps the
        # starvation counter in lockstep with the open decision (same
        # predicates as the XLA path).
        g_ok_nc = (
            ((gmax_ref[:] < i0) | (gcount < gmax_ref[:]))
            & (rc <= tmplc_ref[:])
            & (rr <= tmplr_ref[:])
        )
        g_ok = g_ok_nc & (cursor_ref[:] + gpl_out[:] < gslots_ref[:])
        first_g = jnp.min(jnp.where(g_ok, iota_g, bigi), axis=0, keepdims=True)
        open_ = can_open & (first_g < bigi)
        # Reserve starvation: a group would accept this pod (with a real
        # reserve, gslots > 0) but its never-reclaimed slot reserve is
        # consumed — the silent-divergence case
        # engine.check_autoscaler_bounds surfaces loudly.
        any_nc = (
            jnp.max(
                jnp.where(g_ok_nc & (gslots_ref[:] > i0), i1, i0),
                axis=0,
                keepdims=True,
            )
            > i0
        )
        starved = can_open & ~(first_g < bigi) & any_nc
        starved_out[0:1, :] = (
            starved_out[0:1, :] + starved.astype(jnp.int32)
        )
        g_oh = (iota_g == first_g) & open_  # (Gp, LC)
        g_ohi = g_oh.astype(jnp.int32)
        s_new = jnp.sum(
            g_ohi * (gstart_ref[:] + cursor_ref[:] + gpl_out[:]),
            axis=0,
            keepdims=True,
        )
        tc = jnp.sum(g_ohi * tmplc_ref[:], axis=0, keepdims=True)
        tr = jnp.sum(g_ohi * tmplr_ref[:], axis=0, keepdims=True)
        s_oh = (iota_s == s_new) & open_  # (Sp, LC)
        planned_out[:] = jnp.where(s_oh, i1, planned_out[:])
        seq_ref[:] = jnp.where(s_oh, counter, seq_ref[:])
        pcpu_ref[:] = jnp.where(s_oh, tc, pcpu_ref[:])
        pram_ref[:] = jnp.where(s_oh, tr, pram_ref[:])
        gpl_out[:] = gpl_out[:] + g_ohi
        opi = open_.astype(jnp.int32)
        scal_ref[0:1, :] = total + opi
        scal_ref[1:2, :] = counter + opi

    def loop_body(k):
        candidate(k)
        return k + i1

    jax.lax.while_loop(lambda k: k < k_bound, loop_body, jnp.int32(0))


@functools.partial(jax.jit, static_argnames=("n_slots", "interpret"))
def fused_ca_scale_up(
    max_nodes: jnp.ndarray,  # (C, 1) int32 global CA node quota
    ca_count: jnp.ndarray,   # (C, Gn) int32
    ca_cursor: jnp.ndarray,  # (C, Gn) int32
    ng_max: jnp.ndarray,     # (C, Gn) int32 (<0 unbounded)
    ng_slots: jnp.ndarray,   # (C, Gn) int32
    ng_tmpl_cpu: jnp.ndarray,  # (C, Gn) int32
    ng_tmpl_ram: jnp.ndarray,  # (C, Gn) int32
    ng_start: jnp.ndarray,   # (C, Gn) int32
    cvalid: jnp.ndarray,     # (C, K) bool/int32
    creq_cpu: jnp.ndarray,   # (C, K) int32
    creq_ram: jnp.ndarray,   # (C, K) int32
    n_slots: int = 0,
    interpret: bool = False,
):
    """Returns (planned (C, S) bool, planned_per_group (C, Gn) int32,
    reserve_starved (C, 1) int32 — open attempts blocked ONLY by the
    consumed slot reserve)."""
    C, Gn = ca_count.shape
    K = cvalid.shape[1]
    S = n_slots
    Cp = -(-C // _LANE) * _LANE
    Sp = -(-S // _SUB) * _SUB
    Gp = -(-Gn // _SUB) * _SUB
    Kp = -(-K // _SUB) * _SUB

    def prep(x, n_sub, fill):
        return _pad_axis(_pad_axis(x.T, 0, n_sub, fill), 1, Cp, fill)

    with jax.named_scope("kernel_io"):
        meta_p = prep(max_nodes.astype(jnp.int32), _SUB, 0)
        args = (
            meta_p,
            prep(ca_count.astype(jnp.int32), Gp, 0),
            prep(ca_cursor.astype(jnp.int32), Gp, 0),
            prep(ng_max.astype(jnp.int32), Gp, 0),
            prep(ng_slots.astype(jnp.int32), Gp, 0),
            prep(ng_tmpl_cpu.astype(jnp.int32), Gp, 0),
            prep(ng_tmpl_ram.astype(jnp.int32), Gp, 0),
            prep(ng_start.astype(jnp.int32), Gp, 0),
            prep(cvalid.astype(jnp.int32), Kp, 0),
            prep(creq_cpu.astype(jnp.int32), Kp, 0),
            prep(creq_ram.astype(jnp.int32), Kp, 0),
        )

    meta_spec = pl.BlockSpec((_SUB, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    slot_spec = pl.BlockSpec((Sp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    group_spec = pl.BlockSpec((Gp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)
    k_spec = pl.BlockSpec((Kp, _LANE), lambda i: (0, i), memory_space=pltpu.VMEM)

    with jax.enable_x64(False):
        planned_o, gpl_o, starved_o = pl.pallas_call(
            _ca_up_kernel,
            name="fused_ca_scale_up",
            grid=(Cp // _LANE,),
            in_specs=[meta_spec] + [group_spec] * 7 + [k_spec] * 3,
            out_specs=[slot_spec, group_spec, meta_spec],
            out_shape=[
                jax.ShapeDtypeStruct((Sp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((Gp, Cp), jnp.int32),
                jax.ShapeDtypeStruct((_SUB, Cp), jnp.int32),
            ],
            scratch_shapes=[
                pltpu.VMEM((Sp, _LANE), jnp.int32),
                pltpu.VMEM((Sp, _LANE), jnp.int32),
                pltpu.VMEM((Sp, _LANE), jnp.int32),
                pltpu.VMEM((_SUB, _LANE), jnp.int32),
            ],
            compiler_params=pltpu.CompilerParams(
                vmem_limit_bytes=_VMEM_LIMIT
            ),
            interpret=interpret,
        )(*args)

    # starved as (C, 1): every output leads with the cluster axis.
    with jax.named_scope("kernel_io"):
        return planned_o[:S, :C].T != 0, gpl_o[:Gn, :C].T, starved_o[0:1, :C].T

