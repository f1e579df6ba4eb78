"""RL environment: the batched simulator driven by a learned scheduler policy.

The policy replaces the KubeScheduler filter/score pass at the same seam the
scalar path exposes via PodSchedulingAlgorithm (reference:
src/core/scheduler/interface.rs:14-23): per pending pod, node logits over the
cluster's nodes, action-masked to Fit-feasible nodes. Everything else — trace
events, queues, finishes, delays, metrics — is the unmodified batched step, so
the policy trains against exactly the simulated control-plane dynamics.

A rollout scans scheduling windows on-device, recording per-decision
transitions (features, action, log-prob, value, reward) for PPO.
"""

from __future__ import annotations

from functools import partial
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp

from kubernetriks_tpu.batched.state import ClusterBatchState, StepConstants, TraceSlab
from kubernetriks_tpu.batched.step import (
    _apply_window_events,
    commit_cycle,
    cycle_timing,
    decision_metrics,
    prepare_cycle,
)

INF = jnp.inf


class Transition(NamedTuple):
    """One scheduling decision per (cluster,) slice; stacked over (W, K)."""

    obs: jnp.ndarray  # (..., C, N, F) node features
    action: jnp.ndarray  # (..., C) chosen node (or argmax'd park)
    log_prob: jnp.ndarray  # (..., C)
    value: jnp.ndarray  # (..., C)
    reward: jnp.ndarray  # (..., C)
    valid: jnp.ndarray  # (..., C) decision actually happened


def featurize(
    alive, alloc_cpu, alloc_ram, cap_cpu, cap_ram, req_cpu, req_ram
) -> jnp.ndarray:
    """Per-node features for one pending pod: (C, N, F). The action mask's
    feasibility channel is the scheduler pipeline's Fit device plugin
    (batched/pipeline.py) — the policy's action space and the
    kube-scheduler's filter chain agree on what "fits" means."""
    from kubernetriks_tpu.batched.pipeline import profile_fit_mask, DEFAULT_PROFILE

    cap_cpu_f = jnp.maximum(cap_cpu.astype(jnp.float32), 1.0)
    cap_ram_f = jnp.maximum(cap_ram.astype(jnp.float32), 1.0)
    fits = profile_fit_mask(
        DEFAULT_PROFILE, alive, alloc_cpu, alloc_ram,
        req_cpu[:, None], req_ram[:, None],
    )
    return jnp.stack(
        [
            alive.astype(jnp.float32),
            fits.astype(jnp.float32),
            alloc_cpu.astype(jnp.float32) / cap_cpu_f,
            alloc_ram.astype(jnp.float32) / cap_ram_f,
            req_cpu.astype(jnp.float32)[:, None] / cap_cpu_f,
            req_ram.astype(jnp.float32)[:, None] / cap_ram_f,
        ],
        axis=-1,
    )


def policy_cycle(
    state: ClusterBatchState,
    W: jnp.ndarray,
    consts: StepConstants,
    K: int,
    policy_apply,
    params,
    rng: jnp.ndarray,
    greedy: bool = False,
    conditional_move: bool = False,
    reward_size_weighted: bool = False,
    shaping_coef: float = 0.0,
    shaping_gamma: float = 0.99,
    wake=None,
) -> Tuple[ClusterBatchState, Transition]:
    """One scheduling cycle (at window index W) where the policy picks nodes;
    returns the K per-cluster transitions. Action space = nodes, masked to
    Fit-feasible ones; no feasible node -> the pod parks unschedulable (like
    the Fit filter).

    Reward options (defaults preserve the plain +1/-1 reward):
    - reward_size_weighted: placements/parks pay req_cpu/node_cap instead of
      1 — capacity-weighted throughput, so stranding a full-node pod costs
      what a full node's worth of small pods earns.
    - shaping_coef (alpha): reward shaping F = gamma*phi(s') - phi(s) with
      phi = alpha * (count of whole-free alive nodes), applied per decision.
      Fragmenting a pristine node is charged AT the decision that fragments
      it instead of hundreds of decisions later when a large pod parks — the
      credit horizon collapses from O(rollout) to O(1). NOTE: this is
      potential-based (Ng/Harada/Russell 1999) only over the decision
      subsequence; phi changes caused by environment transitions between
      windows (pod finishes re-emptying nodes, CA scale-ups) carry no
      compensating term, so a small bias against fragmenting pristine nodes
      remains even where the trace would make it free. Measured on the
      bimodal proof scenario this bias points toward the true optimum
      (best-fit packing) and the trained greedy policy converges exactly to
      it (scripts/train_rl_proof.py, docs/RL_LEARNING.json)."""
    C, P = state.pods.phase.shape
    N = state.nodes.alive.shape[1]
    rows1 = jnp.arange(C, dtype=jnp.int32)

    cc = prepare_cycle(state, W, consts, K, conditional_move, wake)
    alive = state.nodes.alive

    alive_count = alive.sum(axis=1, dtype=jnp.int32).astype(jnp.float32)
    pod_sched_time = jnp.float32(consts.time_per_node) * alive_count
    # Timing mechanics shared with the kube paths (batched/step.py).
    pod_queue_time_k, start_s_k, park_s_k = cycle_timing(
        cc.valid, cc.waited, pod_sched_time, consts
    )

    def body(carry, xs):
        alloc_cpu, alloc_ram, rng = carry
        valid, req_cpu, req_ram, pod_queue_time = xs

        obs = featurize(
            alive, alloc_cpu, alloc_ram, state.nodes.cap_cpu, state.nodes.cap_ram,
            req_cpu, req_ram,
        )
        fit = obs[..., 1] > 0  # (C, N)
        any_fit = fit.any(axis=1)

        logits, value = policy_apply(params, obs)  # (C, N), (C,)
        # Finite mask value (not -inf): keeps softmax/log_softmax gradients
        # NaN-free while making masked nodes unselectable.
        masked_logits = jnp.where(fit, logits, -1e9)
        # Guard fully-infeasible rows (uniform over nodes; decision is a park).
        safe_logits = jnp.where(
            any_fit[:, None], masked_logits, jnp.zeros_like(masked_logits)
        )
        rng, sub = jax.random.split(rng)
        sampled = jax.random.categorical(sub, safe_logits, axis=-1)
        best = jax.lax.argmax(safe_logits, 1, jnp.int32)
        action = jnp.where(greedy, best, sampled).astype(jnp.int32)
        log_probs = jax.nn.log_softmax(safe_logits, axis=-1)
        log_prob = log_probs[rows1, action]

        assign = valid & any_fit
        park = valid & ~any_fit
        action_c = jnp.clip(action, 0, None)
        whole_free_before = (
            (alive & (alloc_cpu == state.nodes.cap_cpu))
            .sum(axis=1)
            .astype(jnp.float32)
        )
        alloc_cpu = alloc_cpu.at[rows1, action_c].add(jnp.where(assign, -req_cpu, 0))
        alloc_ram = alloc_ram.at[rows1, action_c].add(jnp.where(assign, -req_ram, 0))

        # Reward: placement pays +1 (or its capacity share), an unschedulable
        # park costs the same magnitude, minus a queue-time penalty so the
        # policy learns not to strand future pods.
        if reward_size_weighted:
            cap_at = jnp.maximum(
                state.nodes.cap_cpu[rows1, action_c].astype(jnp.float32), 1.0
            )
            unit = req_cpu.astype(jnp.float32) / cap_at
        else:
            unit = jnp.ones_like(req_cpu, jnp.float32)
        reward = jnp.where(
            assign,
            unit - 0.01 * jnp.minimum(pod_queue_time.astype(jnp.float32), 100.0),
            jnp.where(park, -unit, 0.0),
        )
        if shaping_coef:
            whole_free_after = (
                (alive & (alloc_cpu == state.nodes.cap_cpu))
                .sum(axis=1)
                .astype(jnp.float32)
            )
            # Only valid decisions carry shaping (invalid slots must stay
            # transparent to GAE's masked recursion).
            reward = reward + jnp.where(
                valid,
                shaping_coef
                * (jnp.float32(shaping_gamma) * whole_free_after - whole_free_before),
                0.0,
            )
        transition = Transition(
            obs=obs,
            action=action,
            log_prob=log_prob,
            value=value,
            reward=reward,
            valid=valid,
        )
        outs = (assign, park, action, transition)
        return (alloc_cpu, alloc_ram, rng), outs

    xs = (cc.valid.T, cc.req_cpu.T, cc.req_ram.T, pod_queue_time_k.T)
    (alloc_cpu, alloc_ram, _), outs = jax.lax.scan(
        body,
        (state.nodes.alloc_cpu, state.nodes.alloc_ram, rng),
        xs,
    )
    assign_k, park_k, action_k, transitions = outs
    metrics = decision_metrics(
        state.metrics, assign_k.T, pod_queue_time_k, pod_sched_time
    )
    state = commit_cycle(
        state, cc, W, consts, alloc_cpu, alloc_ram, metrics,
        assign_k.T, park_k.T, action_k.T, start_s_k, park_s_k,
    )
    return state, transitions  # transitions stacked over K on axis 0


@partial(
    jax.jit,
    static_argnames=(
        "policy_apply",
        "max_events_per_window",
        "max_pods_per_cycle",
        "greedy",
        "conditional_move",
        "max_ca_pods_per_cycle",
        "max_pods_per_scale_down",
        "reward_size_weighted",
        "shaping_coef",
        "shaping_gamma",
    ),
)
def rollout(
    state: ClusterBatchState,
    slab: TraceSlab,
    window_idxs: jnp.ndarray,
    consts: StepConstants,
    params,
    rng: jnp.ndarray,
    policy_apply,
    max_events_per_window: int,
    max_pods_per_cycle: int,
    greedy: bool = False,
    conditional_move: bool = False,
    autoscale_statics=None,
    max_ca_pods_per_cycle: int = 64,
    max_pods_per_scale_down: int = 8,
    reward_size_weighted: bool = False,
    shaping_coef: float = 0.0,
    shaping_gamma: float = 0.99,
) -> Tuple[ClusterBatchState, Transition]:
    """Scan scheduling windows (int32 indices) under the policy; transitions
    stacked (W, K, C, ...). With autoscale_statics, the HPA/CA passes run
    after each policy cycle exactly as on the kube-scheduler path, so the
    policy trains against autoscaler-driven dynamics."""

    def body(carry, w):
        st, rng = carry
        rng, sub = jax.random.split(rng)
        w_arr = jnp.broadcast_to(jnp.asarray(w, jnp.int32), st.time.shape)
        st, wake, _ = _apply_window_events(
            st, slab, w_arr, consts, max_events_per_window, conditional_move,
            node_name_rank=(
                autoscale_statics.node_name_rank
                if autoscale_statics is not None else None
            ),
            pod_name_rank=(
                autoscale_statics.pod_name_rank
                if autoscale_statics is not None else None
            ),
        )
        pre_cycle = (
            st.pods.phase,
            st.pods.attempts,
            st.nodes.alloc_cpu,
            st.nodes.alloc_ram,
        )
        st, transition = policy_cycle(
            st, w_arr, consts, max_pods_per_cycle, policy_apply, params, sub,
            greedy=greedy, conditional_move=conditional_move,
            reward_size_weighted=reward_size_weighted,
            shaping_coef=shaping_coef, shaping_gamma=shaping_gamma,
            wake=wake,
        )
        if autoscale_statics is not None:
            from kubernetriks_tpu.batched.autoscale import ca_pass, hpa_pass

            auto = st.auto
            st, auto = hpa_pass(st, auto, autoscale_statics, w_arr, consts)
            st, auto = ca_pass(
                st, auto, autoscale_statics, w_arr, consts,
                max_ca_pods_per_cycle, max_pods_per_scale_down,
                pre=pre_cycle,
                # Reclaim-armed states (ca_alloc present — the accelerator
                # KTPU_RECLAIM default) must stamp allocation indices at
                # scale-up, or the cursor drifts past the ca_alloc>=0
                # prefix and a later compaction under-counts occupancy.
                reclaim=auto.ca_alloc is not None,
            )
            st = st._replace(auto=auto)
        return (st, rng), transition

    (state, _), transitions = jax.lax.scan(
        body, (state, rng), jnp.asarray(window_idxs, jnp.int32)
    )
    return state, transitions


def final_state_value(state: ClusterBatchState, policy_apply, params) -> jnp.ndarray:
    """Critic value of the post-rollout state (zero-request 'no pending pod'
    features), used to bootstrap truncated-rollout GAE."""
    zeros = jnp.zeros(state.nodes.alive.shape[0], jnp.int32)
    obs = featurize(
        state.nodes.alive,
        state.nodes.alloc_cpu,
        state.nodes.alloc_ram,
        state.nodes.cap_cpu,
        state.nodes.cap_ram,
        zeros,
        zeros,
    )
    _, value = policy_apply(params, obs)
    return value
