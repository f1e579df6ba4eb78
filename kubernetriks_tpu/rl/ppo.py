"""PPO trainer for the scheduler policy over batches of simulated clusters.

Data parallelism follows the simulator's: the cluster axis C is the batch axis
(shardable over a mesh; policy params replicated, XLA inserts the gradient
all-reduce). Each PPO iteration: reset the cluster batch, roll W windows x K
decisions under the current policy, compute GAE over the flattened decision
sequence per cluster, and take clipped-objective gradient steps.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from kubernetriks_tpu.batched.engine import BatchedSimulation
from kubernetriks_tpu.rl.env import Transition, rollout
from kubernetriks_tpu.rl.policy import init_policy


class PPOConfig(NamedTuple):
    gamma: float = 0.99
    gae_lambda: float = 0.95
    clip_eps: float = 0.2
    value_coef: float = 0.5
    entropy_coef: float = 0.01
    learning_rate: float = 3e-4
    epochs_per_iteration: int = 4
    # Gradient accumulation over cluster chunks of this size (0 = whole
    # batch in one backward). The chunks ride a lax.scan, so the compiled
    # program carries ONE chunk-sized backward regardless of C — how the
    # attention policy's update (a much larger XLA program than the MLP's)
    # fits the 8192-cluster tracked config. Chunk losses are combined with the FULL batch's
    # normalization (global advantage mean/std, global valid count), so the
    # accumulated gradient equals the monolithic one up to fp reduction
    # order.
    update_microbatch: int = 0
    # Rollout reward options (see rl/env.py policy_cycle): capacity-weighted
    # placement rewards and potential-based fragmentation shaping.
    reward_size_weighted: bool = False
    shaping_coef: float = 0.0


def compute_gae(
    rewards: jnp.ndarray,  # (T, C)
    values: jnp.ndarray,  # (T, C)
    valid: jnp.ndarray,  # (T, C)
    gamma: float,
    lam: float,
    bootstrap_value: Optional[jnp.ndarray] = None,  # (C,) V(s_final)
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Masked generalized advantage estimation over the decision sequence.

    Rollouts are horizon-truncated, not terminal: bootstrap_value (the critic's
    value of the post-rollout state) seeds the backward recursion so tail
    decisions are not biased as if the episode ended."""
    if bootstrap_value is None:
        bootstrap_value = jnp.zeros_like(values[-1])

    def body(carry, xs):
        next_adv, next_value = carry
        reward, value, is_valid = xs
        delta = reward + gamma * next_value - value
        adv = delta + gamma * lam * next_adv
        # Invalid steps are transparent: they pass the carry through unchanged.
        adv = jnp.where(is_valid, adv, next_adv)
        value_out = jnp.where(is_valid, value, next_value)
        return (adv, value_out), adv

    (_, _), advantages = jax.lax.scan(
        body,
        (jnp.zeros_like(values[-1]), bootstrap_value),
        (rewards, values, valid),
        reverse=True,
    )
    returns = advantages + values
    return advantages, returns


def ppo_loss(
    params,
    policy_apply,
    transition: Transition,  # flattened (T, C, ...)
    advantages: jnp.ndarray,
    returns: jnp.ndarray,
    config: PPOConfig,
    denom: Optional[jnp.ndarray] = None,
):
    """Clipped PPO objective. With denom=None (the monolithic path) the
    advantages are normalized and the loss averaged over this batch's valid
    decisions; a microbatch caller passes the FULL batch's valid count as
    denom and pre-normalized advantages, so summing chunk losses reproduces
    the monolithic objective."""
    logits, values = policy_apply(params, transition.obs)  # (T, C, N), (T, C)
    fit = transition.obs[..., 1] > 0
    # Finite mask value (not -inf): -inf produces NaN gradients through the
    # entropy term (d(p*log p) at log p = -inf is 0 * NaN).
    masked = jnp.where(fit, logits, -1e9)
    any_fit = fit.any(axis=-1, keepdims=True)
    safe = jnp.where(any_fit, masked, jnp.zeros_like(masked))
    log_probs = jax.nn.log_softmax(safe, axis=-1)
    action_log_prob = jnp.take_along_axis(
        log_probs, transition.action[..., None], axis=-1
    )[..., 0]

    mask = transition.valid.astype(jnp.float32)
    adv = advantages
    if denom is None:
        denom = jnp.maximum(mask.sum(), 1.0)
        adv_mean = (adv * mask).sum() / denom
        adv_std = jnp.sqrt(((adv - adv_mean) ** 2 * mask).sum() / denom + 1e-8)
        adv = (adv - adv_mean) / adv_std

    ratio = jnp.exp(action_log_prob - transition.log_prob)
    clipped = jnp.clip(ratio, 1.0 - config.clip_eps, 1.0 + config.clip_eps)
    policy_loss = -(jnp.minimum(ratio * adv, clipped * adv) * mask).sum() / denom

    value_loss = (((values - returns) ** 2) * mask).sum() / denom

    # Double-where: clamp BEFORE the product so backward never sees 0 * inf.
    lp_safe = jnp.where(fit, log_probs, 0.0)
    p_safe = jnp.where(fit, jnp.exp(log_probs), 0.0)
    entropy = -((p_safe * lp_safe).sum(axis=-1) * mask).sum() / denom

    total = (
        policy_loss
        + config.value_coef * value_loss
        - config.entropy_coef * entropy
    )
    return total, {
        "policy_loss": policy_loss,
        "value_loss": value_loss,
        "entropy": entropy,
    }


@partial(jax.jit, static_argnames=("policy_apply", "optimizer", "config"))
def ppo_update(
    params,
    opt_state,
    policy_apply,
    optimizer,
    transition: Transition,
    advantages,
    returns,
    config: PPOConfig,
):
    if config.update_microbatch:
        return _ppo_update_accum(
            params, opt_state, policy_apply, optimizer,
            transition, advantages, returns, config,
        )
    grad_fn = jax.value_and_grad(ppo_loss, has_aux=True)
    (loss, aux), grads = grad_fn(
        params, policy_apply, transition, advantages, returns, config
    )
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss, aux


def _ppo_update_accum(
    params,
    opt_state,
    policy_apply,
    optimizer,
    transition: Transition,
    advantages,
    returns,
    config: PPOConfig,
):
    """One optimizer step whose gradient accumulates over cluster chunks via
    lax.scan: the program holds a single chunk-sized backward, so arbitrary
    C fits a bounded compile budget (BASELINE config 5: attention-policy PPO
    at 8192 clusters)."""
    C = advantages.shape[1]
    Cc = min(config.update_microbatch, C)
    assert C % Cc == 0, (
        f"update_microbatch={Cc} must divide the cluster batch ({C})"
    )
    n_chunks = C // Cc

    # Global normalization BEFORE chunking, so chunk losses summed with the
    # global denom reproduce the monolithic objective.
    mask = transition.valid.astype(jnp.float32)
    denom = jnp.maximum(mask.sum(), 1.0)
    adv_mean = (advantages * mask).sum() / denom
    adv_std = jnp.sqrt(
        ((advantages - adv_mean) ** 2 * mask).sum() / denom + 1e-8
    )
    adv = (advantages - adv_mean) / adv_std

    def chunked(x):
        # (T, C, ...) -> (n_chunks, T, Cc, ...)
        return jnp.swapaxes(
            x.reshape(x.shape[0], n_chunks, Cc, *x.shape[2:]), 0, 1
        )

    xs = (jax.tree.map(chunked, transition), chunked(adv), chunked(returns))
    grad_fn = jax.value_and_grad(ppo_loss, has_aux=True)

    def body(acc, x):
        tr_c, adv_c, ret_c = x
        (loss_c, aux_c), grads_c = grad_fn(
            params, policy_apply, tr_c, adv_c, ret_c, config, denom
        )
        grads, loss, aux = acc
        return (
            jax.tree.map(jnp.add, grads, grads_c),
            loss + loss_c,
            jax.tree.map(jnp.add, aux, aux_c),
        ), None

    zero_grads = jax.tree.map(jnp.zeros_like, params)
    zero_aux = {
        "policy_loss": jnp.float32(0.0),
        "value_loss": jnp.float32(0.0),
        "entropy": jnp.float32(0.0),
    }
    (grads, loss, aux), _ = jax.lax.scan(
        body, (zero_grads, jnp.float32(0.0), zero_aux), xs
    )
    updates, opt_state = optimizer.update(grads, opt_state, params)
    params = optax.apply_updates(params, updates)
    return params, opt_state, loss, aux


class PPOTrainer:
    """Owns the policy/optimizer and iterates rollout -> GAE -> updates against
    a fresh copy of a BatchedSimulation's initial state each iteration."""

    def __init__(
        self,
        sim: BatchedSimulation,
        windows_per_rollout: int = 16,
        config: PPOConfig = PPOConfig(),
        hidden: int = 64,
        seed: int = 0,
        policy_kind: str = "mlp",
    ) -> None:
        self.sim = sim
        self.config = config
        self.windows = np.arange(windows_per_rollout, dtype=np.int32)
        rng = jax.random.PRNGKey(seed)
        self.rng, init_rng = jax.random.split(rng)
        n_nodes = sim.state.nodes.alive.shape[1]
        if policy_kind == "attention":
            from kubernetriks_tpu.rl.attention_policy import (
                attention_policy_apply,
                init_attention_policy,
            )

            self.policy = None
            self.params = init_attention_policy(init_rng, hidden=hidden)
            self.policy_apply = attention_policy_apply
        else:
            assert policy_kind == "mlp", policy_kind
            self.policy, self.params = init_policy(
                init_rng, n_nodes, hidden=hidden
            )
            self.policy_apply = self.policy.apply
        self.optimizer = optax.adam(config.learning_rate)
        self.opt_state = self.optimizer.init(self.params)
        self.initial_state = sim.state

    def save_checkpoint(self, path: str) -> None:
        """Persist policy params, optimizer state and the rollout RNG (the
        simulator side is re-derivable from config+traces; checkpoint it
        separately via BatchedSimulation.save_checkpoint if mid-rollout
        state matters)."""
        from kubernetriks_tpu.checkpoint import ckpt_save

        ckpt_save(
            path,
            {"params": self.params, "opt_state": self.opt_state, "rng": self.rng},
        )

    def load_checkpoint(self, path: str) -> None:
        from kubernetriks_tpu.checkpoint import ckpt_restore

        restored = ckpt_restore(
            path,
            {"params": self.params, "opt_state": self.opt_state, "rng": self.rng},
        )
        self.params = restored["params"]
        self.opt_state = restored["opt_state"]
        self.rng = restored["rng"]

    def collect(self, greedy: bool = False):
        self.rng, sub = jax.random.split(self.rng)
        final_state, transitions = rollout(
            self.initial_state,
            self.sim.slab,
            jnp.asarray(self.windows, jnp.int32),
            self.sim.consts,
            self.params,
            sub,
            self.policy_apply,
            self.sim.max_events_per_window,
            self.sim.max_pods_per_cycle,
            greedy=greedy,
            conditional_move=self.sim.conditional_move,
            autoscale_statics=self.sim.autoscale_statics,
            max_ca_pods_per_cycle=self.sim.max_ca_pods_per_cycle,
            max_pods_per_scale_down=self.sim.max_pods_per_scale_down,
            reward_size_weighted=self.config.reward_size_weighted,
            shaping_coef=self.config.shaping_coef,
            shaping_gamma=self.config.gamma,
        )
        # (W, K, C, ...) -> (W*K, C, ...) decision-ordered sequence.
        flat = jax.tree.map(
            lambda x: x.reshape((-1,) + x.shape[2:]), transitions
        )
        return final_state, flat

    def train_iteration(self) -> Dict[str, float]:
        from kubernetriks_tpu.rl.env import final_state_value

        final_state, flat = self.collect()
        bootstrap = final_state_value(final_state, self.policy_apply, self.params)
        advantages, returns = compute_gae(
            flat.reward, flat.value, flat.valid,
            self.config.gamma, self.config.gae_lambda,
            bootstrap_value=bootstrap,
        )
        aux = {}
        for _ in range(self.config.epochs_per_iteration):
            self.params, self.opt_state, loss, aux = ppo_update(
                self.params,
                self.opt_state,
                self.policy_apply,
                self.optimizer,
                flat,
                advantages,
                returns,
                self.config,
            )
        mask = np.asarray(flat.valid, np.float32)
        denom = max(mask.sum(), 1.0)
        result = {k: float(v) for k, v in aux.items()}
        result["mean_reward"] = float((np.asarray(flat.reward) * mask).sum() / denom)
        result["decisions"] = int(mask.sum())
        result["placements"] = int(
            np.asarray(final_state.metrics.scheduling_decisions).sum()
            - np.asarray(self.initial_state.metrics.scheduling_decisions).sum()
        )
        return result

    def train(self, iterations: int):
        history = []
        for _ in range(iterations):
            history.append(self.train_iteration())
        return history
