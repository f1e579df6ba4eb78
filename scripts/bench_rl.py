"""PPO scheduler-policy benchmark (BASELINE.md tracked config 5: PPO policy
over 8192 clusters).

Phase 1: one full PPO iteration (rollout -> GAE -> clipped updates) over
8192 simulated 8-node clusters on the attached chip; reports wall-clock and
decision throughput.
Phase 2: 10 training iterations at a smaller batch on a contended workload;
reports the mean-reward trajectory to demonstrate learning.

Prints one JSON line per phase.
Usage: python scripts/bench_rl.py [n_clusters] [--skip-learning] [--attention]

--attention benches the attention policy head (rl/attention_policy.py)
instead of the MLP. Its PPO update is a much larger XLA program (self-
attention backward over the (T*C, N) batch) whose padded intermediates
exceeded one chip's compile/memory budget above ~2048 clusters,
so above that the update runs with gradient accumulation over <=1024-cluster
chunks (PPOConfig.update_microbatch: one chunk-sized backward in a lax.scan,
bounded program size and HBM at any C, same gradient up to fp reduction
order).
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import numpy as np


def build(n_clusters, n_nodes=8, rate=0.5, horizon=200.0, seed=7):
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        "sim_name: rl_bench\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(n_nodes, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=rate,
        horizon=horizon,
        seed=seed,
        cpu=4000,
        ram=8 * 1024**3,
        duration_range=(20.0, 60.0),
    )
    return build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=n_clusters,
        max_pods_per_cycle=8,
    )


def build_binpack(n_clusters, seed=13):
    """4 big (16-core) + 8 small (4-core) nodes; mostly 4-core pods with
    16-core pods mixed in. A 16-core pod needs an EMPTY big node, so every
    small pod routed onto a big node can park a later big pod; aggregate
    demand fits iff small pods stay on small nodes — a policy that learns the
    routing parks (almost) nothing, a random one pays -1 per parked cycle."""
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.core.events import CreateNodeRequest, CreatePodRequest
    from kubernetriks_tpu.core.types import Node, Pod

    config = SimulationConfig.from_yaml(
        "sim_name: rl_binpack\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    GiB = 1024**3
    cluster_events = []
    for i in range(4):
        cluster_events.append(
            (0.0, CreateNodeRequest(node=Node.new(f"big_{i}", 16000, 32 * GiB)))
        )
    for i in range(8):
        cluster_events.append(
            (0.0, CreateNodeRequest(node=Node.new(f"small_{i}", 4000, 8 * GiB)))
        )

    rng = np.random.default_rng(seed)
    workload_events = []
    t = 1.0
    for i in range(120):
        big = rng.random() < 0.15
        cpu = 16000 if big else 4000
        ram = (32 if big else 8) * GiB
        workload_events.append(
            (t, CreatePodRequest(pod=Pod.new(f"pod_{i:03d}", cpu, ram, 60.0)))
        )
        t += float(rng.uniform(1.5, 3.5))
    return build_batched_from_traces(
        config, cluster_events, workload_events,
        n_clusters=n_clusters, max_pods_per_cycle=8,
    )


def main(n_clusters=8192, skip_learning=False, policy_kind="mlp") -> None:
    from kubernetriks_tpu.rl.ppo import PPOConfig, PPOTrainer

    # --- phase 1: one iteration at scale ------------------------------------
    sim = build(n_clusters)
    # Attention updates above 2048 clusters: chunk the backward (see module
    # docstring). 1024 keeps the backward's padded attention intermediates
    # ((T, Cc, heads, dim) tiles at 8-16x lane-padding expansion) well under
    # the v5e's 16G HBM; the chunk must divide the batch, so take the
    # largest divisor <= 1024.
    microbatch = 0
    if policy_kind == "attention" and n_clusters > 2048:
        microbatch = max(d for d in range(1, 1025) if n_clusters % d == 0)
    trainer = PPOTrainer(
        sim, windows_per_rollout=16,
        config=PPOConfig(epochs_per_iteration=4, update_microbatch=microbatch),
        policy_kind=policy_kind,
    )
    warm = trainer.train_iteration()  # compile
    t0 = time.perf_counter()
    result = trainer.train_iteration()
    elapsed = time.perf_counter() - t0
    print(
        json.dumps(
            {
                "metric": f"PPO iteration ({policy_kind} policy), {n_clusters}x8-node clusters, 16 windows x 8 decisions",
                "value": round(elapsed, 2),
                "unit": "s/iteration",
                "decisions_per_s": round(result["decisions"] / elapsed),
                "placements": result["placements"],
                "policy_loss": round(float(result["policy_loss"]), 4),
            }
        )
    )
    if skip_learning:
        return

    # --- phase 2: learning curve on a bin-packing-sensitive workload --------
    # Heterogeneous nodes + pod sizes: small pods fit everywhere, big pods
    # only fit big nodes. A policy that routes small pods onto small nodes
    # keeps big nodes free and avoids parking big pods (-1 reward each);
    # LeastAllocated-style spreading strands capacity. Homogeneous scenarios
    # are reward-flat (any feasible node is equivalent), so this shape is
    # what makes the learning signal non-trivial.
    sim2 = build_binpack(512)
    trainer2 = PPOTrainer(
        sim2,
        windows_per_rollout=32,
        config=PPOConfig(epochs_per_iteration=4, learning_rate=3e-3),
    )
    rewards = []
    for _ in range(10):
        out = trainer2.train_iteration()
        rewards.append(round(float(out["mean_reward"]), 4))
    print(
        json.dumps(
            {
                "metric": "PPO mean reward over 10 iterations (512 clusters, bin-packing)",
                "value": rewards[-1],
                "unit": "reward",
                "trajectory": rewards,
                "improved": bool(
                    np.mean(rewards[-3:]) > np.mean(rewards[:3])
                ),
            }
        )
    )


if __name__ == "__main__":
    n = int(sys.argv[1]) if len(sys.argv) > 1 and sys.argv[1].isdigit() else 8192
    main(
        n,
        skip_learning="--skip-learning" in sys.argv,
        policy_kind="attention" if "--attention" in sys.argv else "mlp",
    )
