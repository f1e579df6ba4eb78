"""TP/SP parallelism of the policy head: ring attention (sequence-parallel
over the node axis) and the tensor-parallel FFN, checked for parity against
the single-device forward on the suite's 8-device virtual CPU mesh."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, PartitionSpec as P

from kubernetriks_tpu.parallel.ring import full_attention, ring_attention
from kubernetriks_tpu.rl.attention_policy import (
    attention_policy_apply,
    init_attention_policy,
    make_sharded_apply,
)
from kubernetriks_tpu.rl.policy import NODE_FEATURES


def _seq_mesh(n):
    return Mesh(np.array(jax.devices()[:n]), ("seq",))


def _rand_qkv(rng, B, H, N, D):
    ks = jax.random.split(rng, 4)
    q = jax.random.normal(ks[0], (B, H, N, D), jnp.float32)
    k = jax.random.normal(ks[1], (B, H, N, D), jnp.float32)
    v = jax.random.normal(ks[2], (B, H, N, D), jnp.float32)
    mask = jax.random.bernoulli(ks[3], 0.7, (B, 1, N))
    return q, k, v, mask


def test_ring_attention_matches_full_attention():
    q, k, v, mask = _rand_qkv(jax.random.PRNGKey(0), B=3, H=2, N=16, D=8)
    want = full_attention(q, k, v, mask)

    mesh = _seq_mesh(8)
    ring = jax.jit(
        jax.shard_map(
            lambda q, k, v, m: ring_attention(q, k, v, m, "seq"),
            mesh=mesh,
            in_specs=(
                P(None, None, "seq", None),
                P(None, None, "seq", None),
                P(None, None, "seq", None),
                P(None, None, "seq"),
            ),
            out_specs=P(None, None, "seq", None),
        )
    )
    got = ring(q, k, v, mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=1e-5, atol=1e-6)


def test_ring_attention_fully_masked_rows_are_zero():
    q, k, v, mask = _rand_qkv(jax.random.PRNGKey(1), B=2, H=1, N=8, D=4)
    mask = jnp.zeros_like(mask, bool)  # no valid keys anywhere
    want = full_attention(q, k, v, mask)
    assert np.all(np.asarray(want) == 0.0)

    mesh = _seq_mesh(8)
    got = jax.jit(
        jax.shard_map(
            lambda q, k, v, m: ring_attention(q, k, v, m, "seq"),
            mesh=mesh,
            in_specs=(
                P(None, None, "seq", None),
                P(None, None, "seq", None),
                P(None, None, "seq", None),
                P(None, None, "seq"),
            ),
            out_specs=P(None, None, "seq", None),
        )
    )(q, k, v, mask)
    assert np.all(np.isfinite(np.asarray(got)))
    assert np.all(np.asarray(got) == 0.0)


def _rand_feats(rng, C, N):
    ks = jax.random.split(rng, 2)
    feats = jax.random.uniform(ks[0], (C, N, NODE_FEATURES), jnp.float32)
    alive = jax.random.bernoulli(ks[1], 0.8, (C, N)).astype(jnp.float32)
    return feats.at[..., 0].set(alive)


@pytest.mark.parametrize("mesh_shape", [(2, 2, 2), (1, 4, 2), (2, 4, 1)])
def test_sharded_attention_policy_matches_unsharded(mesh_shape):
    """DP x SP x TP forward == plain forward: clusters sharded on `data`,
    node axis on `seq` (ring attention), FFN hidden dim on `model`."""
    d, s, m = mesh_shape
    devices = np.array(jax.devices()[: d * s * m]).reshape(mesh_shape)
    mesh = Mesh(devices, ("data", "seq", "model"))

    params = init_attention_policy(jax.random.PRNGKey(7), hidden=32, heads=4)
    feats = _rand_feats(jax.random.PRNGKey(8), C=4, N=8)

    want_logits, want_value = attention_policy_apply(params, feats)
    apply = make_sharded_apply(mesh)
    got_logits, got_value = apply(params, feats)

    np.testing.assert_allclose(
        np.asarray(got_logits), np.asarray(want_logits), rtol=1e-5, atol=1e-6
    )
    np.testing.assert_allclose(
        np.asarray(got_value), np.asarray(want_value), rtol=1e-5, atol=1e-6
    )


def test_sharded_attention_policy_gradients_match():
    """Training through the sharded forward: d(loss)/d(params) computed
    through shard_map (ring attention + TP psums) matches the unsharded
    gradient — the guarantee that TP/SP training is the same optimization
    problem, not just the same inference."""
    devices = np.array(jax.devices()[:8]).reshape(2, 2, 2)
    mesh = Mesh(devices, ("data", "seq", "model"))
    params = init_attention_policy(jax.random.PRNGKey(3), hidden=32, heads=4)
    feats = _rand_feats(jax.random.PRNGKey(4), C=4, N=8)
    sharded_apply = make_sharded_apply(mesh)

    def loss(apply):
        def f(p):
            logits, value = apply(p, feats)
            return (jnp.tanh(logits).sum() + (value**2).sum()).astype(jnp.float32)
        return f

    g_ref = jax.grad(loss(attention_policy_apply))(params)
    g_sh = jax.grad(loss(sharded_apply))(params)
    # Tolerances: in float64 the two gradients agree to ~1e-10 relative
    # (mathematically the same function); in float32 the online-softmax
    # backward reassociates, leaving ~1e-6-absolute noise that is large
    # RELATIVE only on near-zero elements — hence the atol floor.
    for k in g_ref:
        np.testing.assert_allclose(
            np.asarray(g_sh[k]), np.asarray(g_ref[k]),
            rtol=5e-3, atol=5e-6, err_msg=k,
        )


def test_ppo_trains_attention_policy():
    """The attention policy drops into the PPO trainer at the same seam as
    the MLP head and one iteration produces finite losses + decisions."""
    from kubernetriks_tpu.batched.engine import build_batched_from_traces
    from kubernetriks_tpu.config import SimulationConfig
    from kubernetriks_tpu.rl.ppo import PPOConfig, PPOTrainer
    from kubernetriks_tpu.trace.generator import (
        PoissonWorkloadTrace,
        UniformClusterTrace,
    )

    config = SimulationConfig.from_yaml(
        "sim_name: attn_rl\nseed: 1\nscheduling_cycle_interval: 10.0"
    )
    cluster = UniformClusterTrace(8, cpu=16000, ram=32 * 1024**3)
    workload = PoissonWorkloadTrace(
        rate_per_second=0.5, horizon=100.0, seed=5, cpu=2000,
        ram=4 * 1024**3, duration_range=(20.0, 60.0),
    )
    sim = build_batched_from_traces(
        config,
        cluster.convert_to_simulator_events(),
        workload.convert_to_simulator_events(),
        n_clusters=4,
        max_pods_per_cycle=8,
    )
    trainer = PPOTrainer(
        sim,
        windows_per_rollout=4,
        config=PPOConfig(epochs_per_iteration=1),
        hidden=32,
        policy_kind="attention",
    )
    result = trainer.train_iteration()
    assert np.isfinite(result["policy_loss"])
    assert result["decisions"] > 0
    assert result["placements"] > 0
