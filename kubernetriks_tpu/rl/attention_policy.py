"""Attention-based scheduler policy with explicit TP/SP sharding.

Same seam as rl/policy.py's MLP head (the PodSchedulingAlgorithm boundary,
reference: src/core/scheduler/interface.rs:14-23): per pending pod, node
logits over the cluster's nodes plus a pooled value. The difference is a
self-attention block over the node axis, so each node's logit can condition
on the whole cluster's occupancy (the MLP scores nodes independently) — and
that node axis is exactly the "sequence" this framework shards for
long-context clusters.

Two applies over the SAME parameter pytree:
- `attention_policy_apply(params, feats)` — plain single-device forward
  (usable anywhere `policy_apply` is, e.g. PPOTrainer(policy_kind=...)).
- `make_sharded_apply(mesh, ...)` — a shard_map'd forward over a
  (data, seq, model) mesh: clusters data-parallel, node axis
  sequence-parallel through ring attention (parallel/ring.py), and the FFN
  hidden dimension megatron-style tensor-parallel (column-split W1, row-split
  W2, psum over the model axis). Parity with the plain forward is asserted in
  tests/test_parallel.py.

Pure functions + an explicit param dict (no flax) so the sharded forward can
consume the pytree directly through shard_map in_specs.
"""

from __future__ import annotations

from functools import partial
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from kubernetriks_tpu.parallel.ring import full_attention, ring_attention
from kubernetriks_tpu.rl.policy import NODE_FEATURES


def init_attention_policy(
    rng,
    hidden: int = 64,
    heads: int = 4,
    ffn_mult: int = 2,
    features: int = NODE_FEATURES,
) -> Dict[str, jnp.ndarray]:
    """He-initialized parameter pytree. hidden must divide by heads; the FFN
    hidden (ffn_mult*hidden) is the tensor-parallel dimension and must divide
    by the mesh's model-axis size when used with make_sharded_apply."""
    assert hidden % heads == 0
    ffn = ffn_mult * hidden

    def dense(key, fan_in, fan_out):
        w = jax.random.normal(key, (fan_in, fan_out), jnp.float32)
        return w * jnp.sqrt(2.0 / fan_in)

    ks = jax.random.split(rng, 10)
    return {
        "embed_w": dense(ks[0], features, hidden),
        "embed_b": jnp.zeros((hidden,), jnp.float32),
        "q_w": dense(ks[1], hidden, hidden),
        "k_w": dense(ks[2], hidden, hidden),
        "v_w": dense(ks[3], hidden, hidden),
        "proj_w": dense(ks[4], hidden, hidden),
        "proj_b": jnp.zeros((hidden,), jnp.float32),
        "ffn1_w": dense(ks[5], hidden, ffn),
        "ffn1_b": jnp.zeros((ffn,), jnp.float32),
        "ffn2_w": dense(ks[6], ffn, hidden),
        "ffn2_b": jnp.zeros((hidden,), jnp.float32),
        "logit_w": dense(ks[7], hidden, 1),
        "logit_b": jnp.zeros((1,), jnp.float32),
        "val1_w": dense(ks[8], hidden, hidden),
        "val1_b": jnp.zeros((hidden,), jnp.float32),
        "val2_w": dense(ks[9], hidden, 1),
        "val2_b": jnp.zeros((1,), jnp.float32),
    }


def _heads(x: jnp.ndarray, heads: int) -> jnp.ndarray:
    """(..., N, H*dh) -> (..., H, N, dh)."""
    *lead, n, d = x.shape
    x = x.reshape(*lead, n, heads, d // heads)
    return jnp.moveaxis(x, -2, -3)


def _unheads(x: jnp.ndarray) -> jnp.ndarray:
    """(..., H, N, dh) -> (..., N, H*dh)."""
    x = jnp.moveaxis(x, -3, -2)
    *lead, n, h, dh = x.shape
    return x.reshape(*lead, n, h * dh)


def _trunk_local(params, feats, attn_fn, heads: int):
    """Shared forward up to per-node embeddings; attn_fn supplies either the
    full or the ring attention over (..., H, N, dh) blocks."""
    alive = feats[..., 0] > 0  # (..., N)
    x = jax.nn.relu(feats @ params["embed_w"] + params["embed_b"])
    qh = _heads(x @ params["q_w"], heads)
    kh = _heads(x @ params["k_w"], heads)
    vh = _heads(x @ params["v_w"], heads)
    mask = alive[..., None, :]  # broadcast over heads then queries
    attn = _unheads(attn_fn(qh, kh, vh, mask))
    x = x + attn @ params["proj_w"] + params["proj_b"]
    return x, alive


def _head_outputs(params, x, alive):
    """Per-node logits + masked-mean pooled value from trunk embeddings."""
    x = jnp.where(alive[..., None], x, 0.0)
    logits = (x @ params["logit_w"] + params["logit_b"])[..., 0]
    count = jnp.maximum(alive.sum(axis=-1, keepdims=True), 1.0)
    pooled = x.sum(axis=-2) / count
    v = jax.nn.relu(pooled @ params["val1_w"] + params["val1_b"])
    value = (v @ params["val2_w"] + params["val2_b"])[..., 0]
    return logits, value


def attention_policy_apply(
    params, feats: jnp.ndarray, heads: int = 4
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """(..., N, F) node features -> ((..., N) logits, (...,) value)."""
    x, alive = _trunk_local(params, feats, full_attention, heads)
    h = jax.nn.relu(x @ params["ffn1_w"] + params["ffn1_b"])
    x = x + h @ params["ffn2_w"] + params["ffn2_b"]
    return _head_outputs(params, x, alive)


def make_sharded_apply(
    mesh: Mesh,
    heads: int = 4,
    data_axis: str = "data",
    seq_axis: str = "seq",
    model_axis: str = "model",
):
    """Build apply(params, feats) for feats (C, N, F) with C sharded over
    data_axis, N over seq_axis (ring attention) and the FFN hidden dimension
    over model_axis (column/row-parallel matmuls + psum). Params enter
    replicated except the FFN weights, which shard_map slices per device.
    C, N and the FFN hidden must divide by the respective mesh axis sizes."""

    ffn_spec = {
        "ffn1_w": P(None, model_axis),
        "ffn1_b": P(model_axis),
        "ffn2_w": P(model_axis, None),
    }

    def spec_for(key):
        return ffn_spec.get(key, P())

    def fwd(params, feats):
        def ring(qh, kh, vh, mask):
            return ring_attention(qh, kh, vh, mask, seq_axis)

        x, alive = _trunk_local(params, feats, ring, heads)

        # Tensor-parallel FFN: column-split first matmul, row-split second,
        # one psum over the model axis restores the full activation.
        h = jax.nn.relu(x @ params["ffn1_w"] + params["ffn1_b"])
        y = jax.lax.psum(h @ params["ffn2_w"], model_axis)
        x = x + y + params["ffn2_b"]

        # Heads: logits stay node-sharded; the pooled value needs the masked
        # mean over ALL nodes -> psum the local sums over the sequence axis.
        x = jnp.where(alive[..., None], x, 0.0)
        logits = (x @ params["logit_w"] + params["logit_b"])[..., 0]
        count = jax.lax.psum(
            alive.sum(axis=-1, keepdims=True).astype(jnp.float32), seq_axis
        )
        pooled = jax.lax.psum(x.sum(axis=-2), seq_axis) / jnp.maximum(count, 1.0)
        v = jax.nn.relu(pooled @ params["val1_w"] + params["val1_b"])
        value = (v @ params["val2_w"] + params["val2_b"])[..., 0]
        return logits, value

    in_specs = (
        {k: spec_for(k) for k in (
            "embed_w", "embed_b", "q_w", "k_w", "v_w", "proj_w", "proj_b",
            "ffn1_w", "ffn1_b", "ffn2_w", "ffn2_b", "logit_w", "logit_b",
            "val1_w", "val1_b", "val2_w", "val2_b",
        )},
        P(data_axis, seq_axis, None),
    )
    out_specs = (P(data_axis, seq_axis), P(data_axis))

    return jax.jit(
        jax.shard_map(
            fwd, mesh=mesh, in_specs=in_specs, out_specs=out_specs
        )
    )
