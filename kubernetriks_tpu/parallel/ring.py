"""Ring attention: sequence-parallel attention over a sharded axis.

The long-context capability of this framework: when the per-cluster node
count is too large for one device (or simply sharded for throughput), the
attention pass of the scheduler policy runs with the node ("sequence") axis
sharded over a mesh axis. Each device holds its own Q/K/V block; K/V blocks
rotate around the ring via `lax.ppermute` while every device folds each
incoming block into a numerically-stable online softmax (the flash-attention
accumulation), so the full N×N attention is computed with O(N/s) memory per
device and only neighbor-to-neighbor ICI traffic — no all-gather ever
materializes the full sequence.

`full_attention` is the single-device reference implementation with the same
masking semantics; `tests/test_parallel.py` asserts the ring path reproduces
it on a virtual mesh.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

# Finite "minus infinity" for masked scores: keeps exp()/max() NaN-free even
# for fully-masked blocks (exp(-1e30) underflows cleanly to 0.0). A plain
# Python float: materializing a jnp scalar at import time would initialize
# the XLA backend, breaking jax.distributed.initialize-before-first-device-op
# (parallel/multihost.py).
_NEG = -1e30


def _accumulate_block(q, k, v, kv_mask, o, m, l, scale):
    """Fold one K/V block into the online-softmax accumulators.

    q: (..., nq, d), k/v: (..., nk, d), kv_mask: broadcastable to
    (..., 1, nk) over the score tensor (..., nq, nk). Accumulators:
    o (..., nq, dv) unnormalized output, m (..., nq) running max,
    l (..., nq) running denominator.
    """
    s = jnp.einsum("...qd,...kd->...qk", q, k) * scale
    s = jnp.where(kv_mask[..., None, :], s, _NEG)
    m_new = jnp.maximum(m, s.max(axis=-1))
    p = jnp.exp(s - m_new[..., None])
    # A fully-masked block leaves m_new == _NEG and would give exp(0) == 1
    # per masked element; zero them explicitly.
    p = jnp.where(kv_mask[..., None, :], p, 0.0)
    alpha = jnp.exp(m - m_new)
    l_new = l * alpha + p.sum(axis=-1)
    o_new = o * alpha[..., None] + jnp.einsum("...qk,...kd->...qd", p, v)
    return o_new, m_new, l_new


def full_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Masked softmax(q k^T / sqrt(d)) v over the full (unsharded) axis.

    kv_mask marks valid keys, shape broadcastable to (..., 1, nk); queries
    with zero valid keys return 0 (no NaN).
    """
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    s = jnp.einsum("...qd,...kd->...qk", q, k) * jnp.float32(scale)
    s = jnp.where(kv_mask[..., None, :], s, _NEG)
    m = s.max(axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    p = jnp.where(kv_mask[..., None, :], p, 0.0)
    l = p.sum(axis=-1, keepdims=True)
    out = jnp.einsum("...qk,...kd->...qd", p, v)
    return out / jnp.maximum(l, 1e-30)


def ring_attention(
    q: jnp.ndarray,
    k: jnp.ndarray,
    v: jnp.ndarray,
    kv_mask: jnp.ndarray,
    axis_name: str,
    scale: Optional[float] = None,
) -> jnp.ndarray:
    """Sequence-parallel attention; call INSIDE shard_map with the sequence
    axis sharded over `axis_name`.

    Per-device shards: q/k/v (..., n_shard, d), kv_mask broadcastable to
    (..., 1, n_shard). Every device computes its local queries' attention
    over ALL keys by rotating the K/V (+mask) shards around the ring once,
    folding each block with the online softmax. Equals `full_attention` on
    the gathered axis up to float32 reassociation (tests pin rtol 1e-5).
    """
    if scale is None:
        scale = 1.0 / float(q.shape[-1]) ** 0.5
    scale = jnp.float32(scale)
    size = jax.lax.psum(1, axis_name)  # static mesh-axis size
    perm = [(j, (j + 1) % size) for j in range(size)]

    # The accumulators are device-varying (each shard computes its own
    # queries' attention), but zeros/full literals trace as unvarying —
    # cast them to q's full varying-axis set (e.g. data AND seq on a 2D+
    # mesh) so the fori_loop carry types match the body's outputs.
    vma = tuple(jax.typeof(q).vma)

    def varying(x):
        return jax.lax.pcast(x, vma, to="varying") if vma else x

    # Accumulator dtype must match what the body's arithmetic produces
    # (float64 when inputs are — the batched subsystem enables x64).
    dt = jnp.result_type(q.dtype, k.dtype, v.dtype, jnp.float32)
    o = varying(jnp.zeros(q.shape[:-1] + (v.shape[-1],), dt))
    m = varying(jnp.full(q.shape[:-1], _NEG, dt))
    l = varying(jnp.zeros(q.shape[:-1], dt))

    def body(_, carry):
        o, m, l, k, v, msk = carry
        o, m, l = _accumulate_block(q, k, v, msk, o, m, l, scale)
        k = jax.lax.ppermute(k, axis_name, perm)
        v = jax.lax.ppermute(v, axis_name, perm)
        msk = jax.lax.ppermute(msk, axis_name, perm)
        return (o, m, l, k, v, msk)

    o, m, l, _, _, _ = jax.lax.fori_loop(
        0, size, body, (o, m, l, k, v, kv_mask)
    )
    return o / jnp.maximum(l[..., None], 1e-30)
