"""The traced pod-window slide (step._slide_apply_traced) as a block move.

The shift `s` and the payload base are one scalar each for the whole batch,
so the slide is a `dynamic_slice` a plane and no gather (PR 42). Held here:

(a) leaf for leaf it equals engine._slide_apply_device, the static
    slice-and-concatenate, over every kind of shift the quantizer can hand
    it, with and without a resident tail and carried name ranks, and with
    `base` at the LAST column the callers' guards allow: a `dynamic_slice`
    whose start would run off the end is moved, silently, where the gather it
    replaces clipped an index at a time;
(b) it lowers no `stablehlo.gather`;
(c) through run_superspan on a mesh of four the slides leave the state the
    one-device run leaves.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import _slide_apply_device
from kubernetriks_tpu.batched.state import fresh_pod_arrays
from kubernetriks_tpu.batched.step import (
    _quantize_shift_device,
    _slide_apply_traced,
)
from kubernetriks_tpu.batched.timerep import TPair

C, W = 3, 64
PAY_KEYS = ("req_cpu", "req_ram", "dur_win", "dur_off", "create_win", "rank")


def _random_like(rng, leaf):
    if leaf.dtype == jnp.bool_:
        return jnp.asarray(rng.integers(0, 2, leaf.shape).astype(bool))
    if jnp.issubdtype(leaf.dtype, jnp.floating):
        return jnp.asarray(rng.uniform(0.0, 9.0, leaf.shape).astype(leaf.dtype))
    return jnp.asarray(rng.integers(1, 1 << 20, leaf.shape).astype(leaf.dtype))


def _pods(rng, P):
    """Pod planes in which every slot of every leaf is its own value: a slot
    moved to the wrong place, or left, shows."""
    zeros = jnp.zeros((C, P), jnp.int32)
    template = fresh_pod_arrays(
        C, P, zeros, zeros, TPair(win=zeros, off=jnp.zeros((C, P), jnp.float32))
    )
    return jax.tree.map(lambda leaf: _random_like(rng, leaf), template)


def _payload(rng, L):
    pay = {key: jnp.asarray(rng.integers(1, 1 << 20, (C, L)).astype(np.int32)) for key in PAY_KEYS}
    pay["dur_off"] = jnp.asarray(rng.uniform(0.0, 9.0, (C, L)).astype(np.float32))
    return pay


_traced = jax.jit(_slide_apply_traced, static_argnames=("W",))

# Every kind of shift _quantize_shift_device returns at W = 64: none, the
# power-of-two fallback below W/8 (1, 2, and 4 from a first blocking slot of
# 5, 6 or 7), and the three quanta.
SHIFTS = [0, 1, 2, 4, W // 8, W // 4, W // 2]


def test_the_shifts_under_test_are_the_quantizers():
    got = {int(_quantize_shift_device(jnp.int32(s0), W)) for s0 in range(W + 1)}
    assert got == set(SHIFTS)
    assert int(_quantize_shift_device(jnp.int32(7), W)) == 4


@pytest.mark.parametrize("where", ["mid", "last_of_stage", "last_of_trace"])
@pytest.mark.parametrize("with_rank", [True, False], ids=["rank", "no_rank"])
@pytest.mark.parametrize("tail", [0, 9], ids=["no_tail", "tail"])
@pytest.mark.parametrize("s", SHIFTS)
def test_traced_slide_equals_the_static_slide_leaf_for_leaf(s, tail, with_rank, where):
    rng = np.random.default_rng(1000 * s + 10 * tail + with_rank)
    # A RefillStage is 4W columns wide, the whole-trace payload T + W.
    L = {"mid": 4 * W, "last_of_stage": 4 * W, "last_of_trace": 150 + W}[where]
    # The last base the callers' guards let a slide by s start from: the
    # superspan's `exhausted` exit keeps base + W + s <= L; on the whole-trace
    # payload a slide only triggers at base + W < T.
    base = {"mid": 70, "last_of_stage": L - W - s, "last_of_trace": min(150 - 1, L - W - s)}[where]
    pods = _pods(rng, W + tail)
    pay = _payload(rng, L)
    if not with_rank:
        del pay["rank"]
    rank = _random_like(rng, pods.phase) if with_rank else None
    base = jnp.int32(base)

    new_pods, new_rank = _traced(pods, rank, pay, base, jnp.int32(s), W=W)
    want_pods, want_rank = _slide_apply_device(pods, rank, pay, base, s=s, W=W)

    assert jax.tree.structure(new_pods) == jax.tree.structure(want_pods)
    for (path, got), want in zip(
        jax.tree_util.tree_leaves_with_path(new_pods), jax.tree.leaves(want_pods)
    ):
        assert got.dtype == want.dtype and got.shape == want.shape, path
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want), err_msg=str(path))
    if with_rank:
        np.testing.assert_array_equal(np.asarray(new_rank), np.asarray(want_rank))
    else:
        assert new_rank is None and want_rank is None
    # What the contract says, read off the result itself: the tail is the
    # old tail, the refill is fresh (EMPTY, unplaced) over the payload's
    # columns base + W .. base + W + s, and no shift at all is the identity.
    np.testing.assert_array_equal(np.asarray(new_pods.node[:, W:]), np.asarray(pods.node[:, W:]))
    assert not np.asarray(new_pods.phase[:, W - s : W]).any()
    assert (np.asarray(new_pods.node[:, W - s : W]) == -1).all()
    np.testing.assert_array_equal(
        np.asarray(new_pods.req_cpu[:, W - s : W]),
        np.asarray(pay["req_cpu"][:, int(base) + W : int(base) + W + s]),
    )
    if s == 0:
        for got, old in zip(jax.tree.leaves(new_pods), jax.tree.leaves(pods)):
            np.testing.assert_array_equal(np.asarray(got), np.asarray(old))


@pytest.mark.parametrize("with_rank", [True, False], ids=["rank", "no_rank"])
def test_traced_slide_lowers_no_gather(with_rank):
    rng = np.random.default_rng(0)
    pods, pay = _pods(rng, W + 9), _payload(rng, 4 * W)
    rank = pods.queue_seq if with_rank else None
    text = _traced.lower(pods, rank, pay, jnp.int32(5), jnp.int32(8), W=W).as_text()
    assert "stablehlo.dynamic_slice" in text
    assert "stablehlo.gather" not in text and "stablehlo.scatter" not in text


def test_superspan_slides_on_a_mesh_of_four_leave_the_one_device_state():
    from kubernetriks_tpu.test_util import leaves_differing
    from tests.sharded_builds import bare_batch, mesh_of

    def run(**kwargs):
        sim = bare_batch(8, pod_window=32, superspan=True, **kwargs)
        sim.step_until_time(400.0)
        sim.step_until_time(900.0)
        return sim

    one, four = run(), run(mesh=mesh_of(4))
    try:
        assert four.dispatch_stats["superspans"] > 0 and four.dispatch_stats["window_chunks"] == 0
        # Slides the superspan completed on the device, the same on both.
        assert four.dispatch_stats["superspan_spans"] == one.dispatch_stats["superspan_spans"] >= 2
        assert four._pod_base == one._pod_base > 0
        assert leaves_differing(one.state, four.state) == []
    finally:
        one.close()
        four.close()
