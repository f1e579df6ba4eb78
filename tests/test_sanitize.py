"""Runtime sanitizer (KTPU_SANITIZE) — the dynamic half of ktpu-lint.

The flagship composed scenario (HPA + CA + sliding window + superspan +
chaos faults) must run to completion under the sanitizer — proving ZERO
unwaived device-to-host transfers in the steady-state dispatch region (an
unwaived transfer raises through jax's transfer guard) — and produce
bit-identical results to the unsanitized run. Plus unit teeth: the guard
really raises on an unwaived sync, and donation enforcement really makes
read-after-donate crash on CPU (where XLA donation is a no-op — the bug
class that silently passes CPU CI without the sanitizer)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from kubernetriks_tpu import sanitize
from kubernetriks_tpu.batched.state import compare_states

from test_superspan import FAULT_SUFFIX
from test_window_donation_dispatch import _build_composed


def _run(sim, ends=(150.0, 300.0, 450.0)):
    for end in ends:
        sim.step_until_time(end)
    return sim


@pytest.mark.slow
def test_sanitized_composed_bit_identical():
    """Sanitizer smoke: one composed span (HPA + CA + superspan + chaos)
    under the sanitizer on CPU — zero unwaived transfers (the guard would
    raise), donated inputs consumed after every donated call, finite sweep
    at each superspan boundary — with results bit-identical to the
    unsanitized path. Slow lane (tier-1 wall-clock budget): KTPU_SANITIZE
    is an opt-in debug mode, not a simulation path — the guard-raise /
    consume-donated / NaN-sweep unit gates below stay tier-1, and the
    composed machinery itself is covered bit-exactly by test_superspan's
    chaos-on tier-1 gate; this composed-under-sanitizer variant runs in
    the slow lane."""
    kwargs = dict(
        config_suffix=FAULT_SUFFIX,
        superspan=True,
        superspan_k=4,
        superspan_chunk=4,
    )
    sane = _run(_build_composed(sanitize_mode=True, **kwargs))
    plain = _run(_build_composed(sanitize_mode=False, **kwargs))
    # The sanitized run exercised the real machinery: superspan dispatches,
    # slides, donation, faults.
    assert sane._sanitize and not plain._sanitize
    assert sane.donate
    assert sane.dispatch_stats["superspans"] > 0
    assert sane._pod_base > 0
    assert sane.fault_params is not None
    summary = sane.metrics_summary()
    assert summary == plain.metrics_summary()
    assert (
        summary["counters"]["pod_interruptions"]
        + summary["counters"]["pods_failed"]
        > 0
    ), "fault run produced no faults; sanitized parity is vacuous"
    assert compare_states(sane.state, plain.state) == []
    assert sane._pod_base == plain._pod_base
    assert sane.next_window_idx == plain.next_window_idx


def test_guard_raises_on_unwaived_transfer():
    """An unwaived device-to-host sync inside the guard raises; the same
    sync inside an allow_transfer scope passes. This backs the 'zero
    unwaived transfers' claim of the smoke test above on EVERY backend:
    jax's own transfer guard never fires on CPU (host-resident buffers),
    so the sanitizer's choke point at to_host is the CPU net."""
    from kubernetriks_tpu.parallel.multihost import to_host

    x = jnp.arange(8)
    with pytest.raises(RuntimeError, match="unwaived device-to-host"):
        with sanitize.guard(True):
            to_host(x + 1)
    with sanitize.guard(True):
        with sanitize.allow_transfer(True, "test readback"):
            got = to_host(x + 1)
    np.testing.assert_array_equal(got, np.arange(1, 9))
    # inactive guard is a no-op nullcontext
    with sanitize.guard(False):
        to_host(x + 2)
    # guard depth unwinds cleanly after the raise above
    to_host(x)


def test_consume_donated_makes_read_after_donate_crash():
    """On CPU, XLA donation is a no-op: a donated input SURVIVES the call,
    so reading it afterwards silently returns stale data — the exact bug
    class the donation lint pass + sanitizer target. consume_donated
    force-deletes the survivors, so the read raises on every backend."""
    donated_step = jax.jit(lambda s: jax.tree.map(lambda a: a + 1, s),
                           donate_argnums=(0,))
    state = {"a": jnp.arange(4), "b": jnp.ones((2, 2))}
    out = donated_step(state)
    # jax 0.9.0's CPU runtime implements donation (inputs come
    # back is_deleted) — consume_donated then force-deletes nothing and the
    # read already raises; on runtimes where donation is a no-op it deletes
    # the survivors. Either way the invariant below holds on every backend.
    sanitize.consume_donated(state)  # ktpu: donation-ok(the test enforces donation on the donated input — that's its job)
    with pytest.raises(RuntimeError, match="deleted"):
        np.asarray(state["a"])  # ktpu: donation-ok(deliberate read-after-donate: the test asserts it RAISES)
    # the call's result is untouched
    np.testing.assert_array_equal(np.asarray(out["a"]), np.arange(1, 5))
    # idempotent: consuming again touches nothing
    assert sanitize.consume_donated(state) == 0  # ktpu: donation-ok(idempotence check on the already-consumed input)


def test_sanitize_folds_in_finite_sweep():
    """KTPU_SANITIZE runs the KTPU_DEBUG_FINITE state sweep without the
    flag being set: a NaN planted in a non-sentinel float field raises at
    the next dispatch boundary."""
    sim = _build_composed(sanitize_mode=True, superspan=True)
    assert not sim._debug_finite  # sweep is active via sanitize alone
    sim.step_until_time(50.0)
    # plant NaN into the first all-finite float leaf instead of guessing
    # field names: flatten, poison, rebuild (the sweep flags NaN in ANY
    # float field, sentinel-exempt or not)
    leaves, treedef = jax.tree_util.tree_flatten(sim.state)
    poisoned = False
    for i, leaf in enumerate(leaves):
        if hasattr(leaf, "dtype") and jnp.issubdtype(leaf.dtype, jnp.floating):
            arr = np.array(leaf)
            if arr.size and np.isfinite(arr).all():
                arr.flat[0] = np.nan
                leaves[i] = jnp.asarray(arr)
                poisoned = True
                break
    assert poisoned, "no finite float leaf found to poison"
    sim.state = jax.tree_util.tree_unflatten(treedef, leaves)
    with pytest.raises(FloatingPointError, match="NaN"):
        sim._check_finite()
