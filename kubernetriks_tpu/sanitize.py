"""Runtime sanitizer (`KTPU_SANITIZE=1`) — the dynamic half of ktpu-lint.

The static passes (kubernetriks_tpu/lint/) prove the SOURCE obeys the
framework invariants; the sanitizer enforces them on a live run:

- **Transfer guard**: the engine's steady-state dispatch region
  (`step_until_time`) runs under
  `jax.transfer_guard_device_to_host("disallow_explicit")`, so ANY
  device-to-host transfer — implicit (`.item()`, `int(arr)`,
  `np.asarray(arr)`) or explicit (`jax.device_get`) — raises unless it
  sits inside an `allow_transfer(reason)` scope. The allow scopes pair
  1:1 with the lint pass's sync-ok waivers: the static budget and the
  runtime budget are the same list.

  The CPU backend never fires jax's transfer guard (host-resident
  buffers make every d2h read zero-copy, checked on jax 0.9.0), so the
  guard alone has no teeth on CPU CI. The sanitizer therefore ALSO keeps
  its own thread-local guard depth, and `to_host` — the framework's d2h
  convention (parallel/multihost.py) — asserts through
  `assert_sync_allowed` that it is inside an allow scope whenever the
  guard is active. Textual sync forms that bypass `to_host`
  (`np.asarray`, `int(arr)`, `.item()`) are the static lint pass's job;
  together the two nets cover both backends.
- **Donation enforcement**: after a donated jit call, donated inputs must
  be dead. On accelerator backends XLA marks them deleted; on CPU
  donation is a no-op, which is exactly why read-after-donate bugs pass
  CPU CI. `consume_donated` force-deletes any surviving donated input so
  a later read raises ("Array has been deleted") on every backend.
- The `KTPU_DEBUG_FINITE` NaN/inf state sweep folds in at every dispatch
  boundary (engine._check_finite runs under sanitize too).

Host-to-device transfers stay unguarded: argument commits at dispatch are
implicit h2d by design (cheap, asynchronous), and staging/refill uploads
are the documented streaming protocol — the sanitizer targets the sync
bug class (d2h), not uploads.
"""

from __future__ import annotations

import contextlib
import threading

import jax

from kubernetriks_tpu.flags import flag_bool

_state = threading.local()


def _depths():
    if not hasattr(_state, "guard"):
        _state.guard = 0
        _state.allow = 0
    return _state


def sanitize_default() -> bool:
    """The build-time default for BatchedSimulation(sanitize_mode=None)."""
    return flag_bool("KTPU_SANITIZE")


@contextlib.contextmanager
def _guard_cm():
    st = _depths()
    st.guard += 1
    try:
        with jax.transfer_guard_device_to_host("disallow_explicit"):
            yield
    finally:
        st.guard -= 1


@contextlib.contextmanager
def _allow_cm():
    st = _depths()
    st.allow += 1
    try:
        with jax.transfer_guard_device_to_host("allow"):
            yield
    finally:
        st.allow -= 1


def guard(active: bool):
    """Context manager for the steady-state dispatch region: disallow ALL
    device-to-host transfers (explicit included) while active — via jax's
    transfer guard on backends that enforce it, and via the
    assert_sync_allowed choke point everywhere."""
    if not active:
        return contextlib.nullcontext()
    return _guard_cm()


def allow_transfer(active: bool, reason: str):
    """Waived-sync scope; `reason` mirrors the lint waiver's reason and is
    kept as a required argument so the runtime budget stays greppable."""
    assert reason, "allow_transfer requires a reason"
    if not active:
        return contextlib.nullcontext()
    return _allow_cm()


def assert_sync_allowed(what: str) -> None:
    """Raise when a device-to-host sync happens inside a sanitized
    dispatch region outside every allow_transfer scope. Called by the
    framework's d2h choke points (to_host); two integer compares when no
    guard is active."""
    st = _depths()
    if st.guard > 0 and st.allow == 0:
        raise RuntimeError(
            f"KTPU_SANITIZE: unwaived device-to-host sync ({what}) inside "
            "the sanitized steady-state dispatch region — wrap a legitimate "
            "sync in sanitize.allow_transfer(reason) and give its line a "
            "sync-ok lint waiver"
        )


def consume_donated(tree) -> int:
    """Enforce donation semantics on `tree` (a pytree that was passed at a
    donated position): every jax.Array leaf must be dead after the call.
    Leaves XLA already consumed are left alone; survivors (CPU, where
    donation is unimplemented and the bug class silently passes) are
    force-deleted so any read-after-donate raises. Returns the number of
    leaves force-deleted."""
    forced = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        if isinstance(leaf, jax.Array):
            try:
                deleted = leaf.is_deleted()
            except AttributeError:  # tracers/ShapeDtypeStructs: nothing to do
                continue
            if not deleted:
                leaf.delete()
                forced += 1
    return forced
