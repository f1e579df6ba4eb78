"""CA slot reclaim + bounded-memory endurance gates (r14, ROADMAP #2).

The batched CA reserves node slots per group; without reclaim the cursor
is monotone, so sustained churn eventually RAISES
(engine.check_autoscaler_bounds) where the reference keeps running — its
node_component_pool reuses components on scale-down
(node_component_pool.rs:60-77). With reclaim (KTPU_RECLAIM) a periodic
in-trace compaction returns fully-retired slots, the cursor tracks LIVE
occupancy, and trajectories stay SCALAR-EXACT because every allocation
carries the scalar's total_allocated naming index
(autoscale.ca_name_order derives every name-ordered walk from it).

Gates here:
1. Churn engineered past the pre-reclaim reserve: the old path raises,
   the new path finishes with the EXACT scalar-oracle node trajectory
   (including double-digit allocation names, "ca_node_10" < "ca_node_2")
   and a quiet loud-bound.
2. A/B bit-identity within the reserve: reclaim on/off agree on
   trajectories, metrics and dispatch_stats when churn never exhausts
   the static reserve.
3. Checkpoint/restore roundtrip carries the reclaim counters (ckpt meta
   guards a mode mismatch loudly).
4. The endurance claim at tier-1 size: churn past the reserve under the
   watchdog fires no reserve verdict, compiles nothing after warm-up and
   leaves the slab accounting where it was.
5. The slow-lane endurance gate: sustained churn many times the reserve
   with chaos + streaming feeder + a mid-run checkpoint/restore, exact
   oracle trajectory, zero saturation verdicts, flat slab watermarks.
"""

import numpy as np
import pytest

from kubernetriks_tpu.batched.engine import build_batched_from_traces
from kubernetriks_tpu.sim.simulator import KubernetriksSimulation
from kubernetriks_tpu.test_util import default_test_simulation_config
from kubernetriks_tpu.trace.generic import GenericClusterTrace, GenericWorkloadTrace

# Reserve = min(per_group_cap, max_node_count) * ca_slot_multiplier:
# max_node_count 2 at multiplier 1 gives a TWO-slot reserve the wave
# churn overruns many times over.
RECLAIM_CA_SUFFIX = """
cluster_autoscaler:
  enabled: true
  autoscaler_type: kube_cluster_autoscaler
  scan_interval: 10.0
  max_node_count: 2
  node_groups:
  - node_template:
      metadata:
        name: ca_node
      status:
        capacity:
          cpu: 16000
          ram: 34359738368
"""

CLUSTER_TRACE = """
events:
- timestamp: 2.0
  event_type:
    !CreateNode
      node:
        metadata: {name: base_node}
        status: {capacity: {cpu: 8000, ram: 17179869184}}
"""


def wave_workload(
    n_waves: int, spacing: float = 200.0, duration: float = 60.0
) -> str:
    """n_waves churn waves: each wave's 12000-mcpu pod only fits the CA
    template (base node is 8000), so the CA opens a node, the pod runs
    `duration` seconds, and the empty node scales back down before the
    next wave — one reserve slot consumed per pod, fully retired between
    waves. Every third wave sends TWO pods (staggered finishes), so two
    CA nodes coexist and the scale-down walks candidates in NAME order
    across reused slots."""
    events = []
    pod = 0
    for k in range(n_waves):
        t0 = 10.0 + k * spacing
        for j in range(2 if k % 3 == 2 else 1):
            events.append(
                f"""
- timestamp: {round(t0 + 7.0 * j, 1)}
  event_type:
    !CreatePod
      pod:
        metadata:
          name: wave_pod_{pod:03d}
        spec:
          resources:
            requests:
              cpu: 12000
              ram: 12582912000
            limits:
              cpu: 12000
              ram: 12582912000
          running_duration: {round(duration + 11.0 * j, 1)}
"""
            )
            pod += 1
    return "events:" + "".join(events)


def _build_batched(workload: str, config_suffix: str = "", **kwargs):
    config = default_test_simulation_config(RECLAIM_CA_SUFFIX + config_suffix)
    kwargs.setdefault("n_clusters", 1)
    kwargs.setdefault("ca_slot_multiplier", 1)
    return config, build_batched_from_traces(
        config,
        GenericClusterTrace.from_yaml(CLUSTER_TRACE).convert_to_simulator_events(),
        GenericWorkloadTrace.from_yaml(workload).convert_to_simulator_events(),
        **kwargs,
    )


def _scalar(config, workload: str) -> KubernetriksSimulation:
    sim = KubernetriksSimulation(config)
    sim.initialize(
        GenericClusterTrace.from_yaml(CLUSTER_TRACE),
        GenericWorkloadTrace.from_yaml(workload),
    )
    return sim


def test_reclaim_churn_past_reserve_matches_scalar():
    """12 waves (16 allocations — double-digit names included) through a
    2-slot reserve: cumulative churn 8x the static capacity. The reclaim
    path finishes with the EXACT scalar node-count trajectory and a
    clean loud-bound; the cursor ends at live occupancy, not cumulative
    allocations."""
    n_waves = 12
    workload = wave_workload(n_waves)
    config, batched = _build_batched(workload, reclaim=True)
    assert batched.reclaim
    scalar = _scalar(config, workload)

    traj_scalar, traj_batched = [], []
    horizon = 10.0 + n_waves * 200.0
    # Mid-window samples OFF the simulator's 0.01 s event-time lattice:
    # the CA cadence drifts 0.7 s/cycle, so over enough cycles some
    # create/remove lands EXACTLY on any on-lattice sample grid and the
    # comparison degenerates to float-dust tie-breaking on both sides
    # (engine.node_count_at docstring) — +5.003 never collides.
    for t in np.arange(15.003, horizon, 10.0):
        scalar.step_until_time(float(t))
        batched.step_until_time(float(t))
        traj_scalar.append(scalar.api_server.node_count())
        traj_batched.append(batched.node_count_at(float(t)))

    assert max(traj_scalar) >= 3, "scenario must exercise the CA"
    assert traj_batched == traj_scalar, (
        f"scalar  {traj_scalar}\nbatched {traj_batched}"
    )
    # Cumulative churn really overran the static reserve, and reclaim
    # returned the retired slots (>= allocations - reserve capacity).
    total = int(np.asarray(batched.state.auto.ca_total).sum())
    reserve = batched._reserve_capacities["ca_reserve"][0]
    assert total >= 3 * reserve, (total, reserve)
    assert int(batched.ca_slots_reclaimed().sum()) >= total - reserve
    # Double-digit allocation names were exercised ("ca_node_10" pops
    # before "ca_node_2" in the scale-down walk).
    assert total >= 10
    # The cursor is LIVE occupancy now: everything scaled back down.
    assert int(np.asarray(batched.state.auto.ca_cursor).sum()) <= reserve
    batched.check_autoscaler_bounds()  # must NOT raise


def test_reclaim_off_churn_past_reserve_raises_loudly():
    """The same churn without reclaim crosses the documented bound: the
    engine raises at readout instead of silently starving, and the
    message points at the reclaim switch."""
    workload = wave_workload(6)
    _, batched = _build_batched(workload, reclaim=False)
    assert not batched.reclaim
    with pytest.raises(RuntimeError, match="CA slot reserve exhausted"):
        batched.step_until_time(6 * 200.0)
        batched.metrics_summary()
    with pytest.raises(RuntimeError, match="KTPU_RECLAIM"):
        batched.check_autoscaler_bounds()


def test_reclaim_ab_bit_identity_within_reserve():
    """KTPU_RECLAIM=0 vs =1 on churn the static reserve can absorb:
    node trajectories, final metrics and dispatch_stats all agree — the
    off path compiles the pre-reclaim programs, the on path's compaction
    is invisible to the trajectory."""
    import jax

    workload = wave_workload(4)
    _, on = _build_batched(workload, reclaim=True, ca_slot_multiplier=3)
    _, off = _build_batched(workload, reclaim=False, ca_slot_multiplier=3)
    traj_on, traj_off = [], []
    for t in np.arange(15.003, 4 * 200.0 + 10.0, 10.0):
        on.step_until_time(float(t))
        off.step_until_time(float(t))
        traj_on.append(on.node_count_at(float(t)))
        traj_off.append(off.node_count_at(float(t)))
    assert traj_on == traj_off
    assert on.dispatch_stats == off.dispatch_stats
    flat_on = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(np.asarray, on.state.metrics)
    )[0]
    flat_off = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(np.asarray, off.state.metrics)
    )[0]
    for (path, a), (_, b) in zip(flat_on, flat_off):
        np.testing.assert_allclose(
            a, b, rtol=1e-6, err_msg=jax.tree_util.keystr(path)
        )
    # The on path really reclaimed (the A/B is not vacuous).
    assert int(on.ca_slots_reclaimed().sum()) > 0
    on.check_autoscaler_bounds()
    off.check_autoscaler_bounds()


def test_reclaim_checkpoint_roundtrip(tmp_path):
    """Mid-run save/restore under reclaim: the reclaim leaves (ca_alloc /
    ca_total / ca_reclaimed) ride the state pytree, the restored run
    continues bit-identically, and restoring into a reclaim-off engine
    raises the actionable meta guard instead of an opaque manifest diff."""
    from kubernetriks_tpu.batched.state import compare_states

    pytest.importorskip("orbax.checkpoint")
    workload = wave_workload(8)
    path = str(tmp_path / "ckpt")

    _, a = _build_batched(workload, reclaim=True)
    a.step_until_time(700.0)
    assert int(a.ca_slots_reclaimed().sum()) > 0, "save point must be post-reclaim"
    a.save_checkpoint(path)
    a.step_until_time(1500.0)

    _, b = _build_batched(workload, reclaim=True)
    b.load_checkpoint(path)
    b.step_until_time(1500.0)
    assert compare_states(a.state, b.state) == []
    np.testing.assert_array_equal(a.ca_slots_reclaimed(), b.ca_slots_reclaimed())

    _, c = _build_batched(workload, reclaim=False)
    with pytest.raises(ValueError, match="reclaim mismatch"):
        c.load_checkpoint(path)


def test_reclaim_tristate_default_follows_checkpoint(tmp_path):
    """A TRISTATE-defaulted engine (no reclaim arg, no KTPU_RECLAIM)
    follows the checkpoint's recorded mode instead of raising: the
    accelerator default is reclaim ON, so every pre-reclaim checkpoint
    would otherwise refuse to restore on TPU/GPU until the user dug up
    KTPU_RECLAIM=0. Explicit requests keep the loud guard (pinned by the
    roundtrip test above). Both directions, continuing bit-identically
    with the matching-mode engine."""
    from kubernetriks_tpu.batched.state import compare_states

    pytest.importorskip("orbax.checkpoint")
    workload = wave_workload(8)

    # Saved WITH reclaim -> defaulted engine (CPU tristate resolves off)
    # flips ON and continues exactly like a reclaim=True engine.
    path_on = str(tmp_path / "ckpt_on")
    _, a = _build_batched(workload, reclaim=True)
    a.step_until_time(700.0)
    a.save_checkpoint(path_on)
    a.step_until_time(1500.0)
    _, b = _build_batched(workload)  # reclaim unset: tristate default
    assert b._reclaim_requested is None and not b.reclaim
    with pytest.warns(RuntimeWarning, match="following the checkpoint"):
        b.load_checkpoint(path_on)
    assert b.reclaim
    b.step_until_time(1500.0)
    assert compare_states(a.state, b.state) == []

    # Saved WITHOUT reclaim -> an engine whose reclaim came from the
    # tristate default (simulated: accelerator backends default on)
    # flips OFF and continues exactly like a reclaim=False engine.
    path_off = str(tmp_path / "ckpt_off")
    _, c = _build_batched(workload, reclaim=False)
    c.step_until_time(700.0)
    c.save_checkpoint(path_off)
    c.step_until_time(1500.0)
    _, d = _build_batched(workload, reclaim=True)
    d._reclaim_requested = None  # as if reclaim=True came from the tristate
    with pytest.warns(RuntimeWarning, match="following the checkpoint"):
        d.load_checkpoint(path_off)
    assert not d.reclaim
    assert d.state.auto.ca_alloc is None
    d.step_until_time(1500.0)
    assert compare_states(c.state, d.state) == []


def test_reclaim_refused_on_interleaving_names():
    """A trace node named inside a CA group's decimal name family makes
    the static class order unsound: explicit reclaim=True raises at
    build, naming the collision."""
    bad_cluster = """
events:
- timestamp: 2.0
  event_type:
    !CreateNode
      node:
        metadata: {name: ca_node_15}
        status: {capacity: {cpu: 8000, ram: 17179869184}}
"""
    config = default_test_simulation_config(RECLAIM_CA_SUFFIX)
    with pytest.raises(ValueError, match="name family"):
        build_batched_from_traces(
            config,
            GenericClusterTrace.from_yaml(bad_cluster).convert_to_simulator_events(),
            GenericWorkloadTrace.from_yaml(wave_workload(2)).convert_to_simulator_events(),
            n_clusters=1,
            reclaim=True,
        )


@pytest.fixture(scope="module")
def watched_churn():
    """Twelve churn waves (16 allocations) through a four-slot reserve with
    the flight recorder and the watchdog on: warm-up over the first three
    waves, then the rest under a warning capture, the slab accounting and
    the jit-cache counts read at both ends of the measured region."""
    import warnings

    from kubernetriks_tpu.batched.fleet import jit_cache_sizes
    from kubernetriks_tpu.telemetry.observatory import SaturationWarning

    n_waves = 12
    _, sim = _build_batched(
        wave_workload(n_waves), reclaim=True, ca_slot_multiplier=2,
        telemetry=True, watchdog=True, telemetry_ring=64,
    )
    with warnings.catch_warnings(record=True):
        warnings.simplefilter("always")
        sim.step_until_time(10.0 + 3 * 200.0)
    art = dict(sizes=jit_cache_sizes(), slabs=[sim._slab_accounting()])
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        for k in range(4, n_waves + 1):
            sim.step_until_time(10.0 + k * 200.0)
            art["slabs"].append(sim._slab_accounting())
        sim.drain_telemetry()
    art["sizes_after"] = jit_cache_sizes()
    art["verdicts"] = [
        str(w.message) for w in caught if issubclass(w.category, SaturationWarning)
    ]
    yield sim, art
    sim.close()


def test_reclaim_churn_keeps_the_watchdog_quiet(watched_churn):
    """The endurance claim at tier-1 size: cumulative churn four times the
    reserve, and no reserve verdict, neither warned along the way nor live
    at the end; the reserve never trends toward exhaustion."""
    sim, art = watched_churn
    total = int(np.asarray(sim.state.auto.ca_total).sum())
    reserve = sim._reserve_capacities["ca_reserve"][0]
    assert total >= 3 * reserve, (total, reserve)
    assert int(sim.ca_slots_reclaimed().sum()) >= total - reserve
    assert [v for v in art["verdicts"] if "reserve" in v] == []
    fired = sim.telemetry_report()["resources"]["watchdog"]["fired"]
    assert not any(kind.endswith("_reserve_used") for kind in fired), fired
    sim.check_autoscaler_bounds()


def test_reclaim_churn_compiles_nothing_and_holds_its_slabs(watched_churn):
    """After warm-up the compaction is a data move: no jit entry gains a
    variant over nine more waves, and the slab accounting does not move."""
    _, art = watched_churn
    assert art["sizes_after"] == art["sizes"]
    assert all(later == art["slabs"][0] for later in art["slabs"][1:]), art["slabs"]


@pytest.mark.slow
def test_endurance_gate_chaos_streaming_ckpt():
    """The ROADMAP #2 endurance gate, slow lane: 48 churn waves (~13
    simulated hours, cumulative allocations ~30x the static reserve)
    with node chaos on, the streaming feeder staging slabs, reclaim
    compacting the reserve, and a mid-run checkpoint/restore roundtrip.
    Finishes with the EXACT scalar-oracle node trajectory, ZERO
    saturation verdicts (the reserve never trends toward exhaustion),
    flat slab watermarks, and a clean loud-bound."""
    import warnings

    from kubernetriks_tpu.telemetry.observatory import SaturationWarning

    n_waves = 48
    workload = wave_workload(n_waves)
    # Seed chosen so the crash chain actually fires at this shape (one
    # base node, ~9400 s horizon): seed 3 samples five crash/recover
    # cycles spread across the run; several nearby seeds sample none.
    fault_suffix = """
fault_injection:
  enabled: true
  seed: 3
  node:
    mttf: 2400.0
    mttr: 120.0
"""
    config_suffix = fault_suffix
    # Reserve 4 (multiplier 2 over the 2-quota): peak live occupancy is
    # 2, so the watchdog has nothing to say while cumulative churn
    # (~64 allocations) overruns the static reserve ~16x.
    kwargs = dict(
        reclaim=True,
        ca_slot_multiplier=2,
        pod_window=32,
        superspan=True,
        stream=True,
        telemetry=True,
        watchdog=True,
        telemetry_ring=64,
    )
    config, batched = _build_batched(workload, config_suffix, **kwargs)
    scalar = _scalar(config, workload)

    horizon = 10.0 + n_waves * 200.0
    ckpt_at = 10.0 + (n_waves // 2) * 200.0
    caught = []
    slabs_seen = []
    traj_scalar, traj_batched = [], []
    # Off-lattice samples — see test_reclaim_churn_past_reserve_matches_scalar.
    for t in np.arange(15.003, horizon, 10.0):
        with warnings.catch_warnings(record=True) as w:
            warnings.simplefilter("always")
            scalar.step_until_time(float(t))
            batched.step_until_time(float(t))
        caught.extend(
            x for x in w if issubclass(x.category, SaturationWarning)
        )
        traj_scalar.append(scalar.api_server.node_count())
        traj_batched.append(batched.node_count_at(float(t)))
        if (int(t) - 15) % 500 == 0:
            slabs_seen.append(
                (batched.pod_window, batched._sample_resources()["slabs"])
            )

    assert traj_batched == traj_scalar, (
        "endurance trajectory diverged from the scalar oracle:\n"
        f"scalar  {traj_scalar}\nbatched {traj_batched}"
    )
    assert max(traj_scalar) >= 3
    assert int(np.asarray(batched.state.metrics.node_crashes).sum()) > 0, (
        "chaos never fired; the endurance gate is vacuous"
    )
    total = int(np.asarray(batched.state.auto.ca_total).sum())
    reserve = batched._reserve_capacities["ca_reserve"][0]
    assert total >= 3 * reserve, (total, reserve)
    assert int(batched.ca_slots_reclaimed().sum()) >= total - reserve
    # The hard gate is the RESERVE trajectory (the reclaim observable);
    # the end-of-trace headroom note and host-speed pipeline verdicts
    # (feeder stalls) are not reclaim regressions.
    reserve_verdicts = [
        str(x.message) for x in caught if "reserve" in str(x.message)
    ]
    assert reserve_verdicts == []
    # Flat slab watermarks per stage geometry (a pod-window growth is a
    # step, not a trend).
    by_geometry: dict = {}
    for pw, slabs in slabs_seen:
        by_geometry.setdefault(pw, []).append(slabs)
    for pw, rows in by_geometry.items():
        for later in rows[1:]:
            assert later == rows[0], (pw, later, rows[0])
    batched.check_autoscaler_bounds()

    # Checkpoint/restore roundtrip against the finished run: restore at
    # the midpoint and replay to the horizon — bit-identical end state.
    try:
        import orbax.checkpoint  # noqa: F401
    except ImportError:
        return
    import tempfile

    from kubernetriks_tpu.batched.state import compare_states

    _, replay = _build_batched(workload, config_suffix, **kwargs)
    with tempfile.TemporaryDirectory() as td:
        path = td + "/ckpt"
        replay.step_until_time(ckpt_at)
        replay.save_checkpoint(path)
        _, resumed = _build_batched(workload, config_suffix, **kwargs)
        resumed.load_checkpoint(path)
        for sim in (replay, resumed):
            sim.step_until_time(horizon - 5.0)
        assert compare_states(replay.state, resumed.state) == []
        replay.close()
        resumed.close()
    batched.close()
