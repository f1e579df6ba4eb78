"""The comparison that decides `correct`, shown to fail: one pod's node or
start time perturbed, and the control (the same answers with times held in
float32, the step a later PR would be tempted by), at a size a test can hold."""

import json
import os

import numpy as np
import pytest

from benchmark import deployment, reference, traffic_gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
TOLERANCE = 5e-6


@pytest.fixture(scope="module")
def oracle_and_view():
    with open(os.path.join(ROOT, "benchmark", "configs", "sched1k.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(ROOT, "benchmark", "traffic", "montecarlo.json")) as fh:
        traffic = json.load(fh)
    dep = dict(config["deployment"], nodes=16)
    traffic["plain"]["rate_per_second"] = 0.5
    text = deployment.config_yaml("sched1k", dep)
    oracle = reference.run_oracle(
        text, traffic_gen.cluster_records(dep), traffic_gen.workload_records(traffic, 77, 0), 1200.0
    )
    # The oracle's own answers in the program's place: a sound run by construction.
    view = {name: ("succeeded", node, start) for name, (node, start) in oracle.succeeded.items()}
    return config["guarantees"], oracle, view


def verdict(guarantees, oracle, view, counters=None):
    checks = reference.compare_pods(
        "c0", view, counters or oracle.counters, oracle, guarantees["counters_exact"], TOLERANCE
    )
    return {c.name.split(".", 1)[1]: c for c in checks}


def test_sound_answers_pass(oracle_and_view):
    guarantees, oracle, view = oracle_and_view
    assert len(view) > 400 and max(s for _, _, s in view.values()) > 900.0
    assert all(c.ok for c in verdict(guarantees, oracle, view).values())


def test_one_pod_on_another_node_fails(oracle_and_view):
    guarantees, oracle, view = oracle_and_view
    name = sorted(view)[len(view) // 2]
    moved = dict(view)
    moved[name] = ("succeeded", "gen_node_0015" if view[name][1] != "gen_node_0015" else "gen_node_0014", view[name][2])
    got = verdict(guarantees, oracle, moved)
    assert not got["pods_on_another_node"].ok and got["pods_on_another_node"].value == 1.0
    assert got["start_time_gap_s"].ok


@pytest.mark.parametrize("shift_s", [1e-5, 10.0])
def test_one_start_time_perturbed_fails(oracle_and_view, shift_s):
    guarantees, oracle, view = oracle_and_view
    name = sorted(view)[3]
    late = dict(view)
    late[name] = (view[name][0], view[name][1], view[name][2] + shift_s)
    got = verdict(guarantees, oracle, late)
    assert not got["start_time_gap_s"].ok
    assert got["start_time_gap_s"].value == pytest.approx(shift_s, rel=1e-3)


def test_a_pod_left_pending_and_a_counter_off_by_one_fail(oracle_and_view):
    guarantees, oracle, view = oracle_and_view
    name = sorted(view)[0]
    pending = dict(view)
    pending[name] = ("other", None, 0.0)
    assert not verdict(guarantees, oracle, pending)["pods_in_another_phase"].ok
    counters = dict(oracle.counters, pods_succeeded=oracle.counters["pods_succeeded"] - 1)
    assert not verdict(guarantees, oracle, view, counters)["pods_succeeded"].ok


def test_control_float32_times_fail_the_start_time_limit(oracle_and_view):
    """Sound runs read under 1e-6 s (pair-time resolution 6e-7 s); float32
    times read 3e-5 s by t = 1000 s. The limit, 5e-6 s, is the configuration's."""
    guarantees, oracle, view = oracle_and_view
    got = verdict(guarantees, oracle, reference.in_float32(view))
    assert not got["start_time_gap_s"].ok
    assert 1e-5 < got["start_time_gap_s"].value < 1e-4
    assert got["pods_on_another_node"].ok and got["pods_in_another_phase"].ok


def test_mismatching_leaves_sees_float32_state_and_structure():
    import jax.numpy as jnp

    a = {"time": np.array([1000.00001, 3.0]), "metrics": {"sum": np.array([1.0, 2.0], np.float32)}}
    same = {"time": a["time"].copy(), "metrics": {"sum": np.array([1.0 + 5e-8, 2.0], np.float32)}}
    assert reference.mismatching_leaves(a, same) == []
    rounded = {"time": a["time"].astype(np.float32).astype(np.float64), "metrics": a["metrics"]}
    assert reference.mismatching_leaves(a, rounded) == ["['time']"]
    assert reference.mismatching_leaves(a, {"time": jnp.asarray(a["time"])})[0].startswith("<tree structure")


def test_oracle_fault_is_named_not_swallowed(monkeypatch):
    def boom(*args, **kwargs):
        raise KeyError("grp_130")

    monkeypatch.setattr(reference, "run_oracle", boom)
    with pytest.raises(reference.OracleFault, match="grp_130"):
        reference.run_oracle_or_fault("", [], [], 1.0)


def test_a_count_judged_within_a_limit(oracle_and_view):
    """A count the CA trajectories move by a few is held to a limit of its own
    (the stream mix's HPA scaled-up pods): a gap inside passes, one outside fails,
    and the row shows the gap beside the limit."""
    _, oracle, _ = oracle_and_view
    base = dict(oracle.counters)
    near = dict(base, total_scaled_up_pods=base["total_scaled_up_pods"] + 4)
    far = dict(base, total_scaled_up_pods=base["total_scaled_up_pods"] + 50)
    within = {"total_scaled_up_pods": 12}
    ok = reference.compare_counts("c0", near, oracle, ["pods_succeeded"], within)
    assert [c.ok for c in ok] == [True, True] and (ok[1].value, ok[1].limit) == (4.0, 12.0)
    bad = reference.compare_counts("c0", far, oracle, ["pods_succeeded"], within)
    assert [c.ok for c in bad] == [True, False] and bad[1].value == 50.0
