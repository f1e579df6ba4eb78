"""The seeded generator: traces differ by cluster and repeat for a seed; every
seed asks the served fleet for the same work in another order."""

import collections
import json
import os

import pytest

from benchmark import traffic_gen

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def traffic(name):
    with open(os.path.join(ROOT, "benchmark", "traffic", name + ".json")) as fh:
        return json.load(fh)


@pytest.mark.parametrize("mix", ["montecarlo", "stream"])
def test_traces_differ_by_cluster_and_repeat_for_a_seed(mix):
    t = traffic(mix)
    seed = 2**31 + 12345  # the driver's seeds are large
    a = [traffic_gen.workload_records(t, seed, c) for c in range(3)]
    b = [traffic_gen.workload_records(t, seed, c) for c in range(3)]
    assert a == b
    assert a[0] != a[1] and a[1] != a[2]
    assert traffic_gen.workload_records(t, seed + 1, 0) != a[0]
    times = [rec[0] for rec in a[0]]
    assert times == sorted(times) and times[-1] <= t["plain"]["horizon_s"]
    pods = [rec for rec in a[0] if rec[1] == "create_pod"]
    # conditioned on its count: every cluster and seed has the same shapes
    assert len(pods) == round(t["plain"]["rate_per_second"] * t["plain"]["horizon_s"])
    assert {len(w) for w in a} == {len(a[0])}
    assert pods[9][2] == "plain_00009"  # zero-padded: sorted-name order is slot order
    assert bool(t.get("pod_group")) == any(rec[1] == "workload_yaml" for rec in a[0])


def test_node_names_sort_in_slot_order():
    names = [rec[2] for rec in traffic_gen.uniform_nodes(1000, 64000, 128 * traffic_gen.GIB)]
    assert names == sorted(names)


def test_every_seed_asks_the_fleet_for_the_same_work():
    t = traffic("whatif-steady")
    streams = [traffic_gen.query_stream(t, seed, 20.0) for seed in (1, 2**31 + 7)]
    assert streams[0] != streams[1]
    for key in (1, 2):  # scenario popularity, horizon mix
        counts = [collections.Counter(q[key] for q in s) for s in streams]
        assert counts[0] == counts[1]
    for s in streams:
        dues = [q[0] for q in s]
        assert dues == sorted(dues) and abs(dues[-1] - 20.0) < 1e-6
        assert len(s) == round(t["queries"]["rate_per_second"] * 20.0)
    horizons = collections.Counter(q[2] for q in streams[0])
    n = len(streams[0])
    assert abs(horizons[450.0] / n - 0.25) < 0.01 and abs(horizons[28.0] / n - 0.5) < 0.01


def test_seeded_order_is_a_permutation_that_repeats():
    a = traffic_gen.seeded_order(99, "clusters.shard0", 1250)
    assert a == traffic_gen.seeded_order(99, "clusters.shard0", 1250)
    assert sorted(a) == list(range(1250))
    assert a != traffic_gen.seeded_order(100, "clusters.shard0", 1250)
