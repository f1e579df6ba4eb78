"""Seeded traffic for the `batch_jobs_kubescore` driver, beside
benchmark/pools_gen.py (which no later PR edits and whose classes carry hard
terms alone): the configuration's pools of machines with their labels and
taints (a pool's taint may be `PreferNoSchedule`), every node in a zone, and a
conditioned Poisson stream whose every arrival draws a class (by share), then a
request shape (uniformly from the class's list), then a duration (uniformly
from the class's range), the class giving the pod its nodeSelector, its
required and PREFERRED node affinity terms (a weight and a term each) and its
tolerations.

numpy, seeded from (`--seed`, "kubescore", cluster); imports nothing of the
program. Records are neutral data, pools_gen's, with one more key in a pod's
placement: "preferred": [(weight, [(key, operator, [values])])].
"""

from __future__ import annotations

from typing import Dict, List

import numpy as np

from benchmark import pools_gen
from benchmark.pools_gen import (  # noqa: F401  (the records' readers, the same)
    Record,
    cluster_records,
    placements_by_pod,
    taints_by_node,
    to_events,
)
from benchmark.traffic_gen import GIB, derive_seed


def class_placement(cls: Dict) -> Dict:
    placement = pools_gen.class_placement(cls)
    placement["preferred"] = [
        (int(weight), [(key, op, list(values)) for key, op, values in term])
        for weight, term in cls.get("preferred_terms") or []
    ]
    return placement


def workload_records(traffic: Dict, seed: int, cluster: int) -> List[Record]:
    """One cluster's stream, as pools_gen.workload_records draws it (exactly
    rate x horizon pods at sorted uniform instants, named in arrival order),
    from this generator's own seed stream and with the classes' soft terms."""
    if traffic.get("pod_group"):
        raise ValueError("kubescore_gen: the mix has no HPA pod group")
    plain, classes = traffic["plain"], traffic["classes"]
    rng = np.random.default_rng(derive_seed(seed, "kubescore", cluster))
    count = int(round(float(plain["rate_per_second"]) * float(plain["horizon_s"])))
    times = np.sort(rng.random(count) * float(plain["horizon_s"]))
    shares = np.asarray([float(c["share"]) for c in classes])
    which = rng.choice(len(classes), size=count, p=shares / shares.sum())
    shape_draw, duration_draw = rng.random(count), rng.random(count)
    placements = [class_placement(c) for c in classes]
    out = []
    for i in range(count):
        cls = classes[which[i]]
        shapes = cls["requests_cores_gib"]
        cores, gib = shapes[int(shape_draw[i] * len(shapes))]
        lo, hi = cls["duration_s"]
        out.append(
            (float(times[i]), "create_pod", f"pod_{i:05d}", int(round(cores * 1000)), int(gib * GIB),
             float(lo + (hi - lo) * duration_draw[i]), placements[which[i]])
        )
    return out


def class_of(traffic: Dict, placement: Dict) -> str:
    """The name of the class a record's placement came from."""
    for cls in traffic["classes"]:
        if class_placement(cls) == placement:
            return cls["name"]
    raise ValueError(f"kubescore_gen: no class has the placement {placement!r}")
