"""Default profile-based filter->score scheduling algorithm
(reference: src/core/scheduler/kube_scheduler.rs)."""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional

from kubernetriks_tpu.core.scheduler.interface import (
    PodSchedulingAlgorithm,
    ScheduleError,
    SchedulingFailure,
)
from kubernetriks_tpu.core.scheduler.plugins import (
    BALANCED,
    BALANCED_ALLOCATION,
    FIT,
    FilterPlugin,
    INTEGER_SCORE_PLUGINS,
    LEAST_ALLOCATED,
    MOST_ALLOCATED,
    NODE_AFFINITY,
    NODE_RESOURCES_FIT,
    PLUGIN_REGISTRY,
    SchedulerCache,
    ScorePlugin,
    TAINT_TOLERATION,
    TOPOLOGY_SPREAD,
    ignores_preferences,
    node_taints,
    unscored_preferred_term,
    unscored_soft_taint,
)
from kubernetriks_tpu.core.types import Node, Pod

DEFAULT_SCHEDULER_NAME = "default_scheduler"


@dataclass
class Plugin:
    name: str
    weight: Optional[float] = None


@dataclass
class Plugins:
    filter: List[Plugin] = field(default_factory=list)
    score: List[Plugin] = field(default_factory=list)


@dataclass
class KubeSchedulerProfile:
    scheduler_name: str
    plugins: Plugins


@dataclass
class KubeSchedulerConfig:
    profiles: Dict[str, KubeSchedulerProfile] = field(default_factory=dict)


def default_kube_scheduler_config() -> KubeSchedulerConfig:
    """Fit filter + LeastAllocatedResources score at weight 1.0
    (reference: src/core/scheduler/kube_scheduler.rs:44-61)."""
    return kube_scheduler_config_from_spec("default")


# Named profile specs — the shared catalogue both paths resolve: the scalar
# KubeScheduler builds its plugin refs from these, and the batched device
# pipeline (kubernetriks_tpu/batched/pipeline.py) lowers the same specs into
# compiled kernel statics. Each value is (filter names, (scorer, weight)...).
NAMED_PROFILE_SPECS: Dict[str, tuple] = {
    # The reference default (kube_scheduler.rs:44-61): spread pods by free
    # share.
    "default": ((FIT,), ((LEAST_ALLOCATED, 1.0),)),
    # Best-fit packing — the policy the RL bimodal proof discovers: the
    # tightest-fitting node wins, keeping whole nodes free for large pods.
    "best_fit": ((FIT,), ((MOST_ALLOCATED, 1.0),)),
    # Weighted filter+score combination: pack first, but trade up to ~12.5
    # score points of tightness for an even cpu/ram drain.
    "balanced_packing": ((FIT,), ((MOST_ALLOCATED, 1.0), (BALANCED, 0.25))),
    # The default with kube-scheduler's zone-spread filter in front of the
    # scorer: pods that carry a topologySpreadConstraint (DoNotSchedule) are
    # held to it, pods without one schedule as under "default".
    "topology_spread": ((FIT, TOPOLOGY_SPREAD), ((LEAST_ALLOCATED, 1.0),)),
    # The default with the two filters by which pods name their nodes: a
    # nodeSelector or required node affinity against the nodes' labels, and
    # tolerations against their NoSchedule taints (node pools, a dedicated
    # pool behind a taint). Pods that carry none schedule as under "default"
    # on the untainted nodes.
    "node_pools": ((FIT, NODE_AFFINITY, TAINT_TOLERATION), ((LEAST_ALLOCATED, 1.0),)),
    # kube-scheduler's own default profile (docs/PARITY.md "Scoring as
    # kube-scheduler scores"): node_pools' filters, and its score plugins at
    # their default weights, integers normalised over the feasible nodes.
    # PodTopologySpread 2, InterPodAffinity 2 and ImageLocality 1 score every
    # node alike here (no pod may carry ScheduleAnyway or an inter-pod term,
    # no image is modelled) and are left out.
    "kube_default": (
        (FIT, NODE_AFFINITY, TAINT_TOLERATION),
        (
            (NODE_RESOURCES_FIT, 1.0),
            (BALANCED_ALLOCATION, 1.0),
            (NODE_AFFINITY, 2.0),
            (TAINT_TOLERATION, 3.0),
        ),
    ),
}

# The reference's scorers: float64 here, float32 (or the exact key) on the
# device. A profile scores by these or by INTEGER_SCORE_PLUGINS, not by both.
FLOAT_SCORE_PLUGINS = (LEAST_ALLOCATED, MOST_ALLOCATED, BALANCED)


def _check_score_kinds(filter_refs, score_refs) -> None:
    """Refuse, by name, a profile that adds kube-scheduler's integer scores
    to the reference's float ones, an integer scorer at a weight that is no
    positive integer, and the score half of a label plugin without its
    filter half."""
    integer = [p for p in score_refs if p.name in INTEGER_SCORE_PLUGINS]
    floats = [p.name for p in score_refs if p.name in FLOAT_SCORE_PLUGINS]
    if not integer:
        return
    if floats:
        raise ValueError(
            f"scheduler profile mixes kube-scheduler's integer scorers {[p.name for p in integer]} with the "
            f"reference's float scorers {floats}: integer scores are normalised to 0-100 and added by "
            "integer weights, the float ones are not; score by one kind"
        )
    for p in integer:
        weight = 1.0 if p.weight is None else p.weight
        if weight < 1 or weight != int(weight):
            raise ValueError(
                f"scheduler profile: integer score plugin {p.name!r} has weight {weight!r}; "
                "kube-scheduler's weights are positive integers"
            )
        if p.name in (NODE_AFFINITY, TAINT_TOLERATION) and p.name not in {f.name for f in filter_refs}:
            raise ValueError(
                f"scheduler profile scores by {p.name!r} without filtering by it: upstream's plugin is "
                "both halves; add it to `filters`"
            )


def kube_scheduler_config_from_spec(spec) -> KubeSchedulerConfig:
    """One profile spec -> KubeSchedulerConfig, accepted forms:

    - None                      -> the reference default profile;
    - "name"                    -> NAMED_PROFILE_SPECS lookup (loud on typos);
    - {"filters": [...],
       "score": [{"name":..., "weight":...}, ...]}
                                -> an explicit profile (weight defaults 1.0);
    - KubeSchedulerConfig       -> passed through.

    This is the ONE parser both backends use (the batched pipeline compiles
    its device profile from the config this returns), so a YAML
    `scheduler_profile:` block means the same thing everywhere."""
    if spec is None:
        spec = "default"
    if isinstance(spec, KubeSchedulerConfig):
        return spec
    if isinstance(spec, str):
        named = NAMED_PROFILE_SPECS.get(spec)
        if named is None:
            raise ValueError(
                f"unknown named scheduler profile {spec!r}; available: "
                f"{sorted(NAMED_PROFILE_SPECS)}"
            )
        filters, scores = named
        spec = {
            "filters": list(filters),
            "score": [{"name": n, "weight": w} for n, w in scores],
        }
    if not isinstance(spec, dict):
        raise TypeError(
            f"scheduler profile spec must be None, a named-profile string, "
            f"a mapping, or a KubeSchedulerConfig; got {type(spec).__name__}"
        )
    # Reject unknown keys LOUDLY: a typo like `scores:` would otherwise
    # yield a silently scoreless profile — the silent-wrong-profile
    # failure mode this subsystem exists to kill.
    unknown = set(spec) - {"filters", "score"}
    if unknown:
        raise ValueError(
            f"scheduler profile spec has unknown key(s) {sorted(unknown)}; "
            "expected 'filters' (list of filter plugin names) and 'score' "
            "(list of {name, weight} scorer refs)"
        )
    # Default the filter chain to Fit only when the key is ABSENT: an
    # explicit `filters: []` is a coherent profile (score every alive
    # node, no feasibility filter) and must not be silently substituted.
    filters_spec = spec.get("filters", [FIT])
    if filters_spec is None:
        filters_spec = [FIT]
    filter_refs = [Plugin(name=str(name)) for name in filters_spec]
    score_refs = []
    for entry in spec.get("score") or []:
        if isinstance(entry, str):
            entry = {"name": entry}
        bad = set(entry) - {"name", "weight"}
        if bad:
            raise ValueError(
                f"scheduler profile score entry {entry!r} has unknown "
                f"key(s) {sorted(bad)}; expected 'name' and optional "
                "'weight'"
            )
        score_refs.append(
            Plugin(
                name=str(entry["name"]),
                weight=float(entry.get("weight", 1.0)),
            )
        )
    _check_score_kinds(filter_refs, score_refs)
    profile = KubeSchedulerProfile(
        scheduler_name=DEFAULT_SCHEDULER_NAME,
        plugins=Plugins(filter=filter_refs, score=score_refs),
    )
    return KubeSchedulerConfig(profiles={DEFAULT_SCHEDULER_NAME: profile})


class KubeScheduler(PodSchedulingAlgorithm):
    def __init__(self, config: Optional[KubeSchedulerConfig] = None) -> None:
        self.config = config or default_kube_scheduler_config()

    def schedule_one(
        self, pod: Pod, nodes: Dict[str, Node], cache: Optional[SchedulerCache] = None
    ) -> str:
        """Filter then weighted-score over name-sorted nodes; argmax keeps the
        reference's `>=` tie-break: among equal max scores the last node in
        sorted-name order wins (reference: src/core/scheduler/kube_scheduler.rs:63-152).
        `cache` is the scheduler's cache the filters may read; a caller
        without one gets a cache of these nodes and no placed pod."""
        if cache is None:
            cache = SchedulerCache(nodes=nodes)
        requests = pod.spec.resources.requests
        if requests.cpu == 0 and requests.ram == 0:
            raise SchedulingFailure(ScheduleError.REQUESTED_RESOURCES_ARE_ZEROS)
        if not nodes:
            raise SchedulingFailure(ScheduleError.NO_NODES_IN_CLUSTER)

        scheduler_name = pod.metadata.labels.get("scheduler_name", DEFAULT_SCHEDULER_NAME)
        profile = self.config.profiles[scheduler_name]

        filtered_nodes = [nodes[name] for name in sorted(nodes)]
        no_terms, no_taints = ignores_preferences(
            {ref.name for ref in profile.plugins.filter}, {ref.name for ref in profile.plugins.score}
        )
        if no_terms and pod.spec.node_affinity is not None and pod.spec.node_affinity.preferred:
            raise unscored_preferred_term(pod.metadata.name)
        if no_taints:
            for node in filtered_nodes:
                if node_taints(node, "PreferNoSchedule"):
                    raise unscored_soft_taint(node.metadata.name)
        for filter_ref in profile.plugins.filter:
            plugin = PLUGIN_REGISTRY[filter_ref.name]
            assert isinstance(plugin, FilterPlugin), (
                f"{filter_ref.name!r} plugin is not a FilterPlugin"
            )
            filtered_nodes = plugin.filter(pod, filtered_nodes, cache)

        if not filtered_nodes:
            raise SchedulingFailure(ScheduleError.NO_SUFFICIENT_RESOURCES)

        node_scores: Dict[str, float] = {
            node.metadata.name: 0.0 for node in filtered_nodes
        }
        for scorer_ref in profile.plugins.score:
            plugin = PLUGIN_REGISTRY[scorer_ref.name]
            assert isinstance(plugin, ScorePlugin), (
                f"{scorer_ref.name!r} plugin is not a ScorePlugin"
            )
            weight = 1.0 if scorer_ref.weight is None else scorer_ref.weight
            scores = plugin.normalize([plugin.score(pod, node) for node in filtered_nodes])
            for node, score in zip(filtered_nodes, scores):
                node_scores[node.metadata.name] += score * weight

        assigned_node = filtered_nodes[0].metadata.name
        max_score = node_scores[assigned_node]
        for node_name in sorted(node_scores):
            if node_scores[node_name] >= max_score:
                assigned_node = node_name
                max_score = node_scores[node_name]
        return assigned_node
