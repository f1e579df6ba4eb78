"""Host-side trace compiler: scalar trace events -> dense device slabs.

The batched path's replacement for the reference's trace-to-event emission
(reference: src/simulator.rs:234-253): names are interned to slots once on the
host; payloads (capacities, requests, durations) are pre-staged into per-slot
arrays; the device sees only (time, kind, slot) triples.

A node re-created under its own name (a chaos recovery, or a trace that
removes a machine and adds it again) returns to ITS OWN slot wherever the two
incarnations are the same machine and lie windows apart (`_reusable_slot`):
incarnations of one name are never alive together, the slot then sits where
name order puts it, so exact score ties break as the scalar walk breaks them,
and the node axis stays the deployment's node count whatever the fault
schedule. Anything else gets a fresh slot, ordered after its elder
(`_node_slots_in_name_order`).
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from kubernetriks_tpu.batched.state import (
    DEFAULT_RAM_UNIT,
    EV_CREATE_NODE,
    EV_CREATE_POD,
    EV_NODE_CRASH,
    EV_NODE_RECOVER,
    EV_REMOVE_NODE,
    EV_REMOVE_POD,
)
from kubernetriks_tpu.batched.timerep import from_f64_np
from kubernetriks_tpu.core.events import (
    CreateNodeRequest,
    CreatePodGroupRequest,
    CreatePodRequest,
    RemoveNodeRequest,
    RemovePodRequest,
)
from kubernetriks_tpu.batched.pipeline import SOFT_TAINT_TOP_BIT, SOFT_WEIGHT_BITS
from kubernetriks_tpu.core.scheduler.plugins import node_taints
from kubernetriks_tpu.trace.interface import TraceEvents


@dataclass
class CompiledPodGroup:
    """Host-side pod-group table for the batched HPA: reserved slot range,
    targets, and the load curve compiled out of the nested YAML usage-model
    config (reference: src/core/resource_usage/interface.rs:13-18)."""

    name: str
    slot_start: int
    slot_count: int  # reserved slots = initial + multiplier x max_pod_count (ring-reused)
    max_pods: int
    initial: int
    creation_time: float
    target_cpu: float  # <=0 means unset
    target_ram: float
    cpu_units: List[Tuple[float, float]]  # (duration, load); [] = no model
    cpu_const: bool
    ram_units: List[Tuple[float, float]]
    ram_const: bool


def _compile_usage_model(model_config) -> Tuple[List[Tuple[float, float]], bool]:
    """ResourceUsageModelConfig -> (units, is_constant). A constant model's
    load IS the utilization; a pod_group model's load is divided by the live
    pod count (reference: src/core/resource_usage/{constant,pod_group}.rs)."""
    import yaml

    if model_config is None:
        return [], False
    parsed = yaml.safe_load(model_config.config)
    if model_config.model_name == "constant":
        return [(1.0, float(parsed["usage"]))], True
    if model_config.model_name == "pod_group":
        return [
            (float(u["duration"]), float(u["total_load"])) for u in parsed
        ], False
    raise ValueError(f"unknown usage model {model_config.model_name!r}")


# What one build's static spread table holds (ops/scheduler_kernel.py keeps a
# workload's per-domain match counts in ONE (8, 128) vreg tile and a pod's
# match bits in an int32): a trace past either is refused by name.
SPREAD_MAX_WORKLOADS = 16
SPREAD_MAX_DOMAINS = 8


@dataclass
class CompiledSpread:
    """One cluster's topology-spread vocabulary, interned (PodTopologySpread,
    core/scheduler/plugins.py): strings never reach the device. Present only
    where a pod of the trace carries a constraint."""

    topology_key: str
    domains: List[str]  # sorted label values; index = domain
    # (sorted selector items, maxSkew), sorted; index = workload
    workloads: List[Tuple[Tuple[Tuple[str, str], ...], int]]
    node_domain: np.ndarray  # (N,) int32 domain of the slot's node, -1 without the key
    pod_group: np.ndarray  # (P,) int32 workload whose constraint the pod carries, -1 none
    pod_bits: np.ndarray  # (P,) int32 bit g set: the pod's labels satisfy workload g's selector
    max_skew: np.ndarray  # (G,) int32


def _compile_spread(node_labels, pods, n_nodes: int) -> Optional[CompiledSpread]:
    """Intern the labels and constraints of one trace. `node_labels`: the
    label dict of each node slot; `pods`: the Pod of each pod slot (None for
    a slot no CreatePod names). Raises what PodTopologySpread refuses."""
    from kubernetriks_tpu.core.scheduler.plugins import (
        selector_matches,
        supported_spread_constraint,
    )

    constraints = [None if pod is None else supported_spread_constraint(pod) for pod in pods]
    carried = [c for c in constraints if c is not None]
    if not carried:
        return None
    keys = sorted({c.topology_key for c in carried})
    if len(keys) > 1:
        raise ValueError(
            f"topology-spread constraints over more than one topologyKey in one trace ({keys}) are "
            "not supported: the batched build holds one domain plane a node"
        )
    key = keys[0]

    def triple(c):
        return tuple(sorted(c.match_labels.items())), int(c.max_skew)

    workloads = sorted({triple(c) for c in carried})
    domains = sorted({labels[key] for labels in node_labels if key in labels})
    if len(workloads) > SPREAD_MAX_WORKLOADS or len(domains) > SPREAD_MAX_DOMAINS:
        raise ValueError(
            f"{len(workloads)} spread workloads over {len(domains)} domains of {key!r}: more workloads or "
            f"domains than the build's static table holds ({SPREAD_MAX_WORKLOADS} workloads, "
            f"{SPREAD_MAX_DOMAINS} domains)"
        )
    domain_of = {value: i for i, value in enumerate(domains)}
    workload_of = {w: g for g, w in enumerate(workloads)}
    bits_of_labels: Dict[Tuple, int] = {}

    def bits(labels: Dict[str, str]) -> int:
        memo = tuple(sorted(labels.items()))
        got = bits_of_labels.get(memo)
        if got is None:
            got = bits_of_labels[memo] = sum(
                1 << g for g, (selector, _) in enumerate(workloads) if selector_matches(dict(selector), labels)
            )
        return got

    return CompiledSpread(
        topology_key=key,
        domains=domains,
        workloads=workloads,
        node_domain=np.asarray(
            [domain_of.get(labels.get(key), -1) for labels in node_labels], np.int32
        ).reshape(n_nodes),
        pod_group=np.asarray(
            [-1 if c is None else workload_of[triple(c)] for c in constraints], np.int32
        ).reshape(len(pods)),
        pod_bits=np.asarray(
            [0 if pod is None else bits(pod.metadata.labels) for pod in pods], np.int32
        ).reshape(len(pods)),
        max_skew=np.asarray([skew for _, skew in workloads], np.int32),
    )


# What one build's node-affinity and taint planes hold (batched/pipeline.py
# "NodeAffinity and TaintToleration"): a node's bits are ONE int32 a node, a
# bit a distinct expression or taint of the trace, bit 31 never a node's; a
# pod's terms one plane each. A trace past either is refused by name.
AFFINITY_MAX_BITS = 31
AFFINITY_MAX_TERMS = 4
AFFINITY_NO_TERM = np.int32(-(2**31))  # an unused term plane's mask: bit 31, which no node has
AFFINITY_NAMES_NODES = np.int32(-(2**31))  # in a pod's untolerated mask: the pod carries a term or a toleration
# The score halves (docs/PARITY.md "Scoring as kube-scheduler scores"): a
# pod's preferred terms are planes of their own, their weights (1-100) packed
# SOFT_WEIGHT_BITS a term into one word; the PreferNoSchedule taints take the
# node plane's top bits, SOFT_TAINT_TOP_BIT downwards, so that their count
# needs no table (pipeline.soft_raw_scores reads both).
SOFT_MAX_TERMS = 4


@dataclass
class CompiledAffinity:
    """One cluster's node selector expressions and taints, interned
    (NodeAffinity and TaintToleration, core/scheduler/plugins.py): strings
    never reach the device, and the expressions are evaluated against the
    nodes' labels here, on the host. Present only where a node of the trace
    carries a taint or a pod a nodeSelector, a node affinity or a toleration."""

    expressions: List[Tuple]  # distinct (key, operator, values); index = bit
    taints: List[Tuple[str, str]]  # distinct NoSchedule (key, value); bit = len(expressions) + index
    node_bits: np.ndarray  # (N,) int32 the expressions the node's labels satisfy and the taints it carries
    # (T, P) int32, T the most terms a pod of the trace has (at least 1): the
    # bits a node must carry to satisfy the pod's t-th term (0: every node
    # does; AFFINITY_NO_TERM: the pod has no t-th term).
    pod_terms: np.ndarray
    # (P,) int32 the taint bits the pod does NOT tolerate (a node passes iff
    # it carries none of them), with AFFINITY_NAMES_NODES where the pod
    # carries a selector, an affinity or a toleration.
    pod_forbid: np.ndarray
    # The score halves; None where the trace has neither a preferred term nor
    # a PreferNoSchedule taint. soft_taints: the distinct PreferNoSchedule
    # (key, value), the i-th at bit SOFT_TAINT_TOP_BIT - i of the node plane;
    # soft_terms (S, P): the bits a node must carry to match the pod's s-th
    # preferred term (AFFINITY_NO_TERM: it has none); soft_weights (P,): the
    # terms' weights, SOFT_WEIGHT_BITS bits each; soft_forbid (P,): the
    # PreferNoSchedule taint bits the pod does not tolerate.
    soft_taints: List[Tuple[str, str]] = field(default_factory=list)
    soft_terms: Optional[np.ndarray] = None
    soft_weights: Optional[np.ndarray] = None
    soft_forbid: Optional[np.ndarray] = None
    # The first pod with a preferred term and the first node with a
    # PreferNoSchedule taint, for the refusal of a profile that would ignore
    # them (engine._build_affinity).
    first_preferring: Optional[str] = None
    first_soft_tainted: Optional[str] = None


def _compile_affinity(
    node_labels, taints_of_node, pods, n_nodes: int, soft_taints_of_node=None, node_names=None
) -> Optional[CompiledAffinity]:
    """Intern one trace's expressions and taints to bits. `node_labels` /
    `taints_of_node` / `soft_taints_of_node`: the label dict and the
    NoSchedule and PreferNoSchedule (key, value) taints of each node slot;
    `pods`: the Pod of each pod slot (None for a slot no CreatePod names).
    Raises what NodeAffinity and TaintToleration refuse."""
    from kubernetriks_tpu.core.scheduler.plugins import (
        expression_matches,
        supported_node_terms,
        supported_preferred_terms,
        supported_tolerations,
        tolerates,
    )

    terms_of = [None if pod is None else supported_node_terms(pod) for pod in pods]
    preferred_of = [() if pod is None else supported_preferred_terms(pod) for pod in pods]
    tolerations_of = [() if pod is None else supported_tolerations(pod) for pod in pods]
    taints = sorted({t for ts in taints_of_node for t in ts})
    soft_taints_of_node = soft_taints_of_node or [()] * len(taints_of_node)
    soft_taints = sorted({t for ts in soft_taints_of_node for t in ts})
    soft = bool(soft_taints) or any(preferred_of)
    if not taints and not soft and not any(terms_of) and not any(tolerations_of):
        return None
    expressions = sorted(
        {e for terms in terms_of if terms for term in terms for e in term}
        | {e for preferred in preferred_of for _, term in preferred for e in term}
    )
    most_terms = max((len(terms) for terms in terms_of if terms), default=1)
    if len(expressions) + len(taints) + len(soft_taints) > AFFINITY_MAX_BITS:
        raise ValueError(
            f"{len(expressions)} distinct node selector expressions and {len(taints) + len(soft_taints)} "
            f"distinct taints in one "
            f"trace (the first: {(expressions + taints + soft_taints)[0]!r}): more than the node plane's "
            f"{AFFINITY_MAX_BITS} bits hold"
        )
    most_preferred = max((len(preferred) for preferred in preferred_of), default=0)
    if most_preferred > SOFT_MAX_TERMS:
        worst = next(pod for pod, p in zip(pods, preferred_of) if len(p) == most_preferred)
        raise ValueError(
            f"pod {worst.metadata.name!r}: {most_preferred} preferred terms, more than the "
            f"{SOFT_MAX_TERMS} preferred-term planes a build holds"
        )
    if most_terms > AFFINITY_MAX_TERMS:
        worst = next(pod for pod, terms in zip(pods, terms_of) if terms and len(terms) == most_terms)
        raise ValueError(
            f"pod {worst.metadata.name!r}: {most_terms} nodeSelectorTerms, more than the "
            f"{AFFINITY_MAX_TERMS} term planes a build holds"
        )
    bit_of = {e: np.int32(1 << i) for i, e in enumerate(expressions)}
    taint_bit = {t: np.int32(1 << (len(expressions) + i)) for i, t in enumerate(taints)}
    soft_bit = {t: np.int32(1 << (SOFT_TAINT_TOP_BIT - i)) for i, t in enumerate(soft_taints)}
    bits_of_labels: Dict[Tuple, int] = {}

    def label_bits(labels: Dict[str, str]) -> int:
        memo = tuple(sorted(labels.items()))
        got = bits_of_labels.get(memo)
        if got is None:
            got = bits_of_labels[memo] = sum(
                int(bit_of[e]) for e in expressions if expression_matches(e, labels)
            )
        return got

    node_bits = np.zeros(n_nodes, np.int32)
    for slot, (labels, carried, soft_carried) in enumerate(
        zip(node_labels, taints_of_node, soft_taints_of_node)
    ):
        node_bits[slot] = (
            label_bits(labels)
            + sum(int(taint_bit[t]) for t in carried)
            + sum(int(soft_bit[t]) for t in soft_carried)
        )
    pod_terms = np.full((most_terms, len(pods)), AFFINITY_NO_TERM, np.int32)
    pod_forbid = np.zeros(len(pods), np.int32)
    every_taint = sum(int(b) for b in taint_bit.values())
    forbid_of: Dict[Tuple, int] = {}
    for slot, (pod, terms, tolerations) in enumerate(zip(pods, terms_of, tolerations_of)):
        if terms is None:
            pod_terms[0, slot] = 0
        else:
            for t, term in enumerate(terms):
                pod_terms[t, slot] = sum(int(bit_of[e]) for e in term)
        if not tolerations:
            forbid = every_taint
        else:
            memo = tuple((t.key, t.operator, t.value, t.effect) for t in tolerations)
            forbid = forbid_of.get(memo)
            if forbid is None:
                forbid = forbid_of[memo] = sum(
                    int(taint_bit[t]) for t in taints if not tolerates(tolerations, t)
                )
        pod_forbid[slot] = forbid
        if pod is not None and pod.spec.names_nodes():
            pod_forbid[slot] |= AFFINITY_NAMES_NODES
    compiled = CompiledAffinity(
        expressions=expressions, taints=taints, node_bits=node_bits,
        pod_terms=pod_terms, pod_forbid=pod_forbid,
    )
    if not soft:
        return compiled
    compiled.soft_taints = soft_taints
    compiled.soft_terms = np.full((max(most_preferred, 1), len(pods)), AFFINITY_NO_TERM, np.int32)
    compiled.soft_weights = np.zeros(len(pods), np.int32)
    compiled.soft_forbid = np.zeros(len(pods), np.int32)
    every_soft = sum(int(b) for b in soft_bit.values())
    soft_forbid_of: Dict[Tuple, int] = {}
    for slot, (pod, preferred, tolerations) in enumerate(zip(pods, preferred_of, tolerations_of)):
        for s, (weight, term) in enumerate(preferred):
            compiled.soft_terms[s, slot] = sum(int(bit_of[e]) for e in term)
            compiled.soft_weights[slot] |= weight << (SOFT_WEIGHT_BITS * s)
        if preferred and compiled.first_preferring is None:
            compiled.first_preferring = pod.metadata.name
        memo = tuple((t.key, t.operator, t.value, t.effect) for t in tolerations)
        forbid = soft_forbid_of.get(memo)
        if forbid is None:
            forbid = soft_forbid_of[memo] = (
                every_soft
                if not tolerations
                else sum(int(soft_bit[t]) for t in soft_taints if not tolerates(tolerations, t, "PreferNoSchedule"))
            )
        compiled.soft_forbid[slot] = forbid
    for slot, carried in enumerate(soft_taints_of_node):
        if carried:
            compiled.first_soft_tainted = node_names[slot] if node_names else f"node slot {slot}"
            break
    return compiled


@dataclass
class CompiledClusterTrace:
    """One cluster's compiled trace + payload tables (numpy, host-side)."""

    ev_time: np.ndarray  # (E,) float64
    ev_kind: np.ndarray  # (E,) int32
    ev_slot: np.ndarray  # (E,) int32
    node_cap_cpu: np.ndarray  # (N,) int32
    node_cap_ram: np.ndarray  # (N,) int32 (ram units)
    pod_req_cpu: np.ndarray  # (P,) int32
    pod_req_ram: np.ndarray  # (P,) int32 (ram units)
    pod_duration: np.ndarray  # (P,) float64 (-1 for long-running)
    node_names: List[str] = field(default_factory=list)
    pod_names: List[str] = field(default_factory=list)
    pod_groups: List[CompiledPodGroup] = field(default_factory=list)
    # (K,) sampled repair span of each chaos-engine crash event, in event
    # order (a slot may crash more than once: a recovery returns to it);
    # None when no faults were injected.
    crash_downtime_s: Optional[np.ndarray] = None
    # Interned labels and topology-spread constraints; None where no pod of
    # the trace carries a constraint (labels alone intern nothing).
    spread: Optional[CompiledSpread] = None
    # Interned node selector expressions and taints; None where no node of
    # the trace carries a taint and no pod a selector, an affinity or a
    # toleration.
    affinity: Optional[CompiledAffinity] = None
    # The first node or pod whose RAM is not a whole number of ram_unit bytes
    # ("node 'x'" / "pod 'y'"), None where every one is: the device holds the
    # rounded number, which an integer score would tell from the bytes
    # (engine: refused under an integer profile).
    inexact_ram: Optional[str] = None

    @property
    def n_events(self) -> int:
        return len(self.ev_time)

    @property
    def n_nodes(self) -> int:
        return len(self.node_cap_cpu)

    @property
    def n_pods(self) -> int:
        return len(self.pod_req_cpu)


def _event_time_shifts(config) -> Tuple[float, float, float]:
    """Per-kind event-time shifts composing the scalar path's control-plane
    hop chains (SURVEY.md §3.2/3.4): (create_node, remove_node, remove_pod)."""
    if config is None:
        return 0.0, 0.0, 0.0
    return (
        3.0 * config.as_to_ps_network_delay + config.ps_to_sched_network_delay,
        2.0 * config.as_to_ps_network_delay + config.as_to_node_network_delay,
        config.as_to_ps_network_delay,
    )

_NODE_EVENT_KINDS = (EV_CREATE_NODE, EV_REMOVE_NODE, EV_NODE_CRASH, EV_NODE_RECOVER)


def _slot_reuse_clock(config):
    """(window_of, free_chain) for deciding when a removed node's slot may
    hold the node again: `window_of(t)` is the scheduling window an effect
    time falls in, None where there is no config to read the window length
    from (every re-creation then takes a fresh slot). Event application is
    window-granular (a slot created and removed in one window ends dead),
    and a finished pod's requests reach the scheduler's cache one
    pending-free chain after the node let them go (state.make_step_constants,
    delta_free_visible): the slot's allocatable must not be rebuilt before
    the last such free of the dead incarnation has been applied, so the
    re-creation has to fall in a window after the one that holds
    removal + free_chain."""
    if config is None:
        return None, 0.0
    from kubernetriks_tpu.batched.state import make_step_constants

    interval = float(config.scheduling_cycle_interval)

    def window_of(ts: float) -> int:
        return int(from_f64_np(np.float64(ts), interval)[0])

    return window_of, float(make_step_constants(config).delta_free_visible or 0.0)


def _reusable_slot(dead, window, cap, labels, node_cap_cpu, node_cap_ram, node_labels):
    """The slot of the name's last incarnation, if a creation in `window`
    may return to it: windows apart (`dead` is (slot, first window allowed),
    _slot_reuse_clock) and the same machine (capacity, and labels with
    taints, are per-slot tables); else None."""
    if dead is None or window < dead[1]:
        return None
    slot = dead[0]
    if (node_cap_cpu[slot], node_cap_ram[slot]) != cap or node_labels[slot] != labels:
        return None
    return slot


def _node_slots_in_name_order(trace: CompiledClusterTrace) -> CompiledClusterTrace:
    """Renumber node slots so that slot order IS sorted-name order.

    The scheduling kernels break a score tie by the highest node slot
    (ops/scheduler_kernel._fit_score_place), the scalar scheduler by the last
    node in sorted-name order (core/scheduler/kube_scheduler.py). The two
    agree only when slots count as names sort: zero-padded names do, the
    Alibaba trace's bare machine ids (`alibaba_node_9` after
    `alibaba_node_10`) do not, and on a mostly empty cluster nearly every
    decision is such a tie. Slots are interned in creation order, so the
    compilers renumber once at the end; a re-created name keeps its
    creation order among its own slots. Identity (the same object) for
    names that already sort as they were created."""
    names = trace.node_names
    order = sorted(range(len(names)), key=lambda slot: (names[slot], slot))
    if order == list(range(len(names))):
        return trace
    new_slot = np.empty(len(names), np.int32)
    new_slot[order] = np.arange(len(names), dtype=np.int32)
    is_node_event = np.isin(trace.ev_kind, _NODE_EVENT_KINDS)
    return dataclasses.replace(
        trace,
        ev_slot=np.where(
            is_node_event, new_slot[np.clip(trace.ev_slot, 0, len(names) - 1)], trace.ev_slot
        ).astype(np.int32),
        node_cap_cpu=trace.node_cap_cpu[order],
        node_cap_ram=trace.node_cap_ram[order],
        node_names=[names[slot] for slot in order],
        spread=None
        if trace.spread is None
        else dataclasses.replace(trace.spread, node_domain=trace.spread.node_domain[order]),
        affinity=None
        if trace.affinity is None
        else dataclasses.replace(trace.affinity, node_bits=trace.affinity.node_bits[order]),
    )


def compile_cluster_trace(
    cluster_events: TraceEvents,
    workload_events: TraceEvents,
    config=None,
    ram_unit: int = DEFAULT_RAM_UNIT,
    pod_group_slot_multiplier: int = 2,
) -> CompiledClusterTrace:
    """Merge + time-sort both traces (stable: cluster events first at equal
    times, matching the scalar initialize() emission order, reference:
    src/simulator.rs:234-253) and intern names to slots.

    Event times are shifted to their *effect* times, composing the scalar
    path's control-plane hop chains (SURVEY.md §3.2/3.4):
    - CreateNode at t becomes schedulable when the scheduler caches it:
      t + 3*as_to_ps + ps_to_sched
    - RemoveNode at t takes effect when the node component cancels its pods:
      t + 2*as_to_ps + as_to_node
    - RemovePod at t takes effect when storage drops it: t + as_to_ps
    - CreatePod stays at t; its queue-entry time is shifted on-device by
      delta_pod_enqueue.
    """
    shift_create_node, shift_remove_node, shift_remove_pod = _event_time_shifts(config)

    # A node's remove effect can never precede its create effect: when the
    # per-kind shifts are asymmetric (shift_create > shift_remove) a same-tick
    # create+remove pair would otherwise reorder after shifting. Clamp the
    # remove to the create's effect time; the stable (time, order) sort then
    # keeps create first (trace file order at equal times).
    node_create_effect: Dict[str, float] = {}
    merged: List[Tuple[float, int, object]] = []
    for order, events in ((0, cluster_events), (1, workload_events)):
        for ts, event in events:
            shifted = float(ts)
            if isinstance(event, CreateNodeRequest):
                shifted += shift_create_node
                # Latest create wins: re-creations of a name clamp their own
                # subsequent remove (cluster events arrive in trace order).
                node_create_effect[event.node.metadata.name] = shifted
            elif isinstance(event, RemoveNodeRequest):
                shifted = max(
                    shifted + shift_remove_node,
                    node_create_effect.get(event.node_name, -np.inf),
                )
            elif isinstance(event, RemovePodRequest):
                shifted += shift_remove_pod
            merged.append((shifted, order, event))
    merged.sort(key=lambda item: (item[0], item[1]))

    ev_time: List[float] = []
    ev_kind: List[int] = []
    ev_slot: List[int] = []
    node_cap_cpu: List[int] = []
    node_cap_ram: List[int] = []
    node_names: List[str] = []
    live_node_slot: Dict[str, int] = {}
    pod_req_cpu: List[int] = []
    pod_req_ram: List[int] = []
    pod_duration: List[float] = []
    pod_names: List[str] = []
    pod_slot: Dict[str, int] = {}
    pod_groups: List[CompiledPodGroup] = []
    crash_downtime_s: List[float] = []
    # (labels, NoSchedule taints, PreferNoSchedule taints) of each node slot:
    # what a slot's machine is beside its capacity.
    node_labels: List[Tuple[Dict[str, str], Tuple, Tuple]] = []
    pod_objects: List[object] = []
    # name -> (slot, the first window in which the slot may be created
    # again) of the name's last, removed incarnation.
    dead_node_slot: Dict[str, Tuple[int, int]] = {}
    window_of, free_chain = _slot_reuse_clock(config)
    inexact_ram: Optional[str] = None

    for ts, _, event in merged:
        if isinstance(event, CreateNodeRequest):
            # A chaos recovery differs from a creation in its event kind
            # alone, for fault accounting.
            node = event.node
            name = node.metadata.name
            cap = (int(node.status.capacity.cpu), int(node.status.capacity.ram) // ram_unit)
            if inexact_ram is None and int(node.status.capacity.ram) % ram_unit:
                inexact_ram = f"node {name!r}"
            labelled = (
                node.metadata.labels, node_taints(node), node_taints(node, "PreferNoSchedule")
            )
            slot = None
            if window_of is not None:
                slot = _reusable_slot(
                    dead_node_slot.pop(name, None), window_of(ts),
                    cap, labelled, node_cap_cpu, node_cap_ram, node_labels,
                )
            if slot is None:
                slot = len(node_cap_cpu)
                node_cap_cpu.append(cap[0])
                node_cap_ram.append(cap[1])
                node_names.append(name)
                node_labels.append(labelled)
            live_node_slot[name] = slot
            ev_time.append(ts)
            ev_kind.append(EV_NODE_RECOVER if event.recovered else EV_CREATE_NODE)
            ev_slot.append(slot)
        elif isinstance(event, RemoveNodeRequest):
            slot = live_node_slot.pop(event.node_name)
            if window_of is not None:
                dead_node_slot[event.node_name] = (slot, window_of(ts + free_chain) + 1)
            ev_time.append(ts)
            if event.crashed:
                ev_kind.append(EV_NODE_CRASH)
                crash_downtime_s.append(float(event.downtime_s))
            else:
                ev_kind.append(EV_REMOVE_NODE)
            ev_slot.append(slot)
        elif isinstance(event, CreatePodRequest):
            pod = event.pod
            slot = len(pod_req_cpu)
            requests = pod.spec.resources.requests
            pod_req_cpu.append(int(requests.cpu))
            pod_req_ram.append(-(-int(requests.ram) // ram_unit))  # ceil
            if inexact_ram is None and int(requests.ram) % ram_unit:
                inexact_ram = f"pod {pod.metadata.name!r}"
            duration = pod.spec.running_duration
            pod_duration.append(-1.0 if duration is None else float(duration))
            pod_names.append(pod.metadata.name)
            pod_objects.append(pod)
            pod_slot[pod.metadata.name] = slot
            ev_time.append(ts)
            ev_kind.append(EV_CREATE_POD)
            ev_slot.append(slot)
        elif isinstance(event, RemovePodRequest):
            ev_time.append(ts)
            ev_kind.append(EV_REMOVE_POD)
            ev_slot.append(pod_slot[event.pod_name])
        elif isinstance(event, CreatePodGroupRequest):
            group = event.pod_group
            template = group.pod_template
            assert template.spec.running_duration is None, (
                "Pod groups with specified duration are not supported. "
                "Only long running services."
            )
            umc = group.resources_usage_model_config
            cpu_units, cpu_const = _compile_usage_model(
                umc.cpu_config if umc else None
            )
            ram_units, ram_const = _compile_usage_model(
                umc.ram_config if umc else None
            )
            slot_start = len(pod_req_cpu)
            # The group's slots form a ring (autoscale.py hpa_pass): head/tail
            # wrap modulo slot_count, so churn reuses freed slots. The reserve
            # needs initial + multiplier*max so that (a) all initial pods fit
            # alongside a full scale-up window and (b) a slot is never
            # rewrapped while its previous occupant is still terminating.
            slot_count = group.initial_pod_count + (
                pod_group_slot_multiplier * group.max_pod_count
            )
            requests = template.spec.resources.requests
            if template.spec.topology_spread_constraints:
                raise ValueError(
                    f"pod group {group.name!r}: topology-spread constraints on an HPA pod group's template "
                    "are not supported (pods made at run time would need labels of their own)"
                )
            if template.spec.names_nodes():
                raise ValueError(
                    f"pod group {group.name!r}: a nodeSelector, a node affinity or tolerations on an HPA pod "
                    "group's template are not supported (pods made at run time would need planes of their own)"
                )
            pod_objects.extend([None] * slot_count)
            for i in range(slot_count):
                pod_req_cpu.append(int(requests.cpu))
                pod_req_ram.append(-(-int(requests.ram) // ram_unit))
                pod_duration.append(-1.0)
                name = f"{group.name}_{i}"
                pod_slot[name] = len(pod_names)
                pod_names.append(name)
            # Initial pods hit the api server at the group's trace time
            # (reference expansion: src/core/api_server.rs:405-455).
            for i in range(group.initial_pod_count):
                ev_time.append(ts)
                ev_kind.append(EV_CREATE_POD)
                ev_slot.append(slot_start + i)
            targets = group.target_resources_usage
            pod_groups.append(
                CompiledPodGroup(
                    name=group.name,
                    slot_start=slot_start,
                    slot_count=slot_count,
                    max_pods=group.max_pod_count,
                    initial=group.initial_pod_count,
                    creation_time=float(ts),
                    target_cpu=float(targets.cpu_utilization or 0.0),
                    target_ram=float(targets.ram_utilization or 0.0),
                    cpu_units=cpu_units,
                    cpu_const=cpu_const,
                    ram_units=ram_units,
                    ram_const=ram_const,
                )
            )
        else:
            raise ValueError(
                f"batched path does not support trace event {type(event).__name__}"
            )

    spread = _compile_spread(
        [labels for labels, _, _ in node_labels], pod_objects, len(node_cap_cpu)
    )
    if spread is not None and pod_groups:
        raise ValueError(
            "topology-spread constraints together with HPA pod groups are not supported (pods made at "
            "run time would need labels of their own)"
        )
    affinity = _compile_affinity(
        [labels for labels, _, _ in node_labels],
        [taints for _, taints, _ in node_labels],
        pod_objects,
        len(node_cap_cpu),
        [soft for _, _, soft in node_labels],
        node_names,
    )
    if affinity is not None and pod_groups:
        raise ValueError(
            "node taints, nodeSelectors, node affinities or tolerations together with HPA pod groups are "
            "not supported (pods made at run time would need planes of their own)"
        )

    return _node_slots_in_name_order(CompiledClusterTrace(
        ev_time=np.asarray(ev_time, np.float64),
        ev_kind=np.asarray(ev_kind, np.int32),
        ev_slot=np.asarray(ev_slot, np.int32),
        node_cap_cpu=np.asarray(node_cap_cpu, np.int32).reshape(-1),
        node_cap_ram=np.asarray(node_cap_ram, np.int32).reshape(-1),
        pod_req_cpu=np.asarray(pod_req_cpu, np.int32).reshape(-1),
        pod_req_ram=np.asarray(pod_req_ram, np.int32).reshape(-1),
        pod_duration=np.asarray(pod_duration, np.float64).reshape(-1),
        node_names=node_names,
        pod_names=pod_names,
        pod_groups=pod_groups,
        crash_downtime_s=np.asarray(crash_downtime_s, np.float64) if crash_downtime_s else None,
        spread=spread,
        affinity=affinity,
        inexact_ram=inexact_ram,
    ))


def segment_pod_slots(
    compiled: Sequence[CompiledClusterTrace],
) -> Tuple[List[CompiledClusterTrace], int]:
    """Renumber pod slots into the segmented layout the sliding pod window
    needs to coexist with HPA pod groups: plain (non-group) pods occupy
    global slots [0, T) in their original event order, pod-group reserved
    ring slots occupy [T, ...), where T is the batch-wide max plain-pod
    count. Group pods are long-running services — they would block the
    window's terminal-prefix shift forever — so the window slides only over
    the plain segment while the ring slots stay device-resident.

    Padding slots inside [plain_count, T) get empty names, zero requests and
    service duration; they are never targeted by any event. Event ORDER (and
    hence queue_seq assignment) is unchanged — only slot numbering moves, so
    the only behavioral deviation is the slot-order stand-in used for
    same-window reschedule ranking (docs/PARITY.md).

    Returns (renumbered traces, T). Identity (same objects) when no trace
    has pod groups.
    """
    if not any(c.pod_groups for c in compiled):
        return list(compiled), max((c.n_pods for c in compiled), default=0)

    group_masks = []
    for c in compiled:
        is_group = np.zeros(c.n_pods, bool)
        for g in c.pod_groups:
            is_group[g.slot_start : g.slot_start + g.slot_count] = True
        group_masks.append(is_group)
    T = max(int((~m).sum()) for m in group_masks)

    out: List[CompiledClusterTrace] = []
    for c, is_group in zip(compiled, group_masks):
        if c.n_pods == 0:
            # Nothing to renumber (and new_slot would be empty while node
            # events still populate ev_slot); pad_and_batch aligns widths.
            out.append(c)
            continue
        R = int(is_group.sum())
        L = T + R
        plain_ord = np.cumsum(~is_group) - 1
        group_ord = np.cumsum(is_group) - 1
        new_slot = np.where(is_group, T + group_ord, plain_ord).astype(np.int32)

        req_cpu = np.zeros(L, np.int32)
        req_ram = np.zeros(L, np.int32)
        duration = np.full(L, -1.0, np.float64)
        names = [""] * L
        req_cpu[new_slot] = c.pod_req_cpu
        req_ram[new_slot] = c.pod_req_ram
        duration[new_slot] = c.pod_duration
        for old, new in enumerate(new_slot):
            names[new] = c.pod_names[old]

        is_pod_ev = (c.ev_kind == EV_CREATE_POD) | (c.ev_kind == EV_REMOVE_POD)
        ev_slot = np.where(
            is_pod_ev, new_slot[np.clip(c.ev_slot, 0, c.n_pods - 1)], c.ev_slot
        ).astype(np.int32)

        groups = [
            dataclasses.replace(g, slot_start=T + int(group_ord[g.slot_start]))
            for g in c.pod_groups
        ]
        out.append(
            CompiledClusterTrace(
                ev_time=c.ev_time,
                ev_kind=c.ev_kind,
                ev_slot=ev_slot,
                node_cap_cpu=c.node_cap_cpu,
                node_cap_ram=c.node_cap_ram,
                pod_req_cpu=req_cpu,
                pod_req_ram=req_ram,
                pod_duration=duration,
                node_names=c.node_names,
                pod_names=names,
                pod_groups=groups,
                crash_downtime_s=c.crash_downtime_s,
            )
        )
    return out, T


def pad_and_batch(
    compiled: Sequence[CompiledClusterTrace],
    n_nodes: Optional[int] = None,
    n_pods: Optional[int] = None,
    n_events: Optional[int] = None,
) -> Tuple[np.ndarray, ...]:
    """Stack per-cluster compilations into (C, ...) arrays, padding slots and
    events (pad events: kind=EV_NONE, time=+inf). The last array is the
    chaos engine's downtime table, (C, K + 1) float32: entry k the summed
    sampled repair spans of a cluster's first k crash events (summed in
    float64), K the most crash events of any cluster; None where no trace
    carries one."""
    C = len(compiled)
    N = n_nodes if n_nodes is not None else max((c.n_nodes for c in compiled), default=0)
    P = n_pods if n_pods is not None else max((c.n_pods for c in compiled), default=0)
    E = n_events if n_events is not None else max((c.n_events for c in compiled), default=0)
    # +1: always keep a (time=+inf, EV_NONE) sentinel after the last real event.
    N, P, E = max(N, 1), max(P, 1), max(E, 0) + 1

    ev_time = np.full((C, E), np.inf, np.float64)
    ev_kind = np.zeros((C, E), np.int32)
    ev_slot = np.zeros((C, E), np.int32)
    node_cap_cpu = np.zeros((C, N), np.int32)
    node_cap_ram = np.zeros((C, N), np.int32)
    pod_req_cpu = np.zeros((C, P), np.int32)
    pod_req_ram = np.zeros((C, P), np.int32)
    pod_duration = np.full((C, P), -1.0, np.float64)
    K = max((len(c.crash_downtime_s) for c in compiled if c.crash_downtime_s is not None), default=0)
    crash_downtime_cum = np.zeros((C, K + 1), np.float64) if K else None

    for i, c in enumerate(compiled):
        ev_time[i, : c.n_events] = c.ev_time
        ev_kind[i, : c.n_events] = c.ev_kind
        ev_slot[i, : c.n_events] = c.ev_slot
        node_cap_cpu[i, : c.n_nodes] = c.node_cap_cpu
        node_cap_ram[i, : c.n_nodes] = c.node_cap_ram
        pod_req_cpu[i, : c.n_pods] = c.pod_req_cpu
        pod_req_ram[i, : c.n_pods] = c.pod_req_ram
        pod_duration[i, : c.n_pods] = c.pod_duration
        if c.crash_downtime_s is not None:
            total = np.cumsum(c.crash_downtime_s)
            crash_downtime_cum[i, 1 : len(total) + 1] = total
            crash_downtime_cum[i, len(total) + 1 :] = total[-1]

    return (
        ev_time,
        ev_kind,
        ev_slot,
        node_cap_cpu,
        node_cap_ram,
        pod_req_cpu,
        pod_req_ram,
        pod_duration,
        None if crash_downtime_cum is None else crash_downtime_cum.astype(np.float32),
    )


def compile_from_arrays(
    cluster_arrays,
    workload_arrays,
    config=None,
    ram_unit: int = DEFAULT_RAM_UNIT,
) -> CompiledClusterTrace:
    """Dense-array fast path: native-feeder output -> CompiledClusterTrace
    without materializing per-event Python objects.

    Semantically identical to compile_cluster_trace() over
    {cluster,workload}_events_from_arrays(...) — the equality is asserted in
    tests/test_native_feeder.py. Node events (small) run through a Python
    loop; pod events (the multi-million-row axis on Alibaba traces) are
    vectorized numpy end to end.

    cluster_arrays: kubernetriks_tpu.trace.feeder.ClusterArrays or None.
    workload_arrays: kubernetriks_tpu.trace.feeder.WorkloadArrays.
    """
    shift_create_node, shift_remove_node, _ = _event_time_shifts(config)

    # --- node events (loop; N is small) ------------------------------------
    node_cap_cpu: List[int] = []
    node_cap_ram: List[int] = []
    node_names: List[str] = []
    live_node_slot: Dict[int, int] = {}
    c_time: List[float] = []
    c_kind: List[int] = []
    c_slot: List[int] = []
    node_create_effect: Dict[int, float] = {}
    if cluster_arrays is not None:
        for i in range(len(cluster_arrays.ts)):
            mid = int(cluster_arrays.machine_id[i])
            if int(cluster_arrays.kind[i]) == 0:
                slot = len(node_cap_cpu)
                node_cap_cpu.append(int(cluster_arrays.cpu_millicores[i]))
                node_cap_ram.append(int(cluster_arrays.ram_bytes[i]) // ram_unit)
                node_names.append(cluster_arrays.node_name(i))
                live_node_slot[mid] = slot
                shifted = float(cluster_arrays.ts[i]) + shift_create_node
                node_create_effect[mid] = shifted
                c_time.append(shifted)
                c_kind.append(EV_CREATE_NODE)
                c_slot.append(slot)
            else:
                # Clamp like compile_cluster_trace: a remove's effect never
                # precedes its node's create effect under asymmetric shifts.
                c_time.append(
                    max(
                        float(cluster_arrays.ts[i]) + shift_remove_node,
                        node_create_effect.get(mid, -np.inf),
                    )
                )
                c_kind.append(EV_REMOVE_NODE)
                c_slot.append(live_node_slot.pop(mid))

    # --- pod events (vectorized) -------------------------------------------
    P = len(workload_arrays.start_ts)
    w_time = workload_arrays.start_ts.astype(np.float64)
    pod_req_cpu = workload_arrays.cpu_millicores.astype(np.int32)
    pod_req_ram = (-(-workload_arrays.ram_bytes // ram_unit)).astype(np.int32)
    pod_duration = workload_arrays.duration.astype(np.float64)
    pod_names = [workload_arrays.pod_name(i) for i in range(P)]

    # --- stable merge: primary time, cluster events before workload at ties
    times = np.concatenate([np.asarray(c_time, np.float64), w_time])
    kinds = np.concatenate(
        [np.asarray(c_kind, np.int32), np.full(P, EV_CREATE_POD, np.int32)]
    )
    slots = np.concatenate(
        [np.asarray(c_slot, np.int32), np.arange(P, dtype=np.int32)]
    )
    source = np.concatenate(
        [np.zeros(len(c_time), np.int8), np.ones(P, np.int8)]
    )
    order = np.lexsort((source, times))  # stable within each source stream

    return _node_slots_in_name_order(CompiledClusterTrace(
        ev_time=times[order],
        ev_kind=kinds[order],
        ev_slot=slots[order],
        node_cap_cpu=np.asarray(node_cap_cpu, np.int32).reshape(-1),
        node_cap_ram=np.asarray(node_cap_ram, np.int32).reshape(-1),
        pod_req_cpu=pod_req_cpu.reshape(-1),
        pod_req_ram=pod_req_ram.reshape(-1),
        pod_duration=pod_duration.reshape(-1),
        node_names=node_names,
        pod_names=pod_names,
        pod_groups=[],
    ))


def _pad_cols(arr: np.ndarray, lo: int, width: int, fill, dtype) -> np.ndarray:
    """arr[:, lo:lo+width], right-padded with `fill` — the one padding
    rule of the staging column layout (see stage_segment)."""
    C = arr.shape[0]
    out = np.full((C, width), fill, dtype)
    src = arr[:, lo : lo + width]
    out[:, : src.shape[1]] = src
    return out


class PayloadSource:
    """Provider of the slide/staging PAYLOAD columns (pod requests +
    durations) for global plain-pod columns [lo, lo + width) — the seam
    that bounds the engine's steady-state host memory (ROADMAP #2):
    `segment` returns {"req_cpu", "req_ram", "duration"} (C, width)
    numpy arrays with the fresh-slot padding past the trace end (request
    0, duration -1.0 — the long-running-service sentinel the pair
    conversion encodes). ArrayPayloadSource wraps the resident
    whole-trace arrays (the build default, O(T) host); FeederPayloadSource
    materializes only the requested rows from a segment reader
    (trace.feeder.WorkloadSegmentReader), so after
    engine.attach_payload_source the resident payload drops to
    O(stage width) regardless of trace length. Thread-safety contract:
    `segment` is called from the streaming feeder's producer thread —
    implementations must be safe for one concurrent reader."""

    total_rows: int  # plain pod columns the source covers

    def segment(self, lo: int, width: int) -> Dict[str, np.ndarray]:
        raise NotImplementedError


class ArrayPayloadSource(PayloadSource):
    """Whole-trace arrays ({"req_cpu","req_ram","duration"} of shape
    (C, T)) — the resident default."""

    def __init__(self, full_pods: Dict[str, np.ndarray]) -> None:
        self.full_pods = full_pods
        self.total_rows = int(full_pods["req_cpu"].shape[1])

    def segment(self, lo: int, width: int) -> Dict[str, np.ndarray]:
        full = self.full_pods
        return {
            "req_cpu": _pad_cols(full["req_cpu"], lo, width, 0, np.int32),
            "req_ram": _pad_cols(full["req_ram"], lo, width, 0, np.int32),
            "duration": _pad_cols(
                full["duration"], lo, width, -1.0, np.float64
            ),
        }


class FeederPayloadSource(PayloadSource):
    """Bounded host payload over a row-range workload reader (native
    trace.feeder.WorkloadSegmentReader or the python-oracle
    WorkloadArraysReader): pod slots of a pure-workload trace are
    assigned in row order, so payload column i IS sorted workload row i,
    and a segment materializes exactly the requested rows. Conversions
    mirror compile_from_arrays (int32 millicores, ceil-div RAM
    quantization, float64 seconds) so a feeder-sourced slab is
    bit-identical to the resident arrays' slice. The compiled trace must
    carry no pod groups (group ring slots renumber the payload axis);
    the engine validates that at attach time."""

    def __init__(self, reader, n_clusters: int, ram_unit: int) -> None:
        self.reader = reader
        self.n_clusters = int(n_clusters)
        self.ram_unit = int(ram_unit)
        self.total_rows = len(reader)

    def segment(self, lo: int, width: int) -> Dict[str, np.ndarray]:
        C = self.n_clusters
        out = {
            "req_cpu": np.zeros((C, width), np.int32),
            "req_ram": np.zeros((C, width), np.int32),
            "duration": np.full((C, width), -1.0, np.float64),
        }
        n = max(0, min(width, self.total_rows - lo))
        if n:
            wa = self.reader.read(lo, n)
            out["req_cpu"][:, :n] = wa.cpu_millicores.astype(np.int32)[None, :]
            out["req_ram"][:, :n] = (
                -(-wa.ram_bytes // self.ram_unit)
            ).astype(np.int32)[None, :]
            out["duration"][:, :n] = wa.duration.astype(np.float64)[None, :]
        return out


def stage_segment(
    payload,
    create_win: np.ndarray,
    rank_full: Optional[np.ndarray],
    lo: int,
    width: int,
) -> Dict[str, np.ndarray]:
    """Staging-segment extraction for the superspan executor: numpy refill
    payload columns [lo, lo + width) of the trace's PLAIN pod segment, ready
    to become a device RefillStage (batched/state.py).

    Columns past the trace end get the SAME fresh-slot padding the host
    refill path produces — request 0, duration -1.0 (the long-running
    service sentinel the pair conversion encodes), INT32_MAX create window
    (never comes alive), BIG name rank — so a stage straddling the trace
    boundary slides bit-identically to the full-resident payload. The ONE
    owner of the staging column layout: the engine's whole-trace slide
    payload (_init_device_slide) and its bounded stage buffers (_make_stage)
    both assemble through here, so padding rules can never drift apart.
    Duration stays float64 SECONDS here; the caller converts to the device
    pair (duration_pair_np) after padding, exactly like the initial build.

    `payload` is a PayloadSource (or a bare {"req_cpu","req_ram",
    "duration"} whole-trace dict, wrapped on the fly): the request/
    duration columns come from it, while the create-window and name-rank
    tables — small int32 per-pod arrays the engine keeps resident for
    O(1) capacity lookups — are sliced here.
    """
    no_create = np.iinfo(np.int32).max
    BIG_RANK = np.int32(1 << 30)

    if not isinstance(payload, PayloadSource):
        payload = ArrayPayloadSource(payload)
    out = payload.segment(lo, width)
    out["create_win"] = _pad_cols(create_win, lo, width, no_create, np.int32)
    if rank_full is not None:
        out["rank"] = _pad_cols(rank_full, lo, width, BIG_RANK, np.int32)
    return out
