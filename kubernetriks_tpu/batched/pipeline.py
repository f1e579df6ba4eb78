"""Compiled scheduler-profile pipeline: the device-plugin subsystem that
lowers a KubeScheduler profile (ordered filter refs + weighted score refs)
into the batched hot path. The device registry below lowers every built-in of
the scalar registry (core/scheduler/plugins.py): the filters Fit,
PodTopologySpread, NodeAffinity and TaintToleration, the reference's scorers
LeastAllocatedResources, MostAllocatedResources and
BalancedResourceAllocation, and kube-scheduler's own integer scorers
NodeResourcesFit, NodeResourcesBalancedAllocation, NodeAffinity and
TaintToleration (the score halves).

The scalar path interprets profiles per pod through the plugin registry
(core/scheduler/plugins.py, kube_scheduler.py). The batched path cannot —
its decision core runs inside jit-compiled programs and Mosaic/Pallas
kernels — so a profile is COMPILED here, once, at engine construction:

- `compile_profile` validates every plugin ref against the device registry
  below and produces a `CompiledProfile`: a small, hashable NamedTuple of
  plugin names and weights. A profile referencing a plugin the device
  registry cannot lower raises `UnsupportedProfileError` naming the plugin
  and the supported set — the batched engine REFUSES profiles it cannot
  honor instead of silently running the hard-coded default (the
  silent-wrong-profile failure mode this subsystem kills).
- The `CompiledProfile` threads through `_STEP_STATICS` exactly like
  `fault_params` (batched/step.py): it is a jit static, so each profile
  compiles its own window programs, and the expressions below are inlined
  into both the lax.scan oracle path and the Pallas kernels
  (`ops/scheduler_kernel._fit_score_place`) as kernel statics.
- `profile_fit_mask` / `profile_score` are the ONE definition of the
  filter-mask and weighted-score expressions. They are pure elementwise
  jnp programs over broadcast-compatible arrays, which is precisely what
  makes them lowerable in BOTH worlds: the scan body calls them on
  (C, N) node arrays with (C, 1) requests, the kernels on (Np, LANE) node
  tiles with (1, LANE) requests. All literals are explicitly typed
  (Mosaic cannot lower weak f64/i64 constants under jax_enable_x64).

Semantics (pinned bit-for-bit against the pre-profile hard-fused core for
the default profile, and against the scalar oracle for every profile by
tests/test_random_equivalence.py):

- Filters AND into the alive mask (scalar: list comprehension chain).
- PodTopologySpread (DoNotSchedule; semantics in core/scheduler/plugins.py
  and docs/PARITY.md) is the one filter that reads more than the node and
  the pod: a (G workloads x Z domains) table of matching placed pods, which
  the decision core carries from one placement of a cycle to the next
  beside the allocatables. `spread_zone_ok` / `spread_node_mask` /
  `spread_place` below are its ONE definition, in the kernels' layout
  (domains and nodes on axis 0, clusters on axis 1); the scan body feeds
  them transposed arrays. A build none of whose pods is held to a
  constraint carries no table and traces none of it.
- NodeAffinity and TaintToleration (semantics in core/scheduler/plugins.py
  and docs/PARITY.md "Node affinity and taints") read one more node plane
  and the candidate's masks, integers interned at trace compile
  (trace_compile.CompiledAffinity): a term passes where
  `node_bits & mask == mask`, the taints where `node_bits & untolerated ==
  0` (`affinity_node_masks`). No string, no gather and no carried state. A
  build without a taint, a selector, an affinity or a toleration carries no
  plane and traces none of it.
- Scores are float32, summed over scorers after weighting; a weight of
  exactly 1.0 skips the multiply so the default profile's expression tree
  is textually identical to the historical hard-fused one.
- Zero-allocatable nodes score NaN on the scalar path (plugins.py) and
  -inf here: neither can win the last-max-wins `>=` argmax, so decisions
  agree; -inf keeps the kernels free of NaN-propagation hazards.
- Tie-breaks: last max in node-slot order == the reference's `>=` sweep
  over name-sorted nodes (kube_scheduler.rs:140-150).
- Where requests and capacities do not move in lockstep (a trace of
  heterogeneous pods), two nodes' scores can lie closer than float32
  resolves while the scalar path's float64 tells them apart: on a replay
  of 17,899 such pods over 1,313 nodes the float32 argmax put the 4,434th
  pod on another node and half of all pods after it (PERF.md, PR 28). For
  such builds the engine sets `exact_bits` (`exact_score_bits`) and a
  profile that scores by LeastAllocatedResources alone, whatever its
  filters, ranks nodes by `exact_least_allocated_key`, a 3-digit
  fixed-point quotient in int32 that orders as the float64 score does.
- A profile whose scorers are all kube-scheduler's (docs/PARITY.md "Scoring
  as kube-scheduler scores"; `is_integer_profile`) ranks in int32 and needs
  no key: "integer scorers" below. It is the one kind of score that is not
  elementwise in the node: NodeAffinity and TaintToleration are normalised
  over the nodes that passed the filters, two reductions over the node axis
  before the argmax. Such a profile never mixes with the float scorers.
"""

from __future__ import annotations

import logging
from typing import Callable, Dict, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from kubernetriks_tpu.core.scheduler.kube_scheduler import (
    DEFAULT_SCHEDULER_NAME,
    KubeSchedulerConfig,
    kube_scheduler_config_from_spec,
)
from kubernetriks_tpu.core.scheduler.plugins import (
    BALANCED,
    BALANCED_ALLOCATION,
    FIT,
    INTEGER_SCORE_PLUGINS,
    LEAST_ALLOCATED,
    MOST_ALLOCATED,
    NODE_AFFINITY,
    NODE_RESOURCES_FIT,
    TAINT_TOLERATION,
    TOPOLOGY_SPREAD,
)

_NEG_INF = float(np.float32(-np.inf))


class UnsupportedProfileError(ValueError):
    """A configured profile references a plugin the device pipeline cannot
    lower (or an un-lowerable weight). Raised at engine construction —
    loudly, naming the offender and the supported set — never silently
    replaced by the default pipeline."""


class CompiledProfile(NamedTuple):
    """A profile lowered to kernel statics: hashable (it keys the jit
    cache through _STEP_STATICS) and tiny (names + weights only; the
    expressions are regenerated from the registry at trace time)."""

    name: str  # display name ("default", "best_fit", or "custom")
    filters: Tuple[str, ...]  # ordered filter plugin names
    scores: Tuple[Tuple[str, float], ...]  # (scorer name, weight) pairs
    # > 0: rank nodes by exact_least_allocated_key with digits of this many
    # bits, not by the float32 score. Set by the engine from the traces it is
    # built over (exact_score_bits), never by a user.
    exact_bits: int = 0
    # An integer profile's (cpu, ram) units: the gcds of the build's requests
    # and capacities, in which the integer scorers multiply
    # (integer_score_units). Set by the engine like exact_bits.
    units: Tuple[int, int] = (1, 1)
    # How many PreferNoSchedule taint bits the build's node plane holds (from
    # bit 30 downwards; trace_compile._compile_affinity). Set by the engine.
    soft_taints: int = 0


def _zero(x):
    """A typed zero matching x's dtype — Mosaic rejects weak Python-scalar
    constants inside kernel bodies under jax_enable_x64."""
    return x.dtype.type(0)


class NodeFacts(NamedTuple):
    """What a filter may know of a node beside its allocatable: node masks
    the decision core derived for this candidate, each None in a build that
    carries nothing for it (the filter then passes every node)."""

    # Where other pods sit: from the carried spread table (spread_node_mask).
    spread_ok: Optional[jnp.ndarray] = None
    # The node's labels against the candidate's terms, and its taints against
    # the candidate's tolerations (affinity_node_masks).
    affinity_ok: Optional[jnp.ndarray] = None
    taints_ok: Optional[jnp.ndarray] = None


# --- device plugin registry ---------------------------------------------------
# Filters: fn(cpu, ram, rc, rr, facts) -> bool mask (AND-composed onto
# `alive`), or None for "passes every node". Scorers: fn(cpu, ram, rc, rr) ->
# float32 score (summed after weighting). cpu/ram are the nodes' current
# allocatable, rc/rr the candidate's requests; any broadcast-compatible shapes
# (the scan path and the kernels differ). `facts` (NodeFacts, or None) is
# everything else a filter may know.


def _filter_fit(cpu, ram, rc, rr, facts):
    return (rc <= cpu) & (rr <= ram)


def _filter_topology_spread(cpu, ram, rc, rr, facts):
    return None if facts is None else facts.spread_ok


def _filter_node_affinity(cpu, ram, rc, rr, facts):
    return None if facts is None else facts.affinity_ok


def _filter_taint_toleration(cpu, ram, rc, rr, facts):
    return None if facts is None else facts.taints_ok


def _score_least_allocated(cpu, ram, rc, rr):
    neg_inf = jnp.float32(_NEG_INF)
    hundred = jnp.float32(100.0)
    half = jnp.float32(0.5)
    cpu_f = cpu.astype(jnp.float32)
    ram_f = ram.astype(jnp.float32)
    cpu_score = jnp.where(
        cpu > _zero(cpu),
        (cpu_f - rc.astype(jnp.float32)) * hundred / cpu_f,
        neg_inf,
    )
    ram_score = jnp.where(
        ram > _zero(ram),
        (ram_f - rr.astype(jnp.float32)) * hundred / ram_f,
        neg_inf,
    )
    return (cpu_score + ram_score) * half


def _score_most_allocated(cpu, ram, rc, rr):
    neg_inf = jnp.float32(_NEG_INF)
    hundred = jnp.float32(100.0)
    half = jnp.float32(0.5)
    cpu_f = cpu.astype(jnp.float32)
    ram_f = ram.astype(jnp.float32)
    cpu_score = jnp.where(
        cpu > _zero(cpu),
        (rc.astype(jnp.float32) - cpu_f) * hundred / cpu_f,
        neg_inf,
    )
    ram_score = jnp.where(
        ram > _zero(ram),
        (rr.astype(jnp.float32) - ram_f) * hundred / ram_f,
        neg_inf,
    )
    return (cpu_score + ram_score) * half


def _score_balanced(cpu, ram, rc, rr):
    neg_inf = jnp.float32(_NEG_INF)
    hundred = jnp.float32(100.0)
    cpu_f = cpu.astype(jnp.float32)
    ram_f = ram.astype(jnp.float32)
    ok = (cpu > _zero(cpu)) & (ram > _zero(ram))
    # Guard the divisors so the masked-out lanes never divide by zero
    # (where() evaluates both branches).
    one = jnp.float32(1.0)
    cpu_frac = rc.astype(jnp.float32) / jnp.where(ok, cpu_f, one)
    ram_frac = rr.astype(jnp.float32) / jnp.where(ok, ram_f, one)
    return jnp.where(
        ok, hundred - jnp.abs(cpu_frac - ram_frac) * hundred, neg_inf
    )


DEVICE_FILTER_PLUGINS: Dict[str, Callable] = {
    FIT: _filter_fit,
    TOPOLOGY_SPREAD: _filter_topology_spread,
    NODE_AFFINITY: _filter_node_affinity,
    TAINT_TOLERATION: _filter_taint_toleration,
}

DEVICE_SCORE_PLUGINS: Dict[str, Callable] = {
    LEAST_ALLOCATED: _score_least_allocated,
    MOST_ALLOCATED: _score_most_allocated,
    BALANCED: _score_balanced,
}


# The reference default, hard-fused into the batched path since its first
# version — now just the profile every other one is compiled like.
DEFAULT_PROFILE = CompiledProfile(
    name="default",
    filters=(FIT,),
    scores=((LEAST_ALLOCATED, 1.0),),
)


def _supported() -> str:
    return (
        f"the batched path supports filters {sorted(DEVICE_FILTER_PLUGINS)}, scorers "
        f"{sorted(DEVICE_SCORE_PLUGINS)} and, not mixed with them, integer scorers "
        f"{sorted(INTEGER_SCORE_PLUGINS)} (kubernetriks_tpu/batched/pipeline.py); run the scalar "
        "backend for scalar-only plugins"
    )


def is_integer_profile(profile: CompiledProfile) -> bool:
    """Whether the profile scores by kube-scheduler's integer scorers (all of
    its scorers then are: compile_profile refuses a mix)."""
    return any(name in INTEGER_SCORE_PLUGINS for name, _ in profile.scores)


def scores_softly(profile: CompiledProfile) -> bool:
    """Whether the profile scores by NodeAffinity or TaintToleration."""
    return any(name in (NODE_AFFINITY, TAINT_TOLERATION) for name, _ in profile.scores)


def compile_profile(spec=None) -> CompiledProfile:
    """Lower one profile spec to a CompiledProfile.

    Accepts everything kube_scheduler_config_from_spec does (None, a named
    profile string, an explicit {filters, score} mapping, a
    KubeSchedulerConfig) plus an already-compiled CompiledProfile (validated
    again — a hand-built one may still name unknown plugins).

    Raises UnsupportedProfileError naming the offending plugin and the
    supported set when the batched path cannot lower the profile; the
    scalar interpreter may still run such a profile, but the engine must
    never silently substitute the default for it."""
    if isinstance(spec, CompiledProfile):
        prof = spec
    else:
        if spec is None:
            spec = "default"
        name = spec if isinstance(spec, str) else None
        config = kube_scheduler_config_from_spec(spec)
        kprof = config.profiles[DEFAULT_SCHEDULER_NAME]
        prof = CompiledProfile(
            name=name or "custom",
            filters=tuple(p.name for p in kprof.plugins.filter),
            scores=tuple(
                (p.name, float(1.0 if p.weight is None else p.weight))
                for p in kprof.plugins.score
            ),
        )
    for fname in prof.filters:
        if fname not in DEVICE_FILTER_PLUGINS:
            raise UnsupportedProfileError(
                f"scheduler profile {prof.name!r}: filter plugin {fname!r} "
                f"has no device lowering — {_supported()}"
            )
    integer = is_integer_profile(prof)
    for sname, weight in prof.scores:
        if integer:
            if sname in DEVICE_SCORE_PLUGINS:
                raise UnsupportedProfileError(
                    f"scheduler profile {prof.name!r}: float score plugin {sname!r} beside integer scorers "
                    f"{[n for n, _ in prof.scores if n in INTEGER_SCORE_PLUGINS]}: integer scores are "
                    "normalised to 0-100 and ranked in int32, the float ones are not; score by one kind"
                )
            if sname in INTEGER_SCORE_PLUGINS and (weight < 1 or weight != int(weight)):
                raise UnsupportedProfileError(
                    f"scheduler profile {prof.name!r}: integer score plugin {sname!r} has weight "
                    f"{weight!r}; kube-scheduler's weights are positive integers"
                )
            if sname in (NODE_AFFINITY, TAINT_TOLERATION) and sname not in prof.filters:
                raise UnsupportedProfileError(
                    f"scheduler profile {prof.name!r} scores by {sname!r} without filtering by it: "
                    "upstream's plugin is both halves; add it to the filters"
                )
        if sname not in DEVICE_SCORE_PLUGINS and sname not in INTEGER_SCORE_PLUGINS:
            raise UnsupportedProfileError(
                f"scheduler profile {prof.name!r}: score plugin {sname!r} "
                f"has no device lowering — {_supported()}"
            )
        if not (weight > 0.0) or not np.isfinite(weight):
            # Scalar NaN-score semantics survive any positive weight; a
            # zero/negative/non-finite weight would flip the -inf lowering
            # of zero-allocatable nodes into a winning score.
            raise UnsupportedProfileError(
                f"scheduler profile {prof.name!r}: score plugin {sname!r} "
                f"has weight {weight!r}; the device lowering requires a "
                f"finite weight > 0"
            )
    return prof


def to_kube_scheduler_config(profile: CompiledProfile) -> KubeSchedulerConfig:
    """CompiledProfile -> the KubeSchedulerConfig that makes the scalar
    KubeScheduler run the SAME profile — the oracle side of the per-profile
    equivalence sweeps."""
    return kube_scheduler_config_from_spec(
        {
            "filters": list(profile.filters),
            "score": [
                {"name": n, "weight": w} for n, w in profile.scores
            ],
        }
    )


# --- compiled expressions -----------------------------------------------------


def profile_fit_mask(profile: CompiledProfile, alive, cpu, ram, rc, rr, facts=None):
    """The profile's filter chain ANDed onto the alive mask. Elementwise;
    usable in the scan body and inside Mosaic kernels."""
    fit = alive
    for fname in profile.filters:
        mask = DEVICE_FILTER_PLUGINS[fname](cpu, ram, rc, rr, facts)
        if mask is not None:
            fit = fit & mask
    return fit


# --- NodeAffinity and TaintToleration: the planes ------------------------------
# `node_bits`: a node's int32 of interned bits (the expressions its labels
# satisfy, the taints it carries; bit 31 never set); `terms`: the candidate's
# term masks, one array a term plane of the build (bit 31 alone: the pod has
# no such term, and no node passes it); `forbid`: the taint bits the
# candidate does not tolerate, bit 31 saying that the pod names its nodes.
# Any broadcast-compatible shapes: (Np, L) against (1, L) in the kernels,
# (C, N) against (C, 1) in the scan body.


def uses_affinity(profile: CompiledProfile) -> bool:
    return NODE_AFFINITY in profile.filters or TAINT_TOLERATION in profile.filters


def affinity_node_masks(node_bits, terms, forbid):
    """(affinity_ok, taints_ok): the nodes that satisfy at least one of the
    candidate's terms, and those none of whose taints it fails to tolerate."""
    affinity_ok = None
    for want in terms:
        hit = (node_bits & want) == want
        affinity_ok = hit if affinity_ok is None else affinity_ok | hit
    return affinity_ok, (node_bits & forbid) == jnp.int32(0)


def affinity_names_nodes(forbid):
    """Whether the candidate carries a selector, an affinity or a toleration."""
    return forbid < jnp.int32(0)


# --- PodTopologySpread: the carried table --------------------------------------
# Kernel layout throughout: a workload's per-domain match counts are ONE
# (SPREAD_ZONE_TILE, L) tile (domains on sublanes, clusters on lanes; domains
# past the build's Z are never alive), the table a list of G such tiles;
# `group` / `bits` are the candidate's (1, L) workload (-1: no constraint) and
# match bits. Static Python loops over G and Z, typed literals only: the same
# code runs in the scan body and inside the Mosaic kernels.

SPREAD_ZONE_TILE = 8


def uses_spread(profile: CompiledProfile) -> bool:
    return TOPOLOGY_SPREAD in profile.filters


def spread_tiles(table):
    """(C, G, Z) -> G tiles of (SPREAD_ZONE_TILE, C) int32: the layout the
    three functions below work in (the kernels stack the tiles into one
    block, the scan body carries them as they are)."""
    pad = ((0, 0), (0, 0), (0, SPREAD_ZONE_TILE - table.shape[2]))
    padded = jnp.pad(table.astype(jnp.int32), pad)
    return tuple(padded[:, g, :].T for g in range(table.shape[1]))


def spread_alive_tile(zone_alive):
    """(C, Z) bool -> (SPREAD_ZONE_TILE, C) bool; padded domains are dead."""
    return jnp.pad(zone_alive, ((0, 0), (0, SPREAD_ZONE_TILE - zone_alive.shape[1]))).T


def spread_zone_ok(tiles, limits, zone_alive, group, bits):
    """(zone_ok (8, L) bool, constrained (1, L) bool, closed (1, L) bool) for
    one candidate a lane: the domains its constraint leaves open
    (match(d) + self - minMatch <= maxSkew over the live domains), whether it
    carries one, and whether the skew closed any live domain."""
    i0, i1 = jnp.int32(0), jnp.int32(1)
    big = jnp.int32(2**31 - 1)
    cnt = jnp.zeros_like(tiles[0])
    lim = jnp.zeros_like(tiles[0])
    own = jnp.zeros_like(group)
    for g, (tile, limit) in enumerate(zip(tiles, limits)):
        pick = group == jnp.int32(g)
        cnt = jnp.where(pick, tile, cnt)
        lim = jnp.where(pick, limit, lim)
        own = jnp.where(pick, (bits >> jnp.int32(g)) & i1, own)
    constrained = group >= i0
    least = jnp.min(jnp.where(zone_alive, cnt, big), axis=0, keepdims=True)
    zone_ok = zone_alive & (cnt + own - least <= lim)
    shut = jnp.max((zone_alive & ~zone_ok).astype(jnp.int32), axis=0, keepdims=True) > i0
    return zone_ok, constrained, constrained & shut


def spread_node_mask(domain, zone_ok, constrained, n_domains: int):
    """(Np, L) the nodes the candidate's constraint admits: those whose
    domain is open (a node without the key, domain -1, is in none); every
    node for a candidate without a constraint."""
    ok = ~constrained
    for z in range(n_domains):
        ok = ok | ((domain == jnp.int32(z)) & zone_ok[z : z + 1, :])
    return ok


def spread_place(tiles, zbest, assign, bits):
    """The table after one placement: +1 at the placed node's domain in
    every workload whose selector the placed pod satisfies."""
    i0, i1 = jnp.int32(0), jnp.int32(1)
    iota = jax.lax.broadcasted_iota(jnp.int32, tiles[0].shape, 0)
    hit = (iota == zbest) & assign
    return [
        tile + jnp.where(hit & (((bits >> jnp.int32(g)) & i1) != i0), i1, i0)
        for g, tile in enumerate(tiles)
    ]


def profile_score(profile: CompiledProfile, fit, cpu, ram, rc, rr):
    """The profile's weighted score sum, masked to -inf off the fit set.
    weight == 1.0 skips the multiply, so the default profile generates the
    exact historical expression tree (bit-identical programs)."""
    neg_inf = jnp.float32(_NEG_INF)
    total = None
    for sname, weight in profile.scores:
        s = DEVICE_SCORE_PLUGINS[sname](cpu, ram, rc, rr)
        if weight != 1.0:
            s = s * jnp.float32(weight)
        total = s if total is None else total + s
    if total is None:
        # Scoreless profile: every fitting node scores 0.0; the last-max
        # argmax then picks the last fitting slot, matching the scalar
        # `>=` sweep over all-zero node_scores.
        return jnp.where(fit, jnp.float32(0.0), neg_inf)
    return jnp.where(fit, total, neg_inf)


# --- exact ranking for heterogeneous requests ---------------------------------

_EXACT_DIGITS = 3
_EXACT_BITS_MAX = 14
_EXACT_BITS_MIN = 10
# A lockstep trace's score is a function of one integer, free units A, and
# steps by 100 k / A**2 between neighbours; float32 resolves that to here.
_LOCKSTEP_MAX_UNITS = 1024


def _resource_units(requests, capacities):
    """(every cpu amount, every ram amount, their gcds) over iterables of
    (cpu, ram) integer array pairs; the gcds 0 where there is no amount."""
    pairs = [
        (np.asarray(c, np.int64).ravel(), np.asarray(r, np.int64).ravel())
        for c, r in (*requests, *capacities)
    ]
    cpus = np.concatenate([c for c, _ in pairs]) if pairs else np.zeros(0, np.int64)
    rams = np.concatenate([r for _, r in pairs]) if pairs else np.zeros(0, np.int64)
    return cpus, rams, int(np.gcd.reduce(cpus, initial=0)), int(np.gcd.reduce(rams, initial=0))


def exact_score_bits(profile: CompiledProfile, requests, capacities) -> int:
    """The `exact_bits` static for an engine built over these pods and nodes.

    `requests` and `capacities` are iterables of (cpu, ram) integer array
    pairs (pods of every trace, node capacities incl. the CA's templates).
    0 = the float32 score decides as the scalar path's float64 does, which
    holds when every request and capacity is a whole multiple k of ONE
    (cpu, ram) unit pair: allocatable is then (A u_c, A u_m), the score
    100 - 100 k / A, and distinct A differ by far more than float32's 1e-5.
    The dense Monte-Carlo and autoscaled deployments are such (4 cores with
    8 GiB on 64 with 128), and their programs stay as they were. Otherwise
    the digit width that keeps `remainder << bits` inside int32 for the
    largest capacity. Only the default profile (Fit + LeastAllocated at
    weight 1) has the exact key: a build whose traces call for it and that
    cannot have it (another profile, a capacity too large for 10-bit
    digits) keeps float32 and says so in a warning, since its placements
    can then part from the scalar path's."""
    if is_integer_profile(profile):
        return 0  # integer scores are exact as they are: no key to need
    cpus, rams, unit_cpu, unit_ram = _resource_units(requests, capacities)
    if unit_cpu == 0 or unit_ram == 0:
        return 0  # no pod or no node asks for anything: every score ties
    largest = max(int(cpus.max()), int(rams.max()))
    lockstep = bool((cpus // unit_cpu == rams // unit_ram).all())
    if lockstep and int(cpus.max()) // unit_cpu <= _LOCKSTEP_MAX_UNITS:
        return 0
    bits = min(_EXACT_BITS_MAX, 31 - largest.bit_length())
    default = profile.scores == ((LEAST_ALLOCATED, 1.0),)
    if default and bits >= _EXACT_BITS_MIN:
        return bits
    logging.getLogger(__name__).warning(
        "requests and capacities are not whole multiples of one (cpu, ram) "
        "unit, so float32 node scores can rank two nodes otherwise than the "
        "scalar path's float64, and this build keeps float32 ranking (%s): "
        "placements may differ from the scalar backend's",
        f"capacity {largest} leaves {bits} bits a digit, under {_EXACT_BITS_MIN}"
        if default
        else f"profile {profile.name!r} has no exact key: it scores by {list(profile.scores)}, and only "
        "LeastAllocatedResources at weight 1 has one, whatever the filters",
    )
    return 0


_ESTIMATE_BIAS = 1.0 + 2.0**-16  # see _quotient_digits


def _digits_by_reciprocal(num, den, inv, bits: int):
    """_quotient_digits' long division, handed `inv`, its float32 estimate
    of 2**bits (1 + 2**-16) / den (a parameter so that a test can hand it
    one that is some ulp off)."""
    digits = []
    rem = num
    for _ in range(_EXACT_DIGITS):
        estimate = (rem.astype(jnp.float32) * inv).astype(jnp.int32)
        rem = (rem << jnp.int32(bits)) - estimate * den
        over = rem >> jnp.int32(31)  # -1 where the estimate was one over, else 0
        digits.append(estimate + over)
        rem = rem + (den & over)
    return digits


def _quotient_digits(num, den, bits: int):
    """The first _EXACT_DIGITS base-2**bits digits of num / den after the
    point, for int32 0 <= num <= den < 2**(31 - bits): long division with ONE
    float32 division a denominator. Each digit is estimated as
    trunc(float32(rem) * inv), inv = 2**bits (1 + 2**-16) / den, and
    corrected downwards by the sign of its integer remainder, so the digits
    are exact whatever the backend's division rounds to. Why one side is
    enough:

    - q = (rem << bits) / den, the quotient a digit is the floor of, lies in
      [0, 2**bits], bits <= _EXACT_BITS_MAX = 14: the remainder carried into
      a digit is under den (for the first, num <= den), so it is never
      negative, the conversion's truncation is the floor, and rem << bits
      <= den << bits < 2**31 does not wrap.
    - The bias lifts the estimate by q 2**-16, at most a quarter of a digit.
      Against it stand three roundings: rem <= den < 2**21 and the
      numerator 2**bits + 2**(bits - 16) are exact in float32; the product
      rounds by 2**-24 of its value, and inv by 2**-24 where the division is
      correctly rounded (a backend that takes a reciprocal and multiplies
      rounds twice). Even an inv 32 ulp off (2**-18) leaves the sum under
      2**-17.7, well inside the bias, so estimate >= q before truncation,
      and estimate < q (1 + 2**-16 + 2**-17.7) <= q + 0.33: truncated, it is
      the digit or one over it, never under.
    - So rem = (rem << bits) - estimate den lies in [-den, den) (the product
      may pass 2**31 by less than den: int32 wraps and the difference is
      exact), its sign alone says which, and `rem >> 31` (-1 or 0) corrects
      both words without a compare: the digit by adding it, the remainder by
      adding den under it as a mask. num == den (a pod that fills a node:
      q = 2**bits, an integer the estimate stays within a quarter above)
      takes the same path: digit 2**bits, remainder 0.

    Where the precondition does not hold (a node the pod does not fit, or
    one with nothing allocatable, whose divisor is guarded to 1) the digits
    are garbage without a trap, and exact_least_allocated_key masks them."""
    one = jnp.int32(1)
    den_f = jnp.where(den > _zero(den), den, one).astype(jnp.float32)
    inv = jnp.float32(_ESTIMATE_BIAS * 2.0**bits) / den_f
    return _digits_by_reciprocal(num, den, inv, bits)


def exact_least_allocated_key(fit, cpu, ram, rc, rr, bits: int):
    """(hi, lo) int32: rc / cpu + rr / ram in fixed point, each quotient
    truncated at 3 * bits bits, so that the lexicographically LEAST key is
    the node LeastAllocatedResources scores highest (score = 100 - 50 x the
    sum). Equal allocatables give equal keys, as they give equal float64
    scores; two unequal nodes closer than 2**-(3 bits - 1) can order
    otherwise than float64 does (at 14 bits: 2e-11 of a score whose typical
    gap on 1,313 nodes is 1e-3). Nodes that do not fit, or have nothing
    allocatable (the scalar NaN), get the largest key."""
    big = jnp.int32(2**31 - 1)
    mask = jnp.int32((1 << bits) - 1)
    c1, c2, c3 = _quotient_digits(jnp.broadcast_to(rc, cpu.shape), cpu, bits)
    r1, r2, r3 = _quotient_digits(jnp.broadcast_to(rr, ram.shape), ram, bits)
    lo = c3 + r3
    hi = ((c1 + r1) << jnp.int32(bits)) + (c2 + r2) + (lo >> jnp.int32(bits))
    ok = fit & (cpu > _zero(cpu)) & (ram > _zero(ram))
    return jnp.where(ok, hi, big), jnp.where(ok, lo & mask, big)


def exact_best_node(hi, lo, node_ok, iota, axis: int):
    """Highest node slot among the least (hi, lo) keys along `axis`:
    the last-max-wins argmax of the float32 path on exact keys."""
    least_hi = jnp.min(hi, axis=axis, keepdims=True)
    at_hi = hi == least_hi
    least_lo = jnp.min(jnp.where(at_hi, lo, jnp.int32(2**31 - 1)), axis=axis, keepdims=True)
    return jnp.max(
        jnp.where(at_hi & (lo == least_lo) & node_ok, iota, jnp.int32(-1)), axis=axis, keepdims=True
    )


# --- integer scorers: kube-scheduler's own -------------------------------------
# docs/PARITY.md "Scoring as kube-scheduler scores" is the text; the scalar
# plugins (core/scheduler/plugins.py) and the benchmark's reference are
# written from it too. Everything is int32. cpu / ram / rc / rr are the state's
# (millicores, RAM units); the products are made in units of the build's gcds
# (profile.units). Any broadcast-compatible shapes with the node axis `axis`:
# (Np, L) against (1, L) in the kernels (axis 0), (C, N) against (C, 1) in the
# scan body (axis 1).

INTEGER_PRODUCT_LIMIT = (2**31 - 1) // 100  # the largest cap_cpu * cap_ram, in units


def integer_score_units(requests, capacities) -> Tuple[int, int]:
    """The (cpu, ram) units of an integer profile's build: the gcd of every
    request and capacity (iterables of (cpu, ram) integer array pairs, as
    exact_score_bits takes them); 1 where nothing asks for anything."""
    _, _, unit_cpu, unit_ram = _resource_units(requests, capacities)
    return (max(unit_cpu, 1), max(unit_ram, 1))


def to_units(x, unit: int):
    """x / unit for an int32 x that is a whole multiple of `unit` (every
    free, request and capacity of a build is one of its gcd): the power of
    two shifted out, the odd part divided by multiplying with its inverse
    modulo 2**32, which is exact for a multiple (k odd odd**-1 = k, int32
    wraps). No float, two operations; a non-multiple gives garbage without a
    trap, and only masked nodes hold one."""
    shift = (unit & -unit).bit_length() - 1
    odd = unit >> shift
    if shift:
        x = x >> jnp.int32(shift)
    if odd != 1:
        inverse = pow(odd, -1, 2**32)
        x = x * jnp.int32(inverse - 2**32 if inverse >= 2**31 else inverse)
    return x


def biased_reciprocal(den):
    """floor_quotient's float32 estimate of (1 + 2**-16) / den, the divisor
    guarded to 1 where den is not positive."""
    return jnp.float32(_ESTIMATE_BIAS) / jnp.where(den > _zero(den), den, jnp.int32(1)).astype(
        jnp.float32
    )


def floor_quotient(num, den, inv):
    """floor(num / den) for int32 0 <= num, 0 < den, num / den < 2**14, with
    `inv` = biased_reciprocal(den): ONE digit of _quotient_digits' long
    division (its argument, at bits = 0 and a quotient that may pass 1). The
    estimate trunc(float32(num) * inv) is the quotient or one over it: the
    bias lifts it by q 2**-16 (under a quarter), which covers the three
    float32 roundings (num to float32, inv, the product: 2**-24 of the value
    each, even an inv 32 ulp off) so it is never under q, and q (1 + 2**-16 +
    2**-17.7) < q + 0.33 keeps it under floor(q) + 2. Its integer remainder
    num - estimate den is in [-den, den) (int32 wraps and the difference is
    exact), and its sign alone corrects the estimate: no float decides the
    result. Garbage without a trap where the precondition fails (a node the
    pod does not fit): the callers mask those."""
    estimate = (num.astype(jnp.float32) * inv).astype(jnp.int32)
    return estimate + ((num - estimate * den) >> jnp.int32(31))


class IntegerNodes(NamedTuple):
    """What the resource scorers know of the nodes beside their frees, made
    once a launch (a cycle, in the scan) from the capacity planes: the
    capacities in units, their product, and the three biased reciprocals."""

    cap_cpu: jnp.ndarray
    cap_ram: jnp.ndarray
    whole: jnp.ndarray  # cap_cpu * cap_ram
    inv_cpu: jnp.ndarray
    inv_ram: jnp.ndarray
    inv_whole: jnp.ndarray


def integer_nodes(cap_cpu, cap_ram, units: Tuple[int, int]) -> IntegerNodes:
    cap_cpu, cap_ram = to_units(cap_cpu, units[0]), to_units(cap_ram, units[1])
    whole = cap_cpu * cap_ram
    return IntegerNodes(
        cap_cpu, cap_ram, whole,
        biased_reciprocal(cap_cpu), biased_reciprocal(cap_ram), biased_reciprocal(whole),
    )


class SoftFacts(NamedTuple):
    """What the two label scorers read for one candidate: the node plane
    (AffinityState.node_bits), the candidate's preferred-term masks, its
    packed weights and its untolerated PreferNoSchedule taint bits, and how
    many such taints the build's node plane holds (a static)."""

    node_bits: jnp.ndarray
    terms: Tuple[jnp.ndarray, ...]
    weights: jnp.ndarray
    forbid: jnp.ndarray
    n_taints: int


SOFT_WEIGHT_BITS = 7  # trace_compile packs a preferred term's weight so
SOFT_TAINT_TOP_BIT = 30  # and puts the PreferNoSchedule taints from here down


def soft_raw_scores(soft: SoftFacts):
    """(NodeAffinity's, TaintToleration's) raw scores a node: the weights of
    the preferred terms its labels match, summed; its PreferNoSchedule taints
    the candidate does not tolerate, counted."""
    i0, i1 = jnp.int32(0), jnp.int32(1)
    affinity = None
    for t, want in enumerate(soft.terms):
        weight = (soft.weights >> jnp.int32(SOFT_WEIGHT_BITS * t)) & jnp.int32(2**SOFT_WEIGHT_BITS - 1)
        term = jnp.where((soft.node_bits & want) == want, weight, i0)
        affinity = term if affinity is None else affinity + term
    untolerated = soft.node_bits & soft.forbid
    taints = None
    for j in range(soft.n_taints):
        bit = (untolerated >> jnp.int32(SOFT_TAINT_TOP_BIT - j)) & i1
        taints = bit if taints is None else taints + bit
    return affinity, taints


def _normalized(raw, fit, axis: int, reverse: bool, most=None):
    """(upstream's DefaultNormalizeScore over the nodes in `fit`, the largest
    raw score there): raw as its share of the largest, in whole points.
    `most`: that largest, from a caller that holds it already."""
    i0, hundred = jnp.int32(0), jnp.int32(100)
    if most is None:
        most = jnp.max(jnp.where(fit, raw, i0), axis=axis, keepdims=True)
    share = jnp.where(most > i0, floor_quotient(raw * hundred, most, biased_reciprocal(most)), i0)
    return (hundred - share if reverse else share), most


def soft_normalised(profile: CompiledProfile, soft: SoftFacts) -> Tuple[bool, bool]:
    """Which of (NodeAffinity, TaintToleration) integer_scores normalises in
    a build with these soft planes: the profile scores by it and the planes
    hold something for it. Reads the statics of `soft` alone."""
    scored = dict(profile.scores)
    return NODE_AFFINITY in scored and bool(soft.terms), TAINT_TOLERATION in scored and soft.n_taints > 0


def soft_feasible_raws(fit, soft: SoftFacts):
    """soft_raw_scores with 0 off the fit set: what _normalized reduces over
    the node axis to each scorer's `most`. The kernels, which meet the nodes
    a row block at a time, fold these into running maxima in a sweep of their
    own and hand integer_scores the two as `mosts`."""
    i0 = jnp.int32(0)
    return tuple(None if raw is None else jnp.where(fit, raw, i0) for raw in soft_raw_scores(soft))


def integer_scores(
    profile: CompiledProfile, fit, cpu, ram, rc, rr, nodes: IntegerNodes, soft, axis: int, mosts=(None, None)
):
    """(total, soft part, soft attempt): the profile's weighted integer score,
    -1 off the fit set; of it the label scorers' part (None without `soft`,
    the build's SoftFacts or None) and whether either had something to
    normalise by (M > 0) for this candidate. `mosts` = (NodeAffinity's,
    TaintToleration's) largest raw score over the feasible nodes where the
    caller has reduced them already (soft_feasible_raws), else they are
    reduced here along `axis`."""
    i0, hundred = jnp.int32(0), jnp.int32(100)
    weights = {name: jnp.int32(int(weight)) for name, weight in profile.scores}
    free_cpu = to_units(cpu - rc, profile.units[0])
    free_ram = to_units(ram - rr, profile.units[1])
    total = None

    def add(total, name, score):
        part = score * weights[name]
        return part if total is None else total + part

    if NODE_RESOURCES_FIT in weights:
        left_cpu = jnp.where(
            nodes.cap_cpu > i0, floor_quotient(free_cpu * hundred, nodes.cap_cpu, nodes.inv_cpu), i0
        )
        left_ram = jnp.where(
            nodes.cap_ram > i0, floor_quotient(free_ram * hundred, nodes.cap_ram, nodes.inv_ram), i0
        )
        total = add(total, NODE_RESOURCES_FIT, (left_cpu + left_ram) >> jnp.int32(1))
    if BALANCED_ALLOCATION in weights:
        # U_cpu A_ram - U_ram A_cpu with U = A - free: the capacities cancel.
        skew = free_ram * nodes.cap_cpu - free_cpu * nodes.cap_ram
        skew = jnp.maximum(skew, -skew)
        score = floor_quotient(hundred * nodes.whole - jnp.int32(50) * skew, nodes.whole, nodes.inv_whole)
        total = add(total, BALANCED_ALLOCATION, jnp.where(nodes.whole > i0, score, i0))
    part = attempt = None
    if soft is not None:
        raw_affinity, raw_taints = soft_raw_scores(soft)
        by_affinity, by_taints = soft_normalised(profile, soft)
        if by_affinity:
            score, most = _normalized(raw_affinity, fit, axis, reverse=False, most=mosts[0])
            part, attempt = add(part, NODE_AFFINITY, score), most > i0
        if by_taints:
            score, most = _normalized(raw_taints, fit, axis, reverse=True, most=mosts[1])
            part = add(part, TAINT_TOLERATION, score)
            attempt = most > i0 if attempt is None else attempt | (most > i0)
    if part is not None:
        total = part if total is None else total + part
    return jnp.where(fit, total, jnp.int32(-1)), part, attempt


def integer_best_node(total, node_ok, iota, axis: int):
    """Highest node slot among the largest totals along `axis`: the
    last-max-wins argmax on integer scores."""
    most = jnp.max(total, axis=axis, keepdims=True)
    return jnp.max(jnp.where((total == most) & node_ok, iota, jnp.int32(-1)), axis=axis, keepdims=True)


def soft_honoured(part, fit, chosen, axis: int):
    """Whether the node the candidate went to (`chosen`, one-hot along
    `axis`, all false where it went nowhere) has the largest label score
    among the nodes in `fit`."""
    neg1 = jnp.int32(-1)
    best = jnp.max(jnp.where(fit, part, neg1), axis=axis, keepdims=True)
    got = jnp.max(jnp.where(chosen, part, neg1), axis=axis, keepdims=True)
    return (got == best) & (got >= jnp.int32(0))


def profile_fit_score(profile: CompiledProfile, alive, cpu, ram, rc, rr, facts=None):
    """(fit mask, masked score) in one call — the decision core both the
    lax.scan path (batched/step.py) and the Pallas kernels
    (ops/scheduler_kernel._fit_score_place) build on."""
    fit = profile_fit_mask(profile, alive, cpu, ram, rc, rr, facts)
    return fit, profile_score(profile, fit, cpu, ram, rc, rr)


def bestfit_logits_from_obs(obs):
    """The MostAllocatedResources scorer evaluated on the RL environment's
    observation channels (rl/env.featurize: alloc and request fractions of
    node capacity). The scorer is scale-invariant per resource —
    (rc - cpu)/cpu is unchanged by dividing both by capacity — so the
    capacity-normalized channels rank nodes exactly like the raw
    allocatables. This is the ONE best-fit definition shared by the
    learning proof's heuristic baseline (rl/evaluate.bestfit_policy_apply)
    and the scheduler's "best_fit" device profile."""
    return DEVICE_SCORE_PLUGINS[MOST_ALLOCATED](
        obs[..., 2], obs[..., 3], obs[..., 4], obs[..., 5]
    )
