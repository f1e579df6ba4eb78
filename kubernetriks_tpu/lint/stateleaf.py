"""State-leaf coverage pass: every state pytree leaf is provably handled
in every registered consumer.

The bug class (PR 13/14 lived it twice): `ClusterBatchState` /
`AutoscaleState` / `TelemetryRing` leaves ride lane resets, checkpoint
save/restore, state comparison, telemetry stripping and the sanitizer's
consume-donated sweep — but nothing forced a NEW leaf to reach those
consumers. A leaf that misses one silently survives fleet resets (state
bleeds between what-if queries), restores into the wrong structure, or
escapes the parity comparator. PR 14's fix was architectural ("reclaim
counters ride the pytree so fleet resets cover them automatically");
this pass proves that architecture holds for every future leaf.

Mechanism. The state classes are parsed from their NamedTuple AST
definitions (fields = annotated assignments; a `= None` default marks a
STRUCTURAL leaf — presence is part of the compiled program's identity).
Each registered consumer then proves coverage one of three ways:

- pytree-GENERIC traversal: the function body calls `jax.tree.map` /
  `tree_flatten(_with_path)` / `tree_leaves` (or rebuilds through
  `._replace`, which passes unnamed leaves through unchanged) — every
  leaf, present and future, is handled by construction.
- by NAME: every required field name appears in the function body
  (attribute, keyword, or string) — the init-constructor style.
- by MANIFEST: a module-level constant (tuple or dict keys) lists the
  covered leaves with their coverage story — the checkpoint-meta style
  (`engine.CKPT_COVERED_LEAVES`).

Each class also carries a leaf MANIFEST next to its definition
(`CLUSTER_STATE_LEAVES` / `AUTOSCALE_STATE_LEAVES` /
`TELEMETRY_RING_LEAVES`) that must equal the field list exactly — THE
"how to add a state leaf" checklist anchor (DESIGN §7): adding a leaf
without touching the manifest is a lint error pointing at the checklist,
and a stale manifest entry is equally loud. Allocation-index leaves
(structural `ca_*` members of AutoscaleState) must additionally appear
in the DESIGN §12 invariants list — the doc registry.

A `# ktpu: state-module` file pragma marks a self-contained fixture:
classes, manifests and consumer functions are all resolved within that
file (tests/lint_fixtures/stateleaf_*.py).

Waive a deliberate gap with `# ktpu: leaf-ok(<reason>)` on the consumer
def line or the class line.
"""

from __future__ import annotations

import ast
import os
from dataclasses import dataclass
from typing import Dict, List, Optional, Sequence, Set, Tuple

from kubernetriks_tpu.lint import (
    LintContext,
    SourceFile,
    Violation,
    dotted_name,
)

PASS_ID = "stateleaf"

STATE_PY = "kubernetriks_tpu/batched/state.py"
AUTOSCALE_PY = "kubernetriks_tpu/batched/autoscale.py"
ENGINE_PY = "kubernetriks_tpu/batched/engine.py"
FLEET_PY = "kubernetriks_tpu/batched/fleet.py"
SANITIZE_PY = "kubernetriks_tpu/sanitize.py"

# class name -> defining module (path match is exact on the repo layout;
# a state-module pragma file overrides with its own definitions).
STATE_CLASSES: Dict[str, str] = {
    "ClusterBatchState": STATE_PY,
    "TelemetryRing": STATE_PY,
    "AutoscaleState": AUTOSCALE_PY,
    # Lane-async clock leaves ride StepConstants (traced per-lane data,
    # engine set_lane_plan re-seeds without recompiling) — a new consts
    # leaf must reach the manifest like any state leaf.
    "StepConstants": STATE_PY,
}

# class -> (manifest constant, module holding it)
MANIFESTS: Dict[str, Tuple[str, str]] = {
    "ClusterBatchState": ("CLUSTER_STATE_LEAVES", STATE_PY),
    "TelemetryRing": ("TELEMETRY_RING_LEAVES", STATE_PY),
    "AutoscaleState": ("AUTOSCALE_STATE_LEAVES", AUTOSCALE_PY),
    "StepConstants": ("STEP_CONSTANTS_LEAVES", STATE_PY),
}

CHECKLIST_HINT = (
    "follow the DESIGN §7 'how to add a state leaf' checklist"
)


@dataclass(frozen=True)
class Registry:
    """One registered consumer: `fields` selects which leaves it must
    handle — 'all', 'required' (no default: constructors must name them)
    or 'structural' (`= None` default: presence is program identity, so
    checkpoint meta must record it)."""

    name: str
    path: str
    func: str
    classes: Tuple[str, ...]
    fields: str = "all"  # "all" | "required" | "structural"
    manifest: Optional[str] = None  # module constant instead of the body


CONSUMERS: Tuple[Registry, ...] = (
    Registry(
        "fleet-reset",
        FLEET_PY,
        "_make_lane_programs",
        ("ClusterBatchState", "AutoscaleState", "TelemetryRing"),
    ),
    Registry(
        "compare-states",
        STATE_PY,
        "compare_states",
        ("ClusterBatchState", "AutoscaleState", "TelemetryRing"),
    ),
    Registry("strip-telemetry", STATE_PY, "strip_telemetry", ("ClusterBatchState",)),
    Registry(
        "sanitize-donated",
        SANITIZE_PY,
        "consume_donated",
        ("ClusterBatchState", "AutoscaleState", "TelemetryRing"),
    ),
    Registry("init-state", STATE_PY, "init_state", ("ClusterBatchState",), "required"),
    Registry(
        "init-autoscale-state",
        AUTOSCALE_PY,
        "init_autoscale_state",
        ("AutoscaleState",),
    ),
    Registry(
        "ckpt-meta",
        ENGINE_PY,
        "save_checkpoint",
        ("ClusterBatchState", "AutoscaleState"),
        "structural",
        manifest="CKPT_COVERED_LEAVES",
    ),
)

# Doc registry: structural allocation-index leaves must appear in the
# DESIGN §12 invariants section (they carry scalar-naming semantics a
# future reader must not discover by bisecting an endurance run).
DESIGN_DOC = os.path.join("docs", "DESIGN.md")
DESIGN_SECTION = "## 12"
DESIGN_CLASS = "AutoscaleState"
DESIGN_PREFIX = "ca_"

_GENERIC_MARKERS = (
    "tree.map",
    "tree_map",
    "tree.leaves",
    "tree_leaves",
    "tree_flatten",
    "tree_flatten_with_path",
    "tree.flatten",
    "tree_all",
)


@dataclass
class StateClass:
    name: str
    sf: SourceFile
    line: int
    fields: Tuple[str, ...]
    structural: Tuple[str, ...]  # fields defaulted to None

    def select(self, which: str) -> Tuple[str, ...]:
        if which == "structural":
            return self.structural
        if which == "required":
            return tuple(
                f for f in self.fields if f not in set(self._defaulted)
            )
        return self.fields

    _defaulted: Tuple[str, ...] = ()


def _class_fields(node: ast.ClassDef) -> Tuple[Tuple[str, ...], Tuple[str, ...], Tuple[str, ...]]:
    """(all fields, structural fields (= None default), any-default fields)
    of a NamedTuple class body."""
    fields: List[str] = []
    structural: List[str] = []
    defaulted: List[str] = []
    for st in node.body:
        if isinstance(st, ast.AnnAssign) and isinstance(st.target, ast.Name):
            fields.append(st.target.id)
            if st.value is not None:
                defaulted.append(st.target.id)
                if isinstance(st.value, ast.Constant) and st.value.value is None:
                    structural.append(st.target.id)
    return tuple(fields), tuple(structural), tuple(defaulted)


def _is_namedtuple(node: ast.ClassDef) -> bool:
    for base in node.bases:
        name = dotted_name(base) or ""
        if name.rsplit(".", 1)[-1] == "NamedTuple":
            return True
    return False


def _find_classes(files, fixture: Optional[SourceFile]) -> Dict[str, StateClass]:
    out: Dict[str, StateClass] = {}
    scope = [fixture] if fixture is not None else files
    for sf in scope:
        for node in ast.walk(sf.tree):
            if not isinstance(node, ast.ClassDef) or not _is_namedtuple(node):
                continue
            if node.name not in STATE_CLASSES:
                continue
            if fixture is None and sf.path != STATE_CLASSES[node.name]:
                continue
            fields, structural, defaulted = _class_fields(node)
            sc = StateClass(node.name, sf, node.lineno, fields, structural)
            sc._defaulted = defaulted
            out[node.name] = sc
    return out


def _find_func(sf: SourceFile, name: str) -> Optional[ast.FunctionDef]:
    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            if node.name == name:
                return node
    return None


def _has_generic_traversal(fn: ast.AST) -> bool:
    for node in ast.walk(fn):
        if isinstance(node, ast.Call):
            fname = dotted_name(node.func)
            if fname is not None and (
                fname.endswith(_GENERIC_MARKERS)
                or fname.startswith(("jax.tree", "tree_util."))
            ):
                return True
            if (
                isinstance(node.func, ast.Attribute)
                and node.func.attr == "_replace"
            ):
                # NamedTuple._replace passes every unnamed leaf through
                # unchanged — structure-preserving by construction.
                return True
    return False


def _body_tokens(fn: ast.AST) -> Set[str]:
    """Every identifier-ish token in a function body: attribute names,
    bare names, keyword-argument names, string constants."""
    tokens: Set[str] = set()
    for node in ast.walk(fn):
        if isinstance(node, ast.Attribute):
            tokens.add(node.attr)
        elif isinstance(node, ast.Name):
            tokens.add(node.id)
        elif isinstance(node, ast.keyword) and node.arg:
            tokens.add(node.arg)
        elif isinstance(node, ast.Constant) and isinstance(node.value, str):
            tokens.add(node.value)
    return tokens


def _module_const_names(
    sf: SourceFile, const: str
) -> Tuple[Optional[Set[str]], Optional[int]]:
    """Names listed by a module-level manifest constant: a tuple/list of
    strings, or a dict with string keys (values = coverage reasons)."""
    if not isinstance(sf.tree, ast.Module):
        return None, None
    for node in sf.tree.body:
        if (
            isinstance(node, ast.Assign)
            and len(node.targets) == 1
            and isinstance(node.targets[0], ast.Name)
            and node.targets[0].id == const
        ):
            val = node.value
            names: Set[str] = set()
            if isinstance(val, (ast.Tuple, ast.List)):
                for elt in val.elts:
                    if isinstance(elt, ast.Constant) and isinstance(
                        elt.value, str
                    ):
                        names.add(elt.value)
                    else:
                        return None, node.lineno
                return names, node.lineno
            if isinstance(val, ast.Dict):
                for key in val.keys:
                    if isinstance(key, ast.Constant) and isinstance(
                        key.value, str
                    ):
                        names.add(key.value)
                    else:
                        return None, node.lineno
                return names, node.lineno
            return None, node.lineno
    return None, None


def _check_consumer(
    reg: Registry,
    sf: SourceFile,
    classes: Dict[str, StateClass],
    out: List[Violation],
) -> None:
    # Manifest-backed registry: the constant's keys are the coverage.
    if reg.manifest is not None:
        names, line = _module_const_names(sf, reg.manifest)
        anchor = line or 1
        if names is None:
            out.append(
                Violation(
                    sf.path,
                    anchor,
                    PASS_ID,
                    f"registry '{reg.name}': manifest constant "
                    f"{reg.manifest} missing or not a literal tuple/dict "
                    f"of leaf names in {sf.path}",
                )
            )
            return
        wanted: Set[str] = set()
        resolved_all = all(cls in classes for cls in reg.classes)
        for cls in reg.classes:
            sc = classes.get(cls)
            if sc is None:
                continue
            for leaf in sc.select(reg.fields):
                wanted.add(leaf)
                if leaf not in names and not sf.waived(anchor, PASS_ID):
                    out.append(
                        Violation(
                            sf.path,
                            anchor,
                            PASS_ID,
                            f"state leaf {cls}.{leaf} is not covered by "
                            f"registry '{reg.name}' ({reg.manifest}) — "
                            f"record how checkpoint save/restore handles "
                            f"it, or {CHECKLIST_HINT}",
                        )
                    )
        # Staleness is only judgeable when EVERY registered class resolved
        # in scope — a partial lint (one changed file) must not demand the
        # deletion of entries covering the out-of-scope classes.
        if resolved_all:
            for name in sorted(names - wanted):
                if not sf.waived(anchor, PASS_ID):
                    out.append(
                        Violation(
                            sf.path,
                            anchor,
                            PASS_ID,
                            f"registry '{reg.name}': {reg.manifest} lists "
                            f"{name!r}, which is not a "
                            f"{'/'.join(reg.classes)} {reg.fields} leaf — "
                            "remove the stale entry",
                        )
                    )
        return
    fn = _find_func(sf, reg.func)
    if fn is None:
        out.append(
            Violation(
                sf.path,
                1,
                PASS_ID,
                f"registered state-leaf consumer {reg.func} (registry "
                f"'{reg.name}') not found in {sf.path} — update the "
                "stateleaf registry if it moved or was renamed",
            )
        )
        return
    if _has_generic_traversal(fn):
        return  # every leaf handled by construction
    tokens = _body_tokens(fn)
    for cls in reg.classes:
        sc = classes.get(cls)
        if sc is None:
            continue
        for leaf in sc.select(reg.fields):
            if leaf not in tokens and not sf.waived(fn.lineno, PASS_ID):
                out.append(
                    Violation(
                        sf.path,
                        fn.lineno,
                        PASS_ID,
                        f"state leaf {cls}.{leaf} is not handled in "
                        f"registry '{reg.name}' ({reg.func}): no "
                        "pytree-generic traversal and the leaf is never "
                        f"named — handle it or {CHECKLIST_HINT}",
                    )
                )


def _check_manifest(
    cls: StateClass, sf: SourceFile, const: str, out: List[Violation]
) -> None:
    names, line = _module_const_names(sf, const)
    if names is None:
        out.append(
            Violation(
                sf.path,
                line or cls.line,
                PASS_ID,
                f"leaf manifest {const} for {cls.name} missing or not a "
                f"literal tuple of strings in {sf.path} — the manifest is "
                f"the 'how to add a state leaf' checklist anchor",
            )
        )
        return
    for leaf in cls.fields:
        if leaf not in names and not sf.waived(cls.line, PASS_ID):
            out.append(
                Violation(
                    sf.path,
                    cls.line,
                    PASS_ID,
                    f"new state leaf {cls.name}.{leaf} is missing from "
                    f"{const} — {CHECKLIST_HINT} (fleet reset, ckpt meta, "
                    "compare_states, sanitize, DESIGN §12 if "
                    "allocation-indexed), then add it to the manifest",
                )
            )
    for name in sorted(names - set(cls.fields)):
        out.append(
            Violation(
                sf.path,
                line,
                PASS_ID,
                f"{const} lists {name!r}, which is not a field of "
                f"{cls.name} — remove the stale manifest entry",
            )
        )


def _check_design_doc(
    classes: Dict[str, StateClass], root: str, out: List[Violation]
) -> None:
    sc = classes.get(DESIGN_CLASS)
    if sc is None or sc.sf.path != STATE_CLASSES[DESIGN_CLASS]:
        return  # only meaningful against the real tree
    doc_path = os.path.join(root, DESIGN_DOC)
    if not os.path.exists(doc_path):
        return  # partial checkout; the docs job lints from the repo root
    with open(doc_path, encoding="utf-8") as fh:
        text = fh.read()
    start = text.find(f"\n{DESIGN_SECTION}")
    if start < 0:
        out.append(
            Violation(
                sc.sf.path,
                sc.line,
                PASS_ID,
                f"registry 'design-s12': section {DESIGN_SECTION!r} not "
                f"found in {DESIGN_DOC} — the allocation-index invariants "
                "list moved; update the stateleaf pass",
            )
        )
        return
    end = text.find("\n## ", start + 1)
    section = text[start : end if end > 0 else len(text)]
    for leaf in sc.structural:
        if not leaf.startswith(DESIGN_PREFIX):
            continue
        if leaf not in section and not sc.sf.waived(sc.line, PASS_ID):
            out.append(
                Violation(
                    sc.sf.path,
                    sc.line,
                    PASS_ID,
                    f"allocation-index leaf {DESIGN_CLASS}.{leaf} is not "
                    f"documented in the {DESIGN_DOC} {DESIGN_SECTION} "
                    "invariants list (registry 'design-s12') — name-order "
                    "semantics must be written down where the reclaim "
                    "protocol lives",
                )
            )


def _root_of(sf: SourceFile) -> str:
    # abspath ends with the repo-relative path; the prefix is the root.
    suffix = sf.path.replace("/", os.sep)
    ap = sf.abspath
    return ap[: -len(suffix)].rstrip(os.sep) if ap.endswith(suffix) else ""


def check(ctx: LintContext) -> List[Violation]:
    out: List[Violation] = []
    by_path = {sf.path: sf for sf in ctx.files}

    # Self-contained fixture modules: classes + consumers in one file.
    fixtures = [sf for sf in ctx.files if "state-module" in sf.pragmas]
    for sf in fixtures:
        classes = _find_classes(ctx.files, fixture=sf)
        if not classes:
            continue
        for cls, (const, _) in MANIFESTS.items():
            if cls in classes:
                _check_manifest(classes[cls], sf, const, out)
        for reg in CONSUMERS:
            if reg.manifest is not None:
                if _module_const_names(sf, reg.manifest)[1] is not None:
                    _check_consumer(reg, sf, classes, out)
                continue
            if _find_func(sf, reg.func) is not None:
                _check_consumer(reg, sf, classes, out)

    # The real tree: classes at their canonical paths, consumers at theirs.
    classes = _find_classes(
        [sf for sf in ctx.files if "state-module" not in sf.pragmas], None
    )
    if classes:
        for cls, sc in classes.items():
            const, path = MANIFESTS[cls]
            holder = by_path.get(path)
            if holder is not None:
                _check_manifest(sc, holder, const, out)
        for reg in CONSUMERS:
            sf = by_path.get(reg.path)
            if sf is None:
                continue  # consumer module out of scope (partial lint)
            if not any(c in classes for c in reg.classes):
                continue
            _check_consumer(reg, sf, classes, out)
        any_sc = next(iter(classes.values()))
        _check_design_doc(classes, _root_of(any_sc.sf), out)
    return out
