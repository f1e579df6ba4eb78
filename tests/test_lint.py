"""ktpu-lint self-tests: golden-clean over the real package, and every
seeded-violation fixture must be caught by its pass (>= 2 fixtures per
pass, acceptance-gated). The fixtures live in tests/lint_fixtures/ —
excluded from the default lint scope, linted here explicitly."""

import os

import pytest

from kubernetriks_tpu.lint import run_lint, run_lint_report
from kubernetriks_tpu.lint.__main__ import DEFAULT_SCOPE, main as lint_main

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FIXTURES = os.path.join("tests", "lint_fixtures")


def _fixture(name: str):
    return os.path.join(FIXTURES, name)


def test_repo_is_golden_clean():
    """The whole default scope (package, chip_smoke.py, tests, scripts,
    experiments) lints clean under all NINE passes — every legitimate
    sync/draw/mix carries an explicit waiver with a reason — AND carries
    zero stale waivers (a *-ok that suppresses nothing would silently
    re-license a future violation). New violations fail CI here and in
    the dedicated lint job (--strict-waivers)."""
    scope = [p for p in DEFAULT_SCOPE if os.path.exists(os.path.join(ROOT, p))]
    report = run_lint_report(scope, ROOT)
    assert report.violations == [], "\n".join(
        v.render() for v in report.violations
    )
    assert report.stale_waivers == [], "\n".join(
        w.render() for w in report.stale_waivers
    )


def test_cli_exit_codes():
    """`python -m kubernetriks_tpu.lint` exits 0 on clean input, 1 on a
    seeded violation."""
    assert lint_main(["--root", ROOT, "kubernetriks_tpu/flags.py"]) == 0
    assert (
        lint_main(["--root", ROOT, _fixture("envflags_direct_read.py")]) == 1
    )


# (fixture file, pass id, expected minimum violations, message fragment)
FIXTURE_CASES = [
    ("donation_read_after_donate.py", "donation", 1, "after it was donated"),
    ("donation_alias_and_attribute.py", "donation", 1, "self.state"),
    ("donation_loop_carried.py", "donation", 1, "step_donated"),
    ("hostsync_item_and_asarray.py", "hostsync", 3, ".item()"),
    ("hostsync_cast_and_branch.py", "hostsync", 2, "int()"),
    ("hostsync_export_hook.py", "hostsync", 3, "np.asarray"),
    ("jitstatic_unknown_param.py", "jitstatic", 1, "max_pods"),
    ("jitstatic_pair_drift.py", "jitstatic", 1, "collect_gauges"),
    ("jitstatic_coupled_drift.py", "jitstatic", 1, "travel together"),
    ("prng_jax_random.py", "prng", 3, "jax.random"),
    ("prng_np_random.py", "prng", 2, "random"),
    ("envflags_direct_read.py", "envflags", 1, "KTPU_SUPERSPAN"),
    ("envflags_unregistered.py", "envflags", 3, "not declared"),
    # contract-prover passes (v2)
    ("stateleaf_missing_consumer.py", "stateleaf", 1, "compare-states"),
    ("stateleaf_manifest_drift.py", "stateleaf", 2, "CLUSTER_STATE_LEAVES"),
    ("scenariotrace_control_flow.py", "scenariotrace", 3, "control flow"),
    (
        "scenariotrace_shape_and_static.py",
        "scenariotrace",
        2,
        "shape expression",
    ),
    ("shapecontract_tolerance_mix.py", "shapecontract", 3, "[:, None]"),
    ("shapecontract_lane_major_mix.py", "shapecontract", 2, "lane-major"),
    ("feederlock_unlocked_touch.py", "feederlock", 3, "unlocked"),
    ("feederlock_blocking_wait.py", "feederlock", 2, "HOLDING the ring lock"),
]


@pytest.mark.parametrize(
    "fixture,pass_id,min_violations,fragment",
    FIXTURE_CASES,
    ids=[c[0] for c in FIXTURE_CASES],
)
def test_fixture_caught(fixture, pass_id, min_violations, fragment):
    violations = run_lint([_fixture(fixture)], ROOT, passes=[pass_id])
    rendered = "\n".join(v.render() for v in violations)
    assert len(violations) >= min_violations, rendered or "no violations"
    assert any(fragment in v.message for v in violations), rendered
    assert all(v.pass_id == pass_id for v in violations)
    # and the CLI gates on it (the CI job's contract)
    assert lint_main(["--root", ROOT, _fixture(fixture)]) == 1


@pytest.mark.parametrize(
    "fixture,pass_id",
    [(c[0], c[1]) for c in FIXTURE_CASES],
    ids=[c[0] for c in FIXTURE_CASES],
)
def test_fixture_all_passes_agree(fixture, pass_id):
    """Running ALL passes over a fixture still reports its seeded class
    (passes don't mask each other)."""
    violations = run_lint([_fixture(fixture)], ROOT)
    assert any(v.pass_id == pass_id for v in violations)


def test_no_false_positive_on_rebind_patterns():
    """The canonical safe patterns stay clean: `state = donated(state)`
    rebinds, alias rebinds through self.state, and a waived sync."""
    violations = run_lint(
        [_fixture("donation_read_after_donate.py")], ROOT, passes=["donation"]
    )
    lines = {v.line for v in violations}
    src_lines = open(
        os.path.join(ROOT, _fixture("donation_read_after_donate.py"))
    ).read().splitlines()
    good_start = next(
        i for i, line in enumerate(src_lines, 1) if "def good_driver" in line
    )
    assert all(line < good_start for line in lines), (
        "good_driver (rebind pattern) must not be flagged"
    )


def test_waiver_suppresses_with_reason_only():
    """A `# ktpu: sync-ok(reason)` waiver suppresses exactly its line; the
    same sync without a waiver in the same fixture is still reported."""
    violations = run_lint(
        [_fixture("hostsync_item_and_asarray.py")], ROOT, passes=["hostsync"]
    )
    src = open(
        os.path.join(ROOT, _fixture("hostsync_item_and_asarray.py"))
    ).read().splitlines()
    waived_lines = {
        i for i, line in enumerate(src, 1) if "ktpu: sync-ok" in line
    }
    assert waived_lines, "fixture must contain a waived sync"
    assert not (waived_lines & {v.line for v in violations})
    assert violations, "unwaived syncs must still be reported"


def test_observatory_and_export_are_hot_path_with_zero_waivers():
    """The capacity observatory's host half (telemetry/observatory.py)
    and its export seam (telemetry/export.py) carry the hot-path pragma
    — the host-sync pass patrols them like tracer.py — and stay
    golden-clean with ZERO sync-ok waivers: exports run strictly from
    drained host copies, never a device value."""
    from kubernetriks_tpu.lint import collect_files, is_hot

    paths = [
        "kubernetriks_tpu/telemetry/observatory.py",
        "kubernetriks_tpu/telemetry/export.py",
        "kubernetriks_tpu/telemetry/tracer.py",  # the PR 8 precedent
        "kubernetriks_tpu/telemetry/histogram.py",  # PR 17 query half
    ]
    files = collect_files(paths, ROOT)
    assert len(files) == len(paths)
    for sf in files:
        assert is_hot(sf), f"{sf.path} lost its hot-path pragma"
        src = open(os.path.join(ROOT, sf.path)).read()
        assert "ktpu: sync-ok" not in src, (
            f"{sf.path} grew a sync-ok waiver — the observatory/export "
            "half of telemetry must stay waiver-free (drained copies only)"
        )
    violations = run_lint(paths, ROOT, passes=["hostsync"])
    assert violations == [], "\n".join(v.render() for v in violations)


def test_jit_table_is_scanned_not_hardcoded():
    """The donated-entry table really comes from scanning jit sites: the
    package-wide context contains the engine's donated entries with their
    donated positions."""
    from kubernetriks_tpu.lint import build_context, collect_files

    files = collect_files(["kubernetriks_tpu"], ROOT)
    ctx = build_context(files)
    for entry in (
        "run_windows_donated",
        "run_windows_skip_donated",
        "run_superspan_donated",
        "hpa_pass_donated",
        "ca_pass_donated",
        "_fused_chunk_slide_donated",
    ):
        assert ctx.donated.get(entry) == (0,), (entry, ctx.donated.get(entry))
    # paired undonated entries resolved with identical statics (rule 2 ran
    # against real data)
    by_name = {e.name: e for e in ctx.jit_entries}
    assert frozenset(by_name["run_windows"].static_argnames) == frozenset(
        by_name["run_windows_donated"].static_argnames
    )


def test_flag_registry_truthiness(monkeypatch):
    """The ONE truthiness rule: '0'/''/'false'/'no'/'off' are false, unset
    takes the default, anything else is true — the bug class in which
    bool(os.environ.get(...)) made '0' truthy can't recur."""
    from kubernetriks_tpu.flags import flag_bool, flag_str, flag_tristate

    for falsy in ("0", "", "false", "No", "OFF"):
        monkeypatch.setenv("KTPU_DEBUG_FINITE", falsy)
        assert flag_bool("KTPU_DEBUG_FINITE") is False
    for truthy in ("1", "2", "true", "on"):
        monkeypatch.setenv("KTPU_DEBUG_FINITE", truthy)
        assert flag_bool("KTPU_DEBUG_FINITE") is True
    monkeypatch.delenv("KTPU_DEBUG_FINITE", raising=False)
    assert flag_bool("KTPU_DEBUG_FINITE") is False  # registered default
    assert flag_bool("KTPU_MEGAKERNEL") is True  # registered default
    monkeypatch.delenv("KTPU_SUPERSPAN", raising=False)
    assert flag_tristate("KTPU_SUPERSPAN") is None
    monkeypatch.setenv("KTPU_SUPERSPAN", "0")
    assert flag_tristate("KTPU_SUPERSPAN") is False
    monkeypatch.setenv("KUBERNETRIKS_LOG", "debug")
    assert flag_str("KUBERNETRIKS_LOG") == "debug"
    monkeypatch.delenv("KUBERNETRIKS_LOG", raising=False)
    assert flag_str("KUBERNETRIKS_LOG") == "INFO"
    with pytest.raises(KeyError):
        flag_bool("KTPU_NOT_REGISTERED")
    with pytest.raises(TypeError):
        flag_bool("KUBERNETRIKS_LOG")  # registered as str, read as bool
    # int flags (streaming pipeline knobs): unset/empty -> default, decimal
    # parses, a typo raises AT the registry instead of selecting a default.
    from kubernetriks_tpu.flags import flag_int

    monkeypatch.delenv("KTPU_STREAM_DEPTH", raising=False)
    assert flag_int("KTPU_STREAM_DEPTH") == 3
    monkeypatch.setenv("KTPU_STREAM_DEPTH", " 5 ")
    assert flag_int("KTPU_STREAM_DEPTH") == 5
    monkeypatch.setenv("KTPU_STREAM_DEPTH", "")
    assert flag_int("KTPU_STREAM_DEPTH") == 3
    monkeypatch.setenv("KTPU_STREAM_DEPTH", "two")
    with pytest.raises(ValueError):
        flag_int("KTPU_STREAM_DEPTH")
    monkeypatch.delenv("KTPU_STREAM_SEGMENT", raising=False)
    assert flag_int("KTPU_STREAM_SEGMENT") is None
    with pytest.raises(TypeError):
        flag_int("KTPU_DEBUG_FINITE")  # registered as bool, read as int


# --- contract-prover v2: state-leaf pass end-to-end --------------------------


def test_stateleaf_scratch_leaf_fails_against_real_tree(tmp_path):
    """THE acceptance gate for pass 6: a scratch leaf added to the REAL
    ClusterBatchState without touching any registry is caught. The test
    copies batched/state.py into a scratch repo layout, inserts a new
    field, and proves the stateleaf pass fails naming the leaf and the
    registries it missed (the untouched copy stays clean)."""
    src_path = os.path.join(ROOT, "kubernetriks_tpu", "batched", "state.py")
    src = open(src_path, encoding="utf-8").read()
    dest_dir = tmp_path / "kubernetriks_tpu" / "batched"
    dest_dir.mkdir(parents=True)
    dest = dest_dir / "state.py"

    # Untouched copy: clean (the classes, manifests and in-file
    # consumers — compare_states, strip_telemetry, init_state — agree).
    dest.write_text(src, encoding="utf-8")
    clean = run_lint(
        ["kubernetriks_tpu/batched/state.py"], str(tmp_path), passes=["stateleaf"]
    )
    assert clean == [], "\n".join(v.render() for v in clean)

    marker = "    nodes: NodeArrays\n"
    assert marker in src, "ClusterBatchState layout changed; update the test"
    dest.write_text(
        src.replace(marker, "    scratch_probe: jnp.ndarray\n" + marker, 1),
        encoding="utf-8",
    )
    violations = run_lint(
        ["kubernetriks_tpu/batched/state.py"], str(tmp_path), passes=["stateleaf"]
    )
    rendered = "\n".join(v.render() for v in violations)
    assert any(
        "scratch_probe" in v.message and "CLUSTER_STATE_LEAVES" in v.message
        for v in violations
    ), rendered or "scratch leaf escaped the manifest registry"
    # The required-field constructor registry catches it too.
    assert any(
        "scratch_probe" in v.message and "init-state" in v.message
        for v in violations
    ), rendered
    # And the CLI gates on it (the CI contract).
    assert (
        lint_main(["--root", str(tmp_path), "kubernetriks_tpu/batched/state.py"])
        == 1
    )


def test_stateleaf_scratch_clock_leaf_fails_against_real_tree(tmp_path):
    """The lane-async variant of the gate above: a scratch per-lane
    CLOCK leaf added to the REAL StepConstants without touching
    STEP_CONSTANTS_LEAVES is caught by the same tmp-tree e2e path (the
    untouched copy stays clean) — the 'how to add a consts leaf'
    checklist anchor for the DESIGN §13 clock protocol."""
    src_path = os.path.join(ROOT, "kubernetriks_tpu", "batched", "state.py")
    src = open(src_path, encoding="utf-8").read()
    dest_dir = tmp_path / "kubernetriks_tpu" / "batched"
    dest_dir.mkdir(parents=True)
    dest = dest_dir / "state.py"

    dest.write_text(src, encoding="utf-8")
    clean = run_lint(
        ["kubernetriks_tpu/batched/state.py"], str(tmp_path), passes=["stateleaf"]
    )
    assert clean == [], "\n".join(v.render() for v in clean)

    marker = "    lane_clock: Optional[jnp.ndarray] = None"
    assert marker in src, "StepConstants layout changed; update the test"
    dest.write_text(
        src.replace(
            marker,
            "    scratch_clock: Optional[jnp.ndarray] = None\n" + marker,
            1,
        ),
        encoding="utf-8",
    )
    violations = run_lint(
        ["kubernetriks_tpu/batched/state.py"], str(tmp_path), passes=["stateleaf"]
    )
    rendered = "\n".join(v.render() for v in violations)
    assert any(
        "scratch_clock" in v.message and "STEP_CONSTANTS_LEAVES" in v.message
        for v in violations
    ), rendered or "scratch clock leaf escaped the consts manifest"
    assert (
        lint_main(["--root", str(tmp_path), "kubernetriks_tpu/batched/state.py"])
        == 1
    )


def test_stateleaf_registries_match_runtime():
    """The AST-parsed manifests equal the live NamedTuple fields, the
    axis/scenario registries name real leaves, and the ckpt manifest
    covers exactly the structural leaves — the lint pass and the runtime
    can never drift apart silently."""
    from kubernetriks_tpu.batched import autoscale, state
    from kubernetriks_tpu.batched.engine import CKPT_COVERED_LEAVES

    assert state.CLUSTER_STATE_LEAVES == state.ClusterBatchState._fields
    assert state.TELEMETRY_RING_LEAVES == state.TelemetryRing._fields
    assert (
        autoscale.AUTOSCALE_STATE_LEAVES == autoscale.AutoscaleState._fields
    )
    assert state.STEP_CONSTANTS_LEAVES == state.StepConstants._fields
    # scenario-traced registries name real statics/consts leaves
    statics_fields = set(autoscale.AutoscaleStatics._fields)
    assert set(autoscale.SCENARIO_TRACED_LEAVES) <= statics_fields
    assert set(state.SCENARIO_TRACED_CONSTS) <= set(
        state.StepConstants._fields
    )
    # the pass's partial-scope fallback copy is pinned EQUAL to the
    # module manifests — the three spellings can never drift
    from kubernetriks_tpu.lint.scenariotrace import DEFAULT_TRACED

    assert DEFAULT_TRACED == set(autoscale.SCENARIO_TRACED_LEAVES) | set(
        state.SCENARIO_TRACED_CONSTS
    )
    # every fleet-composed leaf is registered as traced (compile-once)
    composed = {
        "hpa_interval",
        "hpa_tolerance",
        "ca_threshold",
        "ca_max_nodes",
        "pg_active_from",
        "d_hpa_up",
        "d_hpa_down",
        "d_ca_up",
        "d_ca_down",
        "ca_period",
        "ca_snap",
        "ca_finish_vis",
        "ca_commit_vis",
    }
    assert composed <= set(autoscale.SCENARIO_TRACED_LEAVES)
    # axis signatures name real leaves of the registered NamedTuples
    known = (
        statics_fields
        | set(autoscale.AutoscaleState._fields)
        | set(state.ClusterBatchState._fields)
        | set(state.NodeArrays._fields)
        | set(state.PodArrays._fields)
        | set(state.MetricArrays._fields)
        | set(state.StepConstants._fields)
    )
    for reg in (state.AXIS_SIGNATURES, autoscale.AXIS_SIGNATURES):
        unknown = set(reg) - known
        assert not unknown, f"AXIS_SIGNATURES names unknown leaves: {unknown}"
    # the lane-major-ambiguous set is exactly NODE_HOT_LEAVES
    node_sigs = {
        k for k, v in state.AXIS_SIGNATURES.items() if v == "@node"
    }
    assert node_sigs == set(state.NODE_HOT_LEAVES)
    # ckpt manifest == the structural (None-default) leaves
    structural = {
        f
        for cls in (state.ClusterBatchState, autoscale.AutoscaleState)
        for f in cls._fields
        if cls._field_defaults.get(f, "<nodefault>") is None
    }
    assert set(CKPT_COVERED_LEAVES) == structural


# --- contract-prover v2: stale-waiver detection ------------------------------


def test_stale_waiver_detection(tmp_path):
    """A waiver whose line no longer triggers its pass is reported stale;
    a load-bearing waiver is not; an unknown tag always is. The CLI exits
    0 by default (warning) and 1 under --strict-waivers."""
    fixture = tmp_path / "stale.py"
    fixture.write_text(
        "# ktpu: hot-path\n"
        "def readout(state):\n"
        "    # the USED waiver: .item() really syncs in a hot module\n"
        "    n = state.total.item()  # ktpu: sync-ok(readout at span boundary)\n"
        "    m = 1 + 1  # ktpu: sync-ok(nothing here syncs anymore)\n"
        "    k = 2  # ktpu: synk-ok(typo tag)\n"
        "    return n + m + k\n",
        encoding="utf-8",
    )
    report = run_lint_report([str(fixture)], str(tmp_path))
    assert report.violations == [], [v.render() for v in report.violations]
    lines = {w.line for w in report.stale_waivers}
    assert 5 in lines, "unused waiver not reported stale"
    assert 4 not in lines, "load-bearing waiver wrongly reported stale"
    assert any(
        w.line == 6 and "unknown waiver tag" in w.message
        for w in report.stale_waivers
    )
    assert lint_main(["--root", str(tmp_path), str(fixture)]) == 0
    assert (
        lint_main(
            ["--root", str(tmp_path), "--strict-waivers", str(fixture)]
        )
        == 1
    )


def test_stale_waivers_skipped_under_pass_filter(tmp_path):
    """--pass filters leave other passes' waivers unjudged (their usage
    was never recorded), so the CLI must not report them stale."""
    fixture = tmp_path / "filtered.py"
    fixture.write_text(
        "# ktpu: hot-path\n"
        "def f(state):\n"
        "    return state.total.item()  # ktpu: sync-ok(span boundary)\n",
        encoding="utf-8",
    )
    # envflags-only run: the sync-ok is out of judgment scope -> exit 0
    # even under --strict-waivers.
    assert (
        lint_main(
            [
                "--root",
                str(tmp_path),
                "--strict-waivers",
                "--pass",
                "envflags",
                str(fixture),
            ]
        )
        == 0
    )


# --- contract-prover v2: machine-readable output -----------------------------


def test_json_output(tmp_path, capsys):
    """--json emits file/line/pass/message records for violations and
    stale waivers — the CI annotation/artifact contract."""
    import json

    out_path = tmp_path / "lint.json"
    rc = lint_main(
        [
            "--root",
            ROOT,
            "--json",
            str(out_path),
            _fixture("scenariotrace_control_flow.py"),
        ]
    )
    assert rc == 1
    payload = json.loads(out_path.read_text())
    assert payload["counts"]["violations"] >= 3
    rec = payload["violations"][0]
    assert set(rec) >= {"file", "line", "pass", "message"}
    assert rec["pass"] == "scenariotrace"
    assert rec["file"].endswith("scenariotrace_control_flow.py")
    # --github annotations ride the same findings
    capsys.readouterr()
    lint_main(
        ["--root", ROOT, "--github", _fixture("scenariotrace_control_flow.py")]
    )
    out = capsys.readouterr().out
    assert "::error file=" in out and "ktpu-lint[scenariotrace]" in out


# --- contract-prover v2: doc sync --------------------------------------------

# Deliberate negatives in tests (never real flags).
_DOC_SYNC_ALLOW = {"KTPU_NOT_REGISTERED"}


def test_flag_doc_sync():
    """Every registered flag appears in README/DESIGN, and every KTPU_* /
    KUBERNETRIKS_* token in docs, chip_smoke.py, scripts and tests resolves
    to a registered flag (or a registered-prefix family like KTPU_STREAM_*) —
    renamed tuners can no longer leave stale documentation behind."""
    import glob
    import re

    from kubernetriks_tpu import flags

    docs = ""
    for p in ("README.md", os.path.join("docs", "DESIGN.md")):
        docs += open(os.path.join(ROOT, p), encoding="utf-8").read()
    undocumented = [n for n in flags.REGISTRY if n not in docs]
    assert not undocumented, (
        f"flags missing from README/DESIGN: {undocumented} — document "
        "them (the README 'Environment flags' table is the catch-all)"
    )

    scan = [os.path.join(ROOT, "README.md"), os.path.join(ROOT, "chip_smoke.py")]
    scan += glob.glob(os.path.join(ROOT, "docs", "*.md"))
    scan += glob.glob(os.path.join(ROOT, "tests", "*.py"))
    scan += glob.glob(os.path.join(ROOT, "scripts", "*.py"))
    bad = {}
    for path in scan:
        text = open(path, encoding="utf-8").read()
        for tok in set(re.findall(r"\b(?:KTPU|KUBERNETRIKS)_[A-Z0-9_]*", text)):
            name = tok.rstrip("_")
            if name in flags.REGISTRY or tok in _DOC_SYNC_ALLOW:
                continue
            # KTPU_STREAM_* style family references resolve to a prefix
            if tok.endswith("_") and any(
                k.startswith(tok) for k in flags.REGISTRY
            ):
                continue
            bad.setdefault(os.path.relpath(path, ROOT), []).append(tok)
    assert not bad, f"unregistered flag tokens in docs/tests: {bad}"


def test_every_registered_flag_has_a_reader():
    """A registered flag is READ by name somewhere outside flags.py: a
    `flag_*("NAME")` call in the package, chip_smoke.py or a test, or a row
    of the statics table (whose one loop reads its rows' flags). A mention
    in a comment or a docstring is no reader: a flag nothing reads goes,
    with its README row."""
    import ast
    import glob

    from kubernetriks_tpu import flags
    from kubernetriks_tpu.batched import statics

    files = glob.glob(os.path.join(ROOT, "kubernetriks_tpu", "**", "*.py"), recursive=True)
    files += glob.glob(os.path.join(ROOT, "tests", "*.py"))
    files.append(os.path.join(ROOT, "chip_smoke.py"))
    read = {row.flag for row in statics.TABLE if row.flag}
    for path in files:
        if os.path.samefile(path, flags.__file__):
            continue
        for node in ast.walk(ast.parse(open(path, encoding="utf-8").read())):
            if not (isinstance(node, ast.Call) and node.args):
                continue
            callee = getattr(node.func, "attr", None) or getattr(node.func, "id", "")
            name = node.args[0]
            if callee.startswith("flag_") and isinstance(name, ast.Constant):
                read.add(name.value)
    unread = sorted(set(flags.REGISTRY) - read)
    assert not unread, f"registered flags that nothing reads: {unread}"

