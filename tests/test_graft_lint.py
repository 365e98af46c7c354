"""Tier-1 gate for graft-lint (ISSUE 7): the static-analysis plane.

Three layers:

  1. The GATE — the repo must be clean modulo the committed baseline
     (`script/lint_baseline.json`), and the baseline itself must carry
     no stale (already-paid) debt.  A new blocking call in a coroutine,
     a fire-and-forget create_task, a silent `except Exception`, an
     unpaired gauge, or an undeclared config-knob read fails here.
  2. NEGATIVE FIXTURES — every rule family is proven to FIRE against
     `tests/fixtures/lint/` (a rule that silently stopped matching
     would otherwise look like a clean repo).
  3. MECHANICS — baseline drift detection, pragma handling (including
     bad pragmas), stdlib-only imports, CLI exit codes.
"""

import ast
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), os.pardir))
sys.path.insert(0, REPO)

from garage_tpu.analysis import analyze  # noqa: E402
from garage_tpu.analysis.core import (  # noqa: E402
    diff_baseline,
    load_baseline,
    write_baseline,
)

BASELINE = os.path.join(REPO, "script", "lint_baseline.json")
FIXTURES = "tests/fixtures/lint"

# the knob rule needs the section-dataclass inventory from config.py
CONFIG = "garage_tpu/utils/config.py"

ALL_FAMILIES = {
    "loop-blocker", "orphan-task", "swallowed-exception",
    "resource-discipline", "cancel-safety", "lock-await",
    "trust-boundary", "wire-compat",
    "host-sync", "recompile-hazard", "use-after-donation", "backend-gate",
}

# tier-1 per-rule-family wall budget (msec): the slowest family measures
# ~0.6 s on the slow CI box, so 2 s is margin, not slack — a family that
# blows it has rotted the pre-commit loop
RULE_BUDGET_MSEC = 2000


def lint(*paths, rules=None):
    return analyze(REPO, list(paths), rules)


# --- 1. the gate --------------------------------------------------------------


def test_repo_clean_modulo_baseline():
    violations = lint("garage_tpu")
    baseline = load_baseline(BASELINE)
    new, stale = diff_baseline(violations, baseline)
    assert not new, "NEW graft-lint violations (fix or triage via " \
        "`python script/graft_lint.py --write-baseline`):\n" + "\n".join(
            v.render() for v in new
        )
    assert not stale, (
        "baseline carries PAID debt — regenerate with --write-baseline: "
        f"{stale}"
    )


def test_loop_blocker_baseline_empty_on_data_plane():
    """Acceptance: the data plane (block/, net/, api/) carries ZERO
    triaged-but-unfixed loop blockers — every finding there was fixed,
    not baselined."""
    baseline = load_baseline(BASELINE)
    offenders = [
        k
        for k in baseline
        if k.startswith(
            (
                "loop-blocker:garage_tpu/block/",
                "loop-blocker:garage_tpu/net/",
                "loop-blocker:garage_tpu/api/",
            )
        )
    ]
    assert offenders == []


def test_script_paths_also_clean():
    # the gate scripts hold the repo to the same bar
    violations = lint("script/graft_lint.py")
    assert violations == []


# --- 2. negative fixtures: every rule family fires ----------------------------


def test_fixture_loop_blocker_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/blocking_coroutine.py")
        if v.rule == "loop-blocker"
    ]
    by_symbol = {v.symbol for v in vs}
    # direct blocking calls in the coroutine
    assert "direct_blocker" in by_symbol
    # propagated through TWO levels of sync helpers
    assert "indirect_blocker" in by_symbol
    details = " ".join(v.detail for v in vs)
    assert "os.replace" in details  # the depth-2 call is attributed
    # the pragma'd coroutine is suppressed
    assert "suppressed_blocker" not in by_symbol
    # both direct sites (open + fsync) and both propagated sites
    assert len(vs) >= 4


def test_fixture_loop_blocker_follows_module_imports():
    """`from . import mod` bindings: `mod.helper()` chains resolve into
    the helper's own file (regression — these used to map to the package
    directory and silently drop the chain)."""
    vs = [
        v
        for v in lint(
            f"{FIXTURES}/blocking_import_user.py", f"{FIXTURES}/helper_mod.py"
        )
        if v.rule == "loop-blocker"
    ]
    assert len(vs) == 1
    assert vs[0].symbol == "uses_module_helper"
    assert vs[0].path.endswith("helper_mod.py")
    assert "os.fsync" in vs[0].detail


def test_fixture_orphan_task_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/orphan_task.py")
        if v.rule == "orphan-task"
    ]
    assert len(vs) == 2  # create_task + ensure_future; pragma + stored fine
    assert {v.symbol for v in vs} == {"spawner"}


def test_fixture_swallowed_exception_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/silent_swallow.py")
        if v.rule == "swallowed-exception"
    ]
    assert {v.symbol for v in vs} == {"silent", "silent_tuple"}


def test_fixture_unpaired_gauge_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/leaky_gauge.py")
        if v.rule == "resource-discipline"
    ]
    assert len(vs) == 1
    assert vs[0].symbol == "LeakyWorker"
    assert "leaky_worker_gauge" in vs[0].detail


def test_fixture_unvalidated_knob_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/unvalidated_knob.py", CONFIG)
        if v.rule == "resource-discipline"
    ]
    assert len(vs) == 1
    assert "admin.totally_made_up_knob" in vs[0].detail
    # declared knobs and non-config receivers stay quiet (asserted by
    # the ==1 above: the fixture contains both)


def test_fixture_cancel_safety_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/cancel_unsafe.py")
        if v.rule == "cancel-safety"
    ]
    details = {v.detail for v in vs}
    symbols = {v.symbol for v in vs}
    # all three sub-rules fire
    assert "finally-await:conn.teardown" in details
    assert "cancelled-swallowed" in details
    assert any(d.startswith("cancel-no-drain:") for d in details)
    # good variants stay quiet: shield/reap finally, re-raise handler,
    # gather drain, alias drain, caller-side drain-of-another-task
    assert symbols == {"finally_awaiter", "swallower", "canceller"}


def test_fixture_lock_await_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/lock_rpc.py") if v.rule == "lock-await"
    ]
    symbols = {v.symbol for v in vs}
    assert symbols == {
        "Api.bad_rpc_under_lock",
        "Api.bad_wait_under_lock",
        "Api.bad_resolved_rpc",  # via name-resolved helper hop
    }
    # semaphores, pure compute, and the pragma'd hold stay quiet
    assert "Api.ok_semaphore" not in symbols
    assert "Api.ok_pragma" not in symbols


def test_fixture_taint_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/tainted_label.py")
        if v.rule == "trust-boundary"
    ]
    details = {v.detail for v in vs}
    assert "metric:register_gauge:key_id" in details  # raw label
    assert "log:warning:key_id" in details  # f-string log
    assert "path:join:key_id" in details  # filesystem sink
    assert "metric:set_gauge:dig" in details  # gossiped digest source
    # the one-hop interprocedural flow lands on the callee's gauge call
    assert "metric:register_gauge:tid" in details
    # _esc-wrapped label and %-style logging stay quiet
    symbols = {v.symbol for v in vs}
    assert "Admission.ok_escaped" not in symbols
    assert "Admission.ok_percent_log" not in symbols


def test_fixture_deep_resolution_fires():
    """PR 7's documented limit — `self.persister.save(...)` invisible to
    the loop-blocker — is lifted: constructor AND annotation-tracked
    receivers resolve into the target class."""
    vs = [
        v for v in lint(f"{FIXTURES}/deep_resolution.py")
        if v.rule == "loop-blocker"
    ]
    assert {v.symbol for v in vs} == {
        "Planner.checkpoint",  # self.persister = FilePersister() if ...
        "Planner.checkpoint_annotated",  # p: "FilePersister | None"
    }
    assert all("FilePersister.save" in v.detail for v in vs)


def test_fixture_host_sync_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/host_sync_async.py")
        if v.rule == "host-sync"
    ]
    by_symbol = {v.symbol for v in vs}
    details = {v.detail for v in vs}
    # direct sync points in the coroutine
    assert "direct_sync" in by_symbol
    # block_until_ready AND the scalar extraction both fire
    assert "block_until_ready" in details
    assert "float" in details
    # propagated through one sync helper hop, attributed to the helper
    assert any(d.startswith("np.asarray|helper_fetch") for d in details)
    # to_thread hop, plain-numpy asarray, and pragma stay quiet
    assert by_symbol == {"direct_sync", "until_ready", "indirect_sync"}


def test_fixture_recompile_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/recompile_unbucketed.py")
        if v.rule == "recompile-hazard"
    ]
    details = {v.detail for v in vs}
    symbols = {v.symbol for v in vs}
    # unbucketed dispatch fires; pad-provenance (direct + through a
    # wrapper call) and the pragma stay quiet
    assert "unbucketed-dispatch:fn" in details
    assert "bad_dispatch" in symbols
    assert "ok_dispatch" not in symbols
    assert "ok_wrapped_provenance" not in symbols
    assert "ok_pragma" not in symbols
    # python control flow on a traced param fires (if + for); shape
    # attributes and `is None` stay quiet
    assert "traced-branch:flag" in details
    assert "traced-branch:x" in details
    assert len([d for d in details if d.startswith("traced-branch")]) == 2


def test_fixture_donation_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/donated_reuse.py")
        if v.rule == "use-after-donation"
    ]
    details = {v.detail for v in vs}
    symbols = {v.symbol for v in vs}
    assert "use-after-donation:fn:batch" in details
    assert "donated-reuse-in-loop:fn:batch" in details
    # the advisory fires on the undonated bucketed dispatch
    assert "undonated-dispatch:fn" in details
    # fresh-rebind-per-iteration, last-use, and the pragma stay quiet
    assert symbols == {"use_after", "loop_reuse", "advisory_undonated"}


def test_fixture_backend_gate_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/backend_string.py")
        if v.rule == "backend-gate"
    ]
    symbols = {v.symbol for v in vs}
    assert symbols == {"bad_gate", "bad_env_gate"}
    assert all(v.detail.startswith("platform-compare:") for v in vs)
    # a config-key compare and the pragma'd probe stay quiet (asserted
    # by the symbol set above: the fixture contains both)


def test_fixture_uncounted_codec_path_fires():
    """The codec/ subdirectory is load-bearing: the sub-rule scopes to
    /codec/ modules."""
    vs = [
        v for v in lint(f"{FIXTURES}/codec/uncounted.py")
        if v.rule == "backend-gate"
    ]
    assert len(vs) == 1
    assert vs[0].symbol == "UncountedCodec.encode_batch"
    assert vs[0].detail == "uncounted-codec-path:encode_batch"
    # counted and pragma'd dispatches stay quiet


def test_fixture_crdt_mutation_fires():
    vs = [
        v for v in lint(f"{FIXTURES}/model/bad_crdt.py")
        if v.rule == "wire-compat"
    ]
    assert len(vs) == 1
    assert vs[0].symbol == "BadRegister.sneaky_set"
    # __init__/merge/update mutations are the allowed discipline
    assert "sneaky_set" in vs[0].detail


# --- wire-schema drift --------------------------------------------------------


DIGEST_SRC = '''\
DIGEST_VERSION = {version}

class DigestCollector:
    def collect(self):
        digest = {{
            "v": DIGEST_VERSION,
            "up": 1.0,
            "s3": {{{s3_keys}}},
        }}
        return digest
'''

FRAME_SRC = '''\
async def call(endpoint):
    meta = {{{meta_keys}}}
    return meta
'''

MIGR_SRC = '''\
class Persisted:
    VERSION_MARKER = b"{marker}"
    PREVIOUS = {previous}
'''


def _write_wire_tree(root, *, version=1, s3_keys='"rps": 1.0, "req": 7',
                     meta_keys='"ep": "x", "prio": 0',
                     marker="T0thing", previous="None"):
    import pathlib

    root = pathlib.Path(root)
    (root / "garage_tpu/rpc").mkdir(parents=True, exist_ok=True)
    (root / "garage_tpu/net").mkdir(parents=True, exist_ok=True)
    (root / "script").mkdir(exist_ok=True)
    (root / "garage_tpu/rpc/telemetry_digest.py").write_text(
        DIGEST_SRC.format(version=version, s3_keys=s3_keys)
    )
    (root / "garage_tpu/net/connection.py").write_text(
        FRAME_SRC.format(meta_keys=meta_keys)
    )
    (root / "garage_tpu/migr.py").write_text(
        MIGR_SRC.format(marker=marker, previous=previous)
    )
    return str(root)


def _wire_violations(root):
    return [
        v for v in analyze(root, ["garage_tpu"], ["wire-compat"])
        if v.detail != "wire-schema:missing"
    ]


def test_wire_schema_drift(tmp_path):
    """Acceptance: deleting a digest key or frame meta key without a
    DIGEST_VERSION bump fails; adding keys is clean; bump + snapshot
    regeneration is clean."""
    from garage_tpu.analysis.core import Project
    from garage_tpu.analysis.wire_compat import write_wire_schema

    root = _write_wire_tree(tmp_path)

    def snapshot():
        p = Project(root)
        p.add_tree("garage_tpu")
        write_wire_schema(p)

    snapshot()
    assert _wire_violations(root) == []

    # (a) digest key removed, version unchanged -> violation
    _write_wire_tree(tmp_path, s3_keys='"req": 7')
    vs = _wire_violations(root)
    assert any(v.detail == "digest-key-removed:s3.rps" for v in vs)

    # (b) key ADDED, version unchanged -> clean (additive evolution)
    _write_wire_tree(tmp_path, s3_keys='"rps": 1.0, "req": 7, "p99": 0.1')
    assert _wire_violations(root) == []

    # (c) removal WITH a version bump -> only the regenerate reminder,
    #     and after regenerating the snapshot the tree is clean
    _write_wire_tree(tmp_path, version=2, s3_keys='"req": 7')
    vs = _wire_violations(root)
    assert [v.detail for v in vs] == ["wire-schema:version-drift"]
    snapshot()
    assert _wire_violations(root) == []

    # (d) frame meta key removed without a bump -> violation
    _write_wire_tree(tmp_path, version=2, s3_keys='"req": 7',
                     meta_keys='"ep": "x"')
    vs = _wire_violations(root)
    assert any(v.detail == "frame-meta-removed:prio" for v in vs)

    # (e) Migratable marker changed without PREVIOUS -> violation;
    #     with PREVIOUS declared -> clean
    _write_wire_tree(tmp_path, version=2, s3_keys='"req": 7',
                     marker="T1thing")
    vs = _wire_violations(root)
    assert any(
        v.detail == "migratable-marker-changed:Persisted" for v in vs
    )
    _write_wire_tree(tmp_path, version=2, s3_keys='"req": 7',
                     marker="T1thing", previous="object")
    assert _wire_violations(root) == []


def test_wire_schema_committed_and_current():
    """The committed snapshot must match the tree (a drifted snapshot
    would make every future edit look like removal)."""
    from garage_tpu.analysis.core import Project
    from garage_tpu.analysis.wire_compat import build_schema

    p = Project(REPO)
    p.add_tree("garage_tpu")
    want = build_schema(p)
    got = json.load(open(os.path.join(REPO, "script", "wire_schema.json")))
    assert got["digest_version"] == want["digest_version"]
    assert got["digest_keys"] == want["digest_keys"]
    assert got["frame_meta_keys"] == want["frame_meta_keys"]
    assert got["migratable_markers"] == want["migratable_markers"]


# --- 3. mechanics -------------------------------------------------------------


def test_baseline_drift_new_violation_fails(tmp_path):
    """A newly introduced violation must NOT be absorbed by the
    baseline: simulate by baselining the current fixture findings, then
    adding one more."""
    vs = lint(f"{FIXTURES}/orphan_task.py")
    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), vs)
    baseline = load_baseline(str(bl))
    # same findings: clean
    new, stale = diff_baseline(vs, baseline)
    assert not new and not stale
    # one MORE occurrence of an existing key: caught
    new, _ = diff_baseline(vs + [vs[0]], baseline)
    assert len(new) == 1
    # a paid-off finding: reported stale
    _, stale = diff_baseline(vs[1:], baseline)
    assert stale


def test_fresh_violation_in_repo_tree_fails_gate(tmp_path):
    """End-to-end drift: a tree that was clean gains a violation; the
    CLI exits 1 against its previously-written baseline."""
    pkg = tmp_path / "pkg"
    pkg.mkdir()
    (pkg / "ok.py").write_text("async def f():\n    return 1\n")
    bl = tmp_path / "bl.json"
    vs = analyze(str(tmp_path), ["pkg"])
    write_baseline(str(bl), vs)
    (pkg / "bad.py").write_text(
        "import time\n\nasync def g():\n    time.sleep(1)\n"
    )
    vs2 = analyze(str(tmp_path), ["pkg"])
    new, _ = diff_baseline(vs2, load_baseline(str(bl)))
    assert len(new) == 1 and new[0].rule == "loop-blocker"


def test_bad_pragmas_are_violations(tmp_path):
    (tmp_path / "p.py").write_text(
        "import time\n"
        "async def f():\n"
        "    # graft-lint: allow-blocking()\n"
        "    time.sleep(1)\n"
        "def g():\n"
        "    pass  # graft-lint: allow-everything(nope)\n"
    )
    vs = analyze(str(tmp_path), ["p.py"])
    kinds = {v.detail for v in vs if v.rule == "pragma"}
    assert "empty-reason:blocking" in kinds
    # PRAGMA_RE captures the kind AFTER "allow-"
    assert "unknown:everything" in kinds
    # the empty-reason pragma still suppresses nothing extra to test
    # here; the loop-blocker itself IS suppressed (reason quality is a
    # separate, also-failing, finding)


def test_pragma_in_string_does_not_suppress(tmp_path):
    """Pragma text quoted in a string/docstring must NOT register a live
    suppression (pragmas are comments, found via tokenize)."""
    (tmp_path / "q.py").write_text(
        "import time\n"
        "async def f():\n"
        '    x = "hint: # graft-lint: allow-blocking(quoted, not a pragma)"\n'
        "    time.sleep(1)\n"
        "    return x\n"
    )
    vs = analyze(str(tmp_path), ["q.py"])
    assert [v.rule for v in vs] == ["loop-blocker"]


def test_analyzer_imports_stdlib_only():
    """Acceptance: the analyzer must run in the bare container — stdlib
    imports only (plus intra-package relatives)."""
    import sys as _sys

    stdlib = set(_sys.stdlib_module_names)
    adir = os.path.join(REPO, "garage_tpu", "analysis")
    present = {n for n in os.listdir(adir) if n.endswith(".py")}
    # the guard must actually cover the ISSUE 10 + ISSUE 11 rule files —
    # a rename would silently drop them from this loop
    assert {
        "cancel_safety.py", "lock_await.py", "taint.py", "wire_compat.py",
        "host_sync.py", "recompile.py", "donation.py", "backend_gate.py",
        "device_model.py",
    } <= present
    for name in sorted(present):
        tree = ast.parse(open(os.path.join(adir, name)).read())
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                for a in node.names:
                    root = a.name.split(".")[0]
                    assert root in stdlib, f"{name}: non-stdlib import {a.name}"
            elif isinstance(node, ast.ImportFrom):
                if node.level:
                    continue  # relative: inside the package
                root = (node.module or "").split(".")[0]
                assert root in stdlib, f"{name}: non-stdlib import {node.module}"


def test_cli_exit_codes():
    script = os.path.join(REPO, "script", "graft_lint.py")
    # clean repo against the committed baseline -> 0
    r = subprocess.run(
        [sys.executable, script], capture_output=True, text=True, cwd=REPO
    )
    assert r.returncode == 0, r.stdout + r.stderr
    # fixtures without baseline -> 1, and findings are printed
    r = subprocess.run(
        [sys.executable, script, "--no-baseline",
         f"{FIXTURES}/orphan_task.py"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 1
    assert "orphan-task" in r.stdout
    # JSON mode parses, and carries per-rule timings
    r = subprocess.run(
        [sys.executable, script, "--no-baseline", "--json",
         f"{FIXTURES}/orphan_task.py"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 1
    obj = json.loads(r.stdout)
    assert len(obj["new"]) == 2
    assert set(obj["timings"]) == ALL_FAMILIES
    assert all(t >= 0 for t in obj["timings"].values())


def test_cli_diff_mode():
    """--diff lints only files changed vs a git ref (the pre-commit
    loop).  Against HEAD with a clean tree it reports nothing to do;
    an unknown ref is a usage error, not a crash."""
    script = os.path.join(REPO, "script", "graft_lint.py")
    r = subprocess.run(
        [sys.executable, script, "--diff", "HEAD"],
        capture_output=True, text=True, cwd=REPO,
    )
    # clean tree -> "no analyzable files changed" (0) or, with local
    # edits in flight, a normal lint over just those files
    assert r.returncode == 0, r.stdout + r.stderr
    r = subprocess.run(
        [sys.executable, script, "--diff", "no-such-ref-xyzzy"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 2
    assert "git diff" in r.stderr


def test_cli_rules_selection():
    """--rules runs exactly the named families — including the ISSUE 11
    accelerator set — and an unknown family is a usage error."""
    script = os.path.join(REPO, "script", "graft_lint.py")
    r = subprocess.run(
        [sys.executable, script, "--no-baseline", "--json",
         "--rules", "host-sync,backend-gate",
         f"{FIXTURES}/backend_string.py"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 1
    obj = json.loads(r.stdout)
    assert set(obj["timings"]) == {"host-sync", "backend-gate"}
    assert all(v["rule"] == "backend-gate" for v in obj["new"])
    r = subprocess.run(
        [sys.executable, script, "--rules", "no-such-family"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 2
    assert "unknown rule" in r.stderr


def test_cli_rule_budget_holds_at_tier1():
    """Acceptance: the full 12-family run over the whole package stays
    under the declared per-rule budget — the plane must not rot the
    pre-commit loop as families accrete."""
    script = os.path.join(REPO, "script", "graft_lint.py")
    r = subprocess.run(
        [sys.executable, script, "--json",
         "--max-rule-msec", str(RULE_BUDGET_MSEC)],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    obj = json.loads(r.stdout)
    assert set(obj["timings"]) == ALL_FAMILIES
    assert obj["budget_msec"] == RULE_BUDGET_MSEC
    assert obj["over_budget"] == {}


def test_cli_rule_budget_exceeded_is_exit_2():
    """An impossible budget trips every family: exit 2 (usage-class,
    distinct from exit 1 = violations) and the offenders are named."""
    script = os.path.join(REPO, "script", "graft_lint.py")
    r = subprocess.run(
        [sys.executable, script, "--json", "--max-rule-msec", "0",
         f"{FIXTURES}/backend_string.py"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 2
    assert "rule budget exceeded" in r.stderr
    obj = json.loads(r.stdout)
    assert obj["over_budget"]  # every family is over a 0 ms budget


def test_cli_diff_previous_commit_smoke():
    """`--diff HEAD~1` (the post-commit sanity loop) lints whatever the
    last commit touched, against the committed baseline: a committed
    tree must come out clean."""
    script = os.path.join(REPO, "script", "graft_lint.py")
    r = subprocess.run(
        [sys.executable, script, "--diff", "HEAD~1"],
        capture_output=True, text=True, cwd=REPO,
    )
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_diff_untracked_union_catches_accelerator_rules():
    """Regression for the PR 10 untracked-file union: a brand-new
    (never-committed) file full of accelerator hazards must fail
    --diff, which `git diff` alone would never list."""
    script = os.path.join(REPO, "script", "graft_lint.py")
    scratch = os.path.join(REPO, "garage_tpu", "_lint_scratch_issue11.py")
    src = (
        "import asyncio\n"
        "import jax\n"
        "import numpy as np\n"
        "def make_fn():\n"
        "    def body(x):\n"
        "        return x + 1\n"
        "    return jax.jit(body)\n"
        "async def bad(plat):\n"
        "    fn = make_fn()\n"
        "    if plat == 'cpu':\n"
        "        return None\n"
        "    return np.asarray(fn(np.zeros(4, np.uint8)))\n"
    )
    try:
        with open(scratch, "w", encoding="utf-8") as f:
            f.write(src)
        r = subprocess.run(
            [sys.executable, script, "--diff", "HEAD"],
            capture_output=True, text=True, cwd=REPO,
        )
        assert r.returncode == 1, r.stdout + r.stderr
        assert "host-sync" in r.stdout
        assert "recompile-hazard" in r.stdout
        assert "backend-gate" in r.stdout
    finally:
        os.remove(scratch)


@pytest.mark.slow
def test_sanitize_all_alongside_lint_gate():
    """CI-style pairing (ISSUE 11 satellite): the native sanitizer
    sweep runs next to the lint gate — one summary table, PASS on every
    mode."""
    import shutil

    if shutil.which("g++") is None:
        pytest.skip("g++ unavailable")
    r = subprocess.run(
        [os.path.join(REPO, "script", "sanitize-native.sh"), "--all"],
        cwd=REPO, capture_output=True, text=True, timeout=600,
    )
    assert r.returncode == 0, r.stdout + r.stderr
    assert "sanitize-native summary" in r.stdout
    for mode in ("tsan", "asan", "ubsan"):
        assert f"{mode}\tPASS" in r.stdout, r.stdout


def test_reap_propagates_caller_cancellation():
    """reap() must not eat a cancel aimed at the CALLING coroutine: a
    k2v long-poll cancelled while its finally-block reaps stragglers
    has to end cancelled, not resume and complete (regression for the
    per-task `except CancelledError: pass` drain)."""
    import asyncio

    from garage_tpu.utils.aio import reap

    async def main():
        entered = asyncio.Event()
        started = asyncio.Event()
        resumed = []

        async def slow_straggler():
            started.set()
            try:
                await asyncio.sleep(30)
            except asyncio.CancelledError:
                await asyncio.sleep(0.2)  # slow cancel teardown
                raise

        async def handler():
            loop = asyncio.get_event_loop()
            stragglers = [loop.create_task(slow_straggler())]
            await started.wait()  # straggler is parked in its sleep
            entered.set()
            await reap(stragglers)  # outer cancel lands HERE, mid-drain
            resumed.append(True)  # must NOT run after an outer cancel

        h = asyncio.get_event_loop().create_task(handler())
        await entered.wait()
        await asyncio.sleep(0.05)  # reap is now awaiting the teardown
        h.cancel()
        with pytest.raises(asyncio.CancelledError):
            await h
        assert h.cancelled()
        assert not resumed

    asyncio.run(main())


def test_supervised_spawn_logs_and_drains():
    """The orphan-task remedy itself: spawn_supervised logs crashes via
    the correlated logger and drops its strong reference afterwards."""
    import asyncio
    import logging

    from garage_tpu.utils.aio import spawn_supervised, supervised_count

    async def boom():
        raise RuntimeError("kaboom")

    async def ok():
        return 42

    records = []

    class Capture(logging.Handler):
        def emit(self, record):
            records.append(record)

    async def main():
        h = Capture()
        logging.getLogger("garage.aio").addHandler(h)
        try:
            t1 = spawn_supervised(boom(), name="boom-task")
            t2 = spawn_supervised(ok(), name="ok-task")
            assert supervised_count() >= 2
            await asyncio.gather(t1, t2, return_exceptions=True)
            await asyncio.sleep(0)  # let done-callbacks run
        finally:
            logging.getLogger("garage.aio").removeHandler(h)
        assert supervised_count() == 0
        assert any(
            "boom-task" in r.getMessage() and "kaboom" in r.getMessage()
            for r in records
        )
        # the successful task logged nothing
        assert not any("ok-task" in r.getMessage() for r in records)

    asyncio.run(main())
