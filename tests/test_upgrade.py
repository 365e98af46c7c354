"""Upgrade test (reference script/test-upgrade.sh:14-25): a store written
by the previous release (round-1 commit, via a git worktree) must be
readable — and writable — by the current code.

Validates the persisted-format chain end to end: sqlite trees, Migratable
version markers, block files, key/bucket tables.
"""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "tests", "upgrade_script.py")


def _old_release_commit() -> str | None:
    """The last commit of the previous round (its VERDICT/bench commit)."""
    try:
        out = subprocess.run(
            ["git", "log", "--format=%H %s"],
            cwd=REPO, capture_output=True, text=True, timeout=30,
        ).stdout
    except Exception:  # noqa: BLE001
        return None
    for line in out.splitlines():
        sha, _, subject = line.partition(" ")
        if "VERDICT" in subject and "round" in subject.lower():
            return sha
    return None


def _run(script_args, pythonpath, timeout=180):
    env = dict(os.environ)
    env["PYTHONPATH"] = pythonpath
    env["JAX_PLATFORMS"] = "cpu"
    return subprocess.run(
        [sys.executable, SCRIPT, *script_args],
        env=env, capture_output=True, text=True, timeout=timeout,
    )


def test_old_store_survives_upgrade(tmp_path):
    commit = _old_release_commit()
    if commit is None:
        pytest.skip("no previous-round commit found in history")
    worktree = tmp_path / "old-release"
    add = subprocess.run(
        ["git", "worktree", "add", "--detach", str(worktree), commit],
        cwd=REPO, capture_output=True, text=True, timeout=60,
    )
    if add.returncode != 0:
        pytest.skip(f"git worktree failed: {add.stderr[:200]}")
    try:
        store = str(tmp_path / "store")
        os.makedirs(store)
        # 1. write with the OLD release
        w = _run(["write", store], pythonpath=str(worktree))
        if w.returncode != 0 and "ModuleNotFoundError" in (w.stderr or ""):
            # the old release hard-imports optional deps (zstandard,
            # cryptography) that this stripped container doesn't carry;
            # only the current code has stdlib fallbacks
            pytest.skip(
                "old release cannot run here (missing optional deps): "
                + (w.stderr or "").strip().splitlines()[-1]
            )
        assert w.returncode == 0 and "WRITE-OK" in w.stdout, (
            f"old-version write failed:\n{w.stdout}\n{w.stderr[-2000:]}"
        )
        # 2. read (and write again) with the CURRENT code
        r = _run(["read", store], pythonpath=REPO)
        assert r.returncode == 0 and "READ-OK" in r.stdout, (
            f"reading old store with new code failed:\n{r.stdout}\n{r.stderr[-2000:]}"
        )
    finally:
        subprocess.run(
            ["git", "worktree", "remove", "--force", str(worktree)],
            cwd=REPO, capture_output=True, timeout=60,
        )
