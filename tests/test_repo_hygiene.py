"""Repo-wide hygiene: every ``repro`` module imports, and no bytecode is
tracked in the tree."""
import pytest


def test_every_repro_module_imports():
    """Walk the whole ``repro`` package and import every module — a
    syntax error, a broken import or an accidental import-time side
    effect anywhere in the tree fails here, not in whichever test
    happens to touch the module first."""
    import importlib
    import pkgutil

    import repro

    failures = []
    for mod in pkgutil.walk_packages(repro.__path__, prefix="repro."):
        try:
            importlib.import_module(mod.name)
        except Exception as e:  # noqa: BLE001 - report every breakage
            failures.append(f"{mod.name}: {type(e).__name__}: {e}")
    assert not failures, "\n".join(failures)


def test_no_bytecode_artifacts_tracked():
    """No __pycache__/.pyc files may be committed (they shadow source
    edits and churn diffs); only meaningful when running from a git
    checkout."""
    import pathlib
    import subprocess

    root = pathlib.Path(__file__).resolve().parent.parent
    if not (root / ".git").exists():
        pytest.skip("not a git checkout")
    tracked = subprocess.run(
        ["git", "ls-files"], cwd=root, capture_output=True, text=True,
        check=True).stdout.splitlines()
    bad = [p for p in tracked
           if p.endswith((".pyc", ".pyo")) or "__pycache__" in p]
    assert not bad, f"bytecode artifacts committed: {bad}"
