"""Tier-1 guard: every ``repro.*`` import target exists on disk.

This is the check that would have caught the seed regression where ten
modules imported ``repro.dist.sharding`` but ``src/repro/dist/`` was never
committed, failing collection of the whole suite.
"""

import pathlib
import subprocess
import textwrap

import pytest

from repro.tools.import_integrity import find_missing_imports

REPO_ROOT = pathlib.Path(__file__).resolve().parents[1]


def test_all_repro_imports_resolve():
    assert find_missing_imports(REPO_ROOT) == []


def test_no_tracked_bytecode():
    """Compiled bytecode must never be committed: it bloats diffs, goes
    stale silently, and once slipped a whole ``__pycache__`` tree into a PR.
    Nor must what test runs and entry points generate: hypothesis's
    example database and JAX's compilation cache.  ``.gitignore``
    keeps new ones out; this guards the index itself."""
    try:
        res = subprocess.run(["git", "ls-files"], cwd=REPO_ROOT,
                             capture_output=True, text=True, timeout=60)
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("git unavailable")
    if res.returncode != 0:
        pytest.skip("not a git checkout")
    tracked = res.stdout.splitlines()
    generated = {"__pycache__", ".hypothesis", ".jax_cache"}
    offenders = [f for f in tracked
                 if f.endswith(".pyc") or generated & set(f.split("/"))]
    assert offenders == [], (
        f"tracked bytecode files (git rm --cached them): {offenders[:10]}")
    gitignore = REPO_ROOT / ".gitignore"
    assert gitignore.exists() and ".gitignore" in tracked
    rules = gitignore.read_text().splitlines()
    for required in ("__pycache__/", "*.pyc", ".jaxlint-cache.json",
                     ".hypothesis/", ".jax_cache/"):
        assert required in rules, f".gitignore is missing {required!r}"


def test_checker_flags_missing_module(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "consumer.py").write_text(textwrap.dedent("""
        import repro
        from repro.ghost.sharding import shard
    """))
    missing = find_missing_imports(tmp_path)
    assert len(missing) == 1
    assert "repro.ghost.sharding" in missing[0]
    assert "consumer.py" in missing[0]


def test_checker_accepts_attribute_imports(tmp_path):
    pkg = tmp_path / "src" / "repro"
    pkg.mkdir(parents=True)
    (pkg / "__init__.py").write_text("")
    (pkg / "util.py").write_text("helper = 1\n")
    (pkg / "consumer.py").write_text("from repro.util import helper\n")
    assert find_missing_imports(tmp_path) == []
