"""Repo hygiene guards: compiled artifacts must never be tracked in git.

A previous seed committed ``accelerate_tpu/telemetry/__pycache__`` with no
matching source — stale bytecode that shadows nothing and confuses everyone.
This guard fails the suite if any ``__pycache__``/``.pyc`` ever lands in the
index again.
"""

import os
import subprocess

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _git_ls_files():
    try:
        res = subprocess.run(
            ["git", "ls-files"], cwd=REPO, capture_output=True, text=True, timeout=60
        )
    except (OSError, subprocess.TimeoutExpired):
        pytest.skip("git unavailable")
    if res.returncode != 0:
        pytest.skip("not a git checkout")
    return res.stdout.splitlines()


def test_no_compiled_artifacts_tracked():
    tracked = _git_ls_files()
    bad = [
        path
        for path in tracked
        if "__pycache__" in path or path.endswith((".pyc", ".pyo", ".pyd"))
    ]
    assert bad == [], f"compiled artifacts tracked in git: {bad}"


def test_pycache_is_gitignored():
    gitignore = os.path.join(REPO, ".gitignore")
    assert os.path.exists(gitignore)
    patterns = [line.strip() for line in open(gitignore)]
    assert "__pycache__/" in patterns and "*.pyc" in patterns


# --------------------------------------------------------------- jaxlint --
# The static-analysis baseline (jaxlint-baseline.json) is a ratchet: entries
# exist only to grandfather findings that predate the linter, and the count
# may only ever go DOWN. Fixing debt removes entries; new findings must be
# fixed or inline-suppressed with a justification comment, never baselined.
# PR 6 shipped with zero entries — keep it that way (or lower, if a future
# PR ever has to add one and then pays it off).

MAX_JAXLINT_BASELINE_ENTRIES = 0


def test_jaxlint_baseline_only_shrinks():
    import json

    path = os.path.join(REPO, "jaxlint-baseline.json")
    assert os.path.exists(path), "jaxlint-baseline.json missing from repo root"
    with open(path) as f:
        data = json.load(f)
    assert data.get("version") == 1
    entries = data.get("findings")
    assert isinstance(entries, list)
    assert len(entries) <= MAX_JAXLINT_BASELINE_ENTRIES, (
        f"jaxlint baseline grew to {len(entries)} entr(ies) — the baseline "
        "only ratchets down. Fix the new finding or add an inline "
        "`# jaxlint: disable=Rn` with a justification comment, then (only "
        "if unavoidable) raise MAX_JAXLINT_BASELINE_ENTRIES in the same "
        "review that approves the debt."
    )
    for entry in entries:
        assert {"rule", "path", "symbol", "line_content"} <= set(entry)


# ---------------------------------------------------------------------------
# the main path's arrows point down: serving / generation -> models -> ops


def _imports(path):
    """``(module, name)`` of every import in the file, top level or inside a
    function, relative ones resolved against the file's own package."""
    import ast

    rel = os.path.relpath(path, REPO)[: -len(".py")].split(os.sep)
    package = rel[:-1]
    found = []
    for node in ast.walk(ast.parse(open(path).read(), filename=path)):
        if isinstance(node, ast.Import):
            found += [(alias.name, None) for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[: len(package) - (node.level - 1)] if node.level else []
            module = ".".join(base + (node.module.split(".") if node.module else []))
            found += [(module, alias.name) for alias in node.names]
    return found


@pytest.mark.parametrize("package", ["ops", "models"])
def test_kernels_and_models_import_no_serving_code(package):
    """Nothing under ``ops/`` or ``models/`` imports ``serving`` or
    ``generation``, not even lazily: the pool's device side lives in ``ops``,
    a model's paged forward in ``models``, and the engine calls down."""
    above = ("accelerate_tpu.serving", "accelerate_tpu.generation")
    root = os.path.join(REPO, "accelerate_tpu", package)
    bad = []
    for dirpath, _, files in os.walk(root):
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            for module, imported in _imports(path):
                full = module if imported is None else f"{module}.{imported}"
                if any(full == a or full.startswith(a + ".") for a in above):
                    bad.append(f"{os.path.relpath(path, REPO)}: {full}")
    assert bad == []


def test_the_engine_holds_no_model_code():
    """``serving/engine.py`` takes configuration from ``models.transformer``
    (the config type, the self-draft's config and parameter view) and no
    layer math: the model step is ``config.paged_forward``. The host side of
    the pool takes nothing from ``generation``."""
    engine = os.path.join(REPO, "accelerate_tpu", "serving", "engine.py")
    from_models = {name for module, name in _imports(engine)
                   if module.startswith("accelerate_tpu.models")}
    assert from_models <= {"LlamaConfig", "draft_config", "draft_params"}
    pager = os.path.join(REPO, "accelerate_tpu", "serving", "kv_pager.py")
    assert not [m for m, _ in _imports(pager) if m.startswith("accelerate_tpu.generation")]
