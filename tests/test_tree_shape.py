"""The shape of the tree, checked by reading it: the program never
depends on what measures it, and the README's module table describes
the packages that exist."""

from __future__ import annotations

import ast
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGES = ("analysis", "api", "app", "cluster", "common", "deploy",
            "example", "kafka", "lambda_rt", "ml", "obs", "ops",
            "parallel", "resilience", "serving", "sim")
# what measures or tests the program; none of it ships with it
_OUTSIDE = ("benchmark", "bench", "chip_smoke", "tests")


def test_the_packages_are_the_ones_listed():
    found = sorted(
        d for d in os.listdir(os.path.join(ROOT, "oryx_tpu"))
        if os.path.isfile(os.path.join(ROOT, "oryx_tpu", d, "__init__.py")))
    assert found == sorted(PACKAGES)


def _imports(path: str, package_parts: list[str]):
    """Absolute dotted names of every module ``path`` imports, relative
    imports resolved against its package."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                yield a.name
        elif isinstance(node, ast.ImportFrom):
            base = package_parts[:len(package_parts) - node.level + 1] \
                if node.level else []
            stem = ".".join(base + ([node.module] if node.module else []))
            yield stem
            for a in node.names:   # ``from .. import bench``
                yield f"{stem}.{a.name}" if stem else a.name


@pytest.mark.parametrize("package", PACKAGES)
def test_the_program_imports_nothing_that_measures_it(package):
    offenders = []
    top = os.path.join(ROOT, "oryx_tpu", package)
    for dirpath, _dirs, files in os.walk(top):
        parts = os.path.relpath(dirpath, ROOT).split(os.sep)
        for name in files:
            if not name.endswith(".py"):
                continue
            path = os.path.join(dirpath, name)
            for mod in _imports(path, parts):
                head = mod.split(".")
                if head[0] in _OUTSIDE or head[:2] == ["oryx_tpu", "bench"]:
                    offenders.append(
                        f"{os.path.relpath(path, ROOT)} imports {mod}")
    assert not offenders, offenders


def _module_table_rows() -> list[str]:
    """The paths in the first column of README.md's "Layout" table."""
    with open(os.path.join(ROOT, "README.md"), encoding="utf-8") as f:
        text = f.read()
    table = text.split("## Layout", 1)[1].split("\n## ", 1)[0]
    return re.findall(r"^\| `([^`]+)` \|", table, flags=re.M)


@pytest.mark.parametrize("package", PACKAGES)
def test_the_readme_module_table_describes_the_tree(package):
    rows = _module_table_rows()
    assert f"oryx_tpu/{package}/" in rows
    missing = [r for r in rows if r.endswith("/")
               and not os.path.isdir(os.path.join(ROOT, r))]
    assert not missing, missing
