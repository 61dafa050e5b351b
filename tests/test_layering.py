"""``repro.launch`` holds the entry points and sits on top: no other
package of ``repro`` imports it."""

import ast
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"
PACKAGES = sorted(p.name for p in SRC.iterdir()
                  if p.is_dir() and p.name not in ("launch", "__pycache__"))


def _imports(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield node.module
            yield from (f"{node.module}.{a.name}" for a in node.names)


@pytest.mark.parametrize("package", PACKAGES)
def test_package_does_not_import_launch(package):
    bad = [f"{path.relative_to(SRC)}: {name}"
           for path in sorted((SRC / package).rglob("*.py"))
           for name in _imports(ast.parse(path.read_text()))
           if name == "repro.launch" or name.startswith("repro.launch.")]
    assert not bad, bad
