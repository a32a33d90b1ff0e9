"""The data plane's layering: each package of ``src/repro`` imports only
from the packages below it. Imports are read from the source with
``ast``, lazy ones inside functions included; no module is imported."""

import ast
from pathlib import Path

import pytest

SRC = Path(__file__).resolve().parents[1] / "src" / "repro"

ALLOWED = {
    "configs": set(),
    "models": {"configs"},
    "train": {"configs", "models"},
    "serve": {"configs", "models", "coord"},
    "launch": {"configs", "models", "train", "serve", "coord",
               "consistency"},
}


def _imported_packages(path: Path) -> set[str]:
    """The ``repro`` packages one module imports, by top-level name."""
    package = ["repro", *path.relative_to(SRC).parent.parts]
    found = set()
    for node in ast.walk(ast.parse(path.read_text(), str(path))):
        if isinstance(node, ast.Import):
            targets = [a.name.split(".") for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            if node.level:
                base = package[:len(package) - (node.level - 1)]
            else:
                base = []
            if node.module:
                targets = [base + node.module.split(".")]
            else:
                targets = [base + [a.name] for a in node.names]
        else:
            continue
        found |= {t[1] for t in targets if len(t) > 1 and t[0] == "repro"}
    return found


@pytest.mark.parametrize("package", sorted(ALLOWED))
def test_package_imports_only_the_layers_below(package):
    modules = sorted((SRC / package).rglob("*.py"))
    assert modules
    reached = {}
    for path in modules:
        for other in _imported_packages(path) - ALLOWED[package] - {package}:
            reached.setdefault(other, []).append(
                str(path.relative_to(SRC)))
    assert not reached, f"{package} imports {reached}"
