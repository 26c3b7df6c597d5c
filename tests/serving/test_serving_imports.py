"""The serving package's imports point one way:

    web <- request <- {python_front, native_front} <- layer;  framework <- layer

`request` (what both fronts run between a parsed request and the bytes
of its answer) knows the layer only as the object it is handed, and a
front knows neither the other front nor the layer. Checked on every
`import` statement of a file, those inside functions included: a late
import is how the arrows came to point both ways.
"""

from __future__ import annotations

import ast
from pathlib import Path

import pytest

SERVING = Path(__file__).resolve().parent.parent.parent / "oryx_tpu" / "serving"
PACKAGE = "oryx_tpu.serving"

# module -> the modules of the package it may not import
FORBIDDEN = {
    "web": {"layer", "request", "python_front", "native_front", "framework"},
    "request": {"layer", "python_front", "native_front", "framework"},
    "python_front": {"layer", "native_front", "framework"},
    "native_front": {"layer", "python_front", "framework"},
    "framework": {"layer", "request", "python_front", "native_front"},
    "layer": set(),
}


def imported_serving_modules(path: Path) -> set[str]:
    """Every module of `oryx_tpu.serving` that some `import` statement of
    the file names, at whatever depth of the tree."""
    found = set()
    for node in ast.walk(ast.parse(path.read_text())):
        if isinstance(node, ast.Import):
            names = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path.name}:{node.lineno}: a relative import"
            # `from oryx_tpu.serving import x` names the module x
            names = [node.module] + [f"{node.module}.{alias.name}" for alias in node.names]
        else:
            continue
        for name in names:
            if name.startswith(PACKAGE + "."):
                found.add(name[len(PACKAGE) + 1:].split(".")[0])
    return found


@pytest.mark.parametrize("module", sorted(FORBIDDEN))
def test_a_serving_module_imports_only_what_lies_below_it(module):
    seen = imported_serving_modules(SERVING / f"{module}.py")
    assert not seen & FORBIDDEN[module], (
        f"serving/{module}.py imports {sorted(seen & FORBIDDEN[module])}"
    )


def test_only_the_package_itself_imports_the_layer():
    importers = {
        path.stem
        for path in SERVING.glob("*.py")
        if "layer" in imported_serving_modules(path)
    }
    assert importers == {"__init__"}
