"""The layers import downward only.

``repro.core`` and ``repro.kernels`` are libraries of transform bodies:
the planner, the engine registry, the front door and the resilience
ladder all sit above them, and the registry is the one place an engine
name becomes code. Every import in those two packages (a lazy import
inside a function included) is checked here, one case per module, so a
failure names the module that reaches up.
"""

import ast
import pathlib

import pytest

SRC = pathlib.Path(__file__).resolve().parents[1] / "src"

#: Packages neither core nor kernels may import.
ABOVE = ("repro.plan", "repro.engines", "repro.xfft", "repro.resilience")

#: Extra packages each lower layer may not import (kernels may use core
#: for the unfused failover; core may not use kernels).
FORBIDDEN = {
    "core": ABOVE + ("repro.kernels",),
    "kernels": ABOVE,
}


def _modules():
    return sorted(
        path for pkg in FORBIDDEN for path in (SRC / "repro" / pkg).rglob("*.py")
    )


def _imported(tree):
    """Every module name an ``import`` or ``from ... import`` names, with
    ``from pkg import sub`` counted as ``pkg.sub``."""
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module and not node.level:
            yield node.module
            yield from (f"{node.module}.{alias.name}" for alias in node.names)


def _reaches(name, package):
    return name == package or name.startswith(package + ".")


@pytest.mark.parametrize(
    "path", _modules(), ids=lambda p: str(p.relative_to(SRC / "repro"))
)
def test_module_imports_only_downward(path):
    layer = path.relative_to(SRC / "repro").parts[0]
    tree = ast.parse(path.read_text(), filename=str(path))
    bad = sorted({
        name for name in _imported(tree)
        if any(_reaches(name, pkg) for pkg in FORBIDDEN[layer])
    })
    assert not bad, f"repro.{layer} module {path.name} imports {bad}"
