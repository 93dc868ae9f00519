"""Imports point down (ROADMAP D15): a module of ``deepspeed_tpu/`` imports
only packages of its own band or a lower one, so the system can be drawn
(``README.md``, "Layers"). Every import statement counts, wherever it stands:
at module level, inside a function, under ``TYPE_CHECKING``."""

import ast
import os

import pytest

PACKAGE = "deepspeed_tpu"
ROOT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    PACKAGE)

# lowest first; a new module goes in the lowest band its imports allow
BANDS = (
    ("utils", "accelerator", "config", "telemetry", "monitor", "profiling"),
    ("comm", "ops"),
    ("parallel", "compression"),
    ("models",),
    ("checkpoint", "runtime", "inference", "linear", "module_inject",
     "elasticity", "launcher"),
    ("serving",),
)
BAND_OF = {name: i for i, band in enumerate(BANDS) for name in band}


PACKAGES = sorted(
    name for name in os.listdir(ROOT)
    if os.path.isfile(os.path.join(ROOT, name, "__init__.py")))


def _modules(package: str):
    for here, _, files in os.walk(os.path.join(ROOT, package)):
        for name in sorted(files):
            if name.endswith(".py"):
                yield os.path.join(here, name)


def _imported_packages(path: str):
    """``(line, package)`` for every import of ``deepspeed_tpu.<package>`` in
    the module at ``path``, relative imports resolved."""
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read(), path)
    # the module's own package path: a relative import climbs from it
    here = os.path.relpath(path, os.path.dirname(ROOT)).split(os.sep)[:-1]
    found = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [alias.name for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = node.module.split(".") if node.module else []
            if node.level:
                base = here[:len(here) - (node.level - 1)] + base
            # ``from deepspeed_tpu import ops`` names the package in the tail
            names = [".".join(base + [alias.name]) for alias in node.names]
        else:
            continue
        for name in names:
            dotted = name.split(".")
            if dotted[0] == PACKAGE and len(dotted) > 1 and dotted[1] in PACKAGES:
                found.add((node.lineno, dotted[1]))
    return sorted(found)


@pytest.mark.parametrize("package", PACKAGES)
def test_imports_point_down(package):
    assert package in BAND_OF, "a new package: give it a band"
    # a package with no band is above every band
    upward = [
        f"{os.path.relpath(path, os.path.dirname(ROOT))}:{line} imports {target}"
        for path in _modules(package)
        for line, target in _imported_packages(path)
        if BAND_OF.get(target, len(BANDS)) > BAND_OF[package]]
    assert upward == []
