"""The arrows of ``dlrover_tpu/models/`` point one way: a family's module
imports from ``models`` only ``layers`` and ``moe``; ``moe`` imports only
``layers``; ``layers`` imports no ``models`` module; none of them reaches
up into the runtime. Read from the source by ``ast``: nothing is imported
(``FAMILIES`` is a literal), so a broken module still gets its verdict."""

import ast
import os

import pytest

MODELS = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                      "dlrover_tpu", "models")
ABOVE = {"trainer", "launcher", "checkpoint", "agent", "master"}


def _families():
    with open(os.path.join(MODELS, "build.py")) as f:
        tree = ast.parse(f.read())
    for node in tree.body:
        if isinstance(node, ast.Assign) and node.targets[0].id == "FAMILIES":
            return sorted({entry[0] for entry in ast.literal_eval(node.value).values()})
    raise AssertionError("models/build.py has no FAMILIES")


def _imports(module):
    """(sibling ``models`` modules, other ``dlrover_tpu`` packages) that
    ``models/<module>.py`` imports anywhere in its text, a function's body
    included."""
    with open(os.path.join(MODELS, module + ".py")) as f:
        tree = ast.parse(f.read())
    siblings, packages = set(), set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names = [(alias.name.split("."), 0) for alias in node.names]
        elif isinstance(node, ast.ImportFrom):
            parts = node.module.split(".") if node.module else []
            if node.module is None:  # ``from . import x``, ``from .. import y``
                names = [([alias.name], node.level) for alias in node.names]
            else:
                names = [(parts, node.level)]
        else:
            continue
        for parts, level in names:
            if level == 0 and parts[0] == "dlrover_tpu":
                parts, level = parts[1:], 2
            if level == 1 and parts:
                siblings.add(parts[0])
            elif level == 2 and parts:
                if parts[0] == "models":
                    siblings.add(parts[1] if len(parts) > 1 else "")
                else:
                    packages.add(parts[0])
    return siblings, packages


ALLOWED = dict({family: {"layers", "moe"} for family in _families()},
               layers=set(), moe={"layers"})


def test_every_family_is_held():
    assert len(ALLOWED) == 11 and {"gpt", "llama", "mla_moe"} < set(ALLOWED)


@pytest.mark.parametrize("module", sorted(ALLOWED))
def test_the_arrows_point_one_way(module):
    siblings, packages = _imports(module)
    assert siblings <= ALLOWED[module], (
        f"models/{module}.py imports models/{sorted(siblings - ALLOWED[module])}: a family "
        f"takes its parts from layers.py and moe.py, never from another family")
    assert not packages & ABOVE, (
        f"models/{module}.py imports {sorted(packages & ABOVE)}: the models stand below the runtime")
