"""Regression: the ops package's function exports shadow its submodules
(ISSUE 19 satellite; this bit the memory autotuner). ``<op>_mod``
aliases are the canonical module handles. And the package's place in the
program (ISSUE 44): no module under ``ops/`` imports what stands above
it, and the token model's file defines no op of its own."""

import ast
import importlib
import inspect
import os

import pytest

OPS = ("resample2d", "channelnorm", "correlation", "spade_modulation")


def test_function_import_shadows_submodule():
    """The historical trap, pinned so nobody 'fixes' the docs away:
    the package attribute named after the op IS the function."""
    import imaginaire_tpu.ops as ops

    for op in OPS:
        assert inspect.isfunction(getattr(ops, op)), op


@pytest.mark.parametrize("op", OPS)
def test_mod_alias_is_the_submodule(op):
    import imaginaire_tpu.ops as ops

    alias = getattr(ops, f"{op}_mod")
    assert inspect.ismodule(alias), f"{op}_mod is not a module"
    assert alias is importlib.import_module(f"imaginaire_tpu.ops.{op}")
    # the attribute the autotuner needed when the shadowing bit it
    assert isinstance(alias.AUTO_IMPLEMENTATION, str)
    # and the function the alias carries is the exported one
    assert getattr(alias, op) is getattr(ops, op)


def test_op_modules_table_matches_aliases():
    import imaginaire_tpu.ops as ops

    assert set(ops.OP_MODULES) == set(OPS)
    for op, mod in ops.OP_MODULES.items():
        assert mod is getattr(ops, f"{op}_mod")


def test_resolved_implementations_uses_modules():
    from imaginaire_tpu.ops import OP_MODULES, resolved_implementations

    resolved = resolved_implementations()
    assert set(resolved) == set(OPS)
    for op, impl in resolved.items():
        assert impl == OP_MODULES[op].AUTO_IMPLEMENTATION


# ---- layering (ISSUE 44): ``ops/`` holds the numerics and knows no model

PACKAGE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "imaginaire_tpu")
# what a block of numerics may not know: who calls it, who measures it
ABOVE_OPS = ("models", "trainers", "optim", "telemetry", "serving",
             "resilience", "data")


def _op_files():
    found = []
    for folder in ("ops", os.path.join("ops", "pallas")):
        for name in sorted(os.listdir(os.path.join(PACKAGE, folder))):
            if name.endswith(".py"):
                found.append(os.path.join(folder, name))
    return found


def _imported(path):
    """Every module name a file imports, at any depth of its code:
    ``import a.b``, ``from a.b import c`` (as ``a.b`` and ``a.b.c``: ``c``
    may be a submodule)."""
    with open(path) as f:
        tree = ast.parse(f.read())
    names = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            names.update(alias.name for alias in node.names)
        elif isinstance(node, ast.ImportFrom):
            assert node.level == 0, f"{path}: a relative import"
            names.add(node.module)
            names.update(f"{node.module}.{alias.name}"
                         for alias in node.names)
    return names


@pytest.mark.parametrize("rel", _op_files())
def test_an_op_imports_nothing_above_it(rel):
    """A module under ``ops/`` takes arrays, shapes and sizes: it imports
    no model, trainer, optimizer, telemetry, serving, resilience or data
    code (``optim/remat.py`` reads the ops' checkpoint names, not the
    other way round), so a model's edit re-keys no op and an op can be
    timed alone."""
    refused = tuple(f"imaginaire_tpu.{name}" for name in ABOVE_OPS)
    above = sorted(name for name in _imported(os.path.join(PACKAGE, rel))
                   if name.startswith(refused))
    assert not above, f"{rel} imports {above}"


def test_the_layering_test_sees_every_op():
    files = _op_files()
    for rel in ("ops/state_space.py", "ops/held_experts.py",
                "ops/pallas/delta_rule_kernel.py",
                "ops/pallas/state_space_kernel.py"):
        assert rel in files


def test_the_movement_sweep_imports_no_model():
    """``scripts/sweep_expert_movement.py`` times five functions of
    ``ops/held_experts.py``; it needs no model for that."""
    names = _imported(os.path.join(os.path.dirname(PACKAGE), "scripts",
                                   "sweep_expert_movement.py"))
    assert "imaginaire_tpu.ops.held_experts" in names
    assert not [n for n in names if n.startswith("imaginaire_tpu.models")]


# what makes a block of numerics an op and not a part of the model
_OPS_OWN = {"custom_vjp", "fori_loop", "switch"}
_MOVED = {"ssd_scan", "route_held", "segment_rows", "gather_rows",
          "add_rows", "held_products", "held_experts_part",
          "weighted_rows_bwd", "held_experts_part_bwd", "expert_tiers",
          "moved_rows", "on_filled_prefix"}


def test_the_model_file_holds_no_op():
    """``hybrid_lm.py`` keeps the mixers, the router, the block, the loss,
    ``Settings`` and the generator: no written-out gradient, no loop over
    a step's own count and no switch of its own, and none of the names
    that moved to ``ops/state_space.py`` and ``ops/held_experts.py``."""
    path = os.path.join(PACKAGE, "models", "generators", "hybrid_lm.py")
    with open(path) as f:
        source = f.read()
    tree = ast.parse(source)
    used = {node.attr for node in ast.walk(tree)
            if isinstance(node, ast.Attribute)}
    used |= {node.id for node in ast.walk(tree) if isinstance(node, ast.Name)}
    assert not used & _OPS_OWN, sorted(used & _OPS_OWN)
    defined = {node.name for node in ast.walk(tree)
               if isinstance(node, (ast.FunctionDef, ast.ClassDef))}
    assert not defined & _MOVED, sorted(defined & _MOVED)
    assert len(source.splitlines()) < 950
