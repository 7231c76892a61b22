"""The surface the benchmark stands on.

``benchmark/`` and ``chip_smoke.py`` drive the program through its own
names: what they import from ``imaginaire_tpu``, the attributes they use
on those modules, and the attributes they use on a trainer. A PR that
may not edit the benchmark (every kind but ``benchmark``) must keep each
of them working, and the end-to-end rehearsals that would notice are in
the slow tier. This file reads those sources with ``ast`` (it never
edits them) and yields one fast case per name.
"""

import ast
import functools
import glob
import importlib
import os

import pytest

ROOT = os.path.join(os.path.dirname(__file__), "..")
SOURCES = sorted(
    os.path.relpath(p, ROOT)
    for pattern in ("benchmark/drivers/*.py", "benchmark/lib/*.py",
                    "chip_smoke.py")
    for p in glob.glob(os.path.join(ROOT, pattern)))


def _tree(rel):
    with open(os.path.join(ROOT, rel)) as f:
        return ast.parse(f.read(), filename=rel)


def _uses():
    """(imports, module_attrs, trainer_attrs): each a sorted list of
    distinct names, whichever source uses them."""
    imports, module_attrs, trainer_attrs = set(), set(), set()
    for rel in SOURCES:
        tree = _tree(rel)
        bound = {}  # local name -> dotted path it was imported as
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) and node.module \
                    and node.module.split(".")[0] == "imaginaire_tpu":
                for alias in node.names:
                    imports.add((node.module, alias.name))
                    bound[alias.asname or alias.name] = (
                        f"{node.module}.{alias.name}")
            elif isinstance(node, ast.Import):
                for alias in node.names:
                    if alias.name.split(".")[0] == "imaginaire_tpu":
                        imports.add((alias.name, None))
        for node in ast.walk(tree):
            if not isinstance(node, ast.Attribute):
                continue
            on = node.value
            if isinstance(on, ast.Name) and on.id in bound:
                module_attrs.add((bound[on.id], node.attr))
            elif ast.unparse(on) in ("trainer", "self.trainer"):
                trainer_attrs.add(node.attr)
    return sorted(imports, key=str), sorted(module_attrs), \
        sorted(trainer_attrs)


IMPORTS, MODULE_ATTRS, TRAINER_ATTRS = _uses()


def _resolve(dotted):
    """The object a dotted path names: a module, or a name inside one."""
    try:
        return importlib.import_module(dotted)
    except ModuleNotFoundError:
        module, _, name = dotted.rpartition(".")
        return getattr(importlib.import_module(module), name)


def test_the_sources_are_read():
    assert "chip_smoke.py" in SOURCES
    assert "benchmark/drivers/train_fed.py" in SOURCES
    assert len(IMPORTS) >= 10 and len(TRAINER_ATTRS) >= 10


@pytest.mark.parametrize(
    "module,name", IMPORTS,
    ids=[m if n is None else f"{m}.{n}" for m, n in IMPORTS])
def test_import_resolves(module, name):
    _resolve(module if name is None else f"{module}.{name}")


@pytest.mark.parametrize("dotted,attr", MODULE_ATTRS,
                         ids=[f"{d}.{a}" for d, a in MODULE_ATTRS])
def test_attribute_of_an_import_exists(dotted, attr):
    assert hasattr(_resolve(dotted), attr), f"{dotted} has no {attr}"


@functools.lru_cache(maxsize=None)
def _base_trainer_names():
    """What every trainer has: ``BaseTrainer``'s class attributes and the
    instance attributes its own methods assign."""
    from imaginaire_tpu.trainers import base

    names = set(dir(base.BaseTrainer))
    cls = next(n for n in _tree("imaginaire_tpu/trainers/base.py").body
               if isinstance(n, ast.ClassDef) and n.name == "BaseTrainer")
    for node in ast.walk(cls):
        if isinstance(node, ast.Attribute) \
                and isinstance(node.ctx, ast.Store) \
                and isinstance(node.value, ast.Name) \
                and node.value.id == "self":
            names.add(node.attr)
    return names


@pytest.mark.parametrize("attr", TRAINER_ATTRS)
def test_trainer_attribute_exists_on_base_trainer(attr):
    assert attr in _base_trainer_names(), (
        f"benchmark/ or chip_smoke.py uses trainer.{attr}; "
        f"BaseTrainer has none")
