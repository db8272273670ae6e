"""The family lookup's promises: nothing outside a family's own files
names it, and moving the Falcon rules behind the lookup changed no
weight."""

import ast
import hashlib
import os

import numpy as np
import pytest

from benchmark import harness, weights

FAMILIES = os.path.join(harness.HERE, "families")
# by-hand tools (not part of a run): a default argument may name a
# configuration, so their string constants are not read
BY_HAND = {"sweep.py", "sizing.py", "prove.py"}


def family_names():
    return sorted(f[:-3] for f in os.listdir(FAMILIES)
                  if f.endswith(".py") and f != "__init__.py")


def shared_modules():
    """Every module of `benchmark/` that is not a family's own file."""
    own = {os.path.join(sub, name + ".py") for name in family_names()
           for sub in ("families", "reference")}
    for sub in ("", "families", "reference"):
        for f in sorted(os.listdir(os.path.join(harness.HERE, sub))):
            if f.endswith(".py") and os.path.join(sub, f) not in own:
                yield os.path.join(sub, f)


def words_of(path: str, strings: bool):
    """Identifiers, imported names and (where `strings`) the string
    constants that are not docstrings."""
    with open(os.path.join(harness.HERE, path)) as f:
        tree = ast.parse(f.read())
    docstrings = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.Module, ast.FunctionDef, ast.ClassDef,
                             ast.AsyncFunctionDef)):
            body = node.body
            if body and isinstance(body[0], ast.Expr) and isinstance(
                    body[0].value, ast.Constant):
                docstrings.add(id(body[0].value))
    for node in ast.walk(tree):
        if isinstance(node, ast.Name):
            yield node.id
        elif isinstance(node, ast.Attribute):
            yield node.attr
        elif isinstance(node, ast.alias):
            yield node.name
            yield node.asname or ""
        elif isinstance(node, ast.ImportFrom):
            yield node.module or ""
        elif isinstance(node, (ast.FunctionDef, ast.ClassDef)):
            yield node.name
        elif isinstance(node, ast.arg):
            yield node.arg
        elif isinstance(node, ast.keyword):
            yield node.arg or ""
        elif strings and isinstance(node, ast.Constant) and isinstance(
                node.value, str) and id(node) not in docstrings:
            yield node.value


def test_there_is_a_family_and_the_test_can_fail():
    assert "falcon" in family_names()
    # the scan sees a name where one is: the family's own module has it
    assert any("falcon" in w.lower()
               for w in words_of("families/falcon.py", True))


@pytest.mark.parametrize("path", list(shared_modules()))
def test_no_shared_module_names_a_family(path):
    """Comments, docstrings and a by-hand tool's default aside, the code
    outside a family's module and reference imports and names no family:
    a second one is added without an edit here."""
    strings = os.path.basename(path) not in BY_HAND
    for word in words_of(path, strings):
        for family in family_names():
            assert family not in word.lower(), (path, word)


# sha256 over both blocks' and the globals' leaves (name, then float32
# bytes, names sorted), taken on the parent commit b095dbb, before the
# rules moved into families/falcon.py
PINNED = {
    ("tiny-falcon", 11):
        "72cbc086cbf33f4d492e49873b0310f59dea6bfde1e47a736a52ffcf03508369",
    ("tiny-falcon", 2147495993):
        "c352e1651fb6d537d2906a2b92179e10895c121bdca2cd206964bc98d33d15ff",
    ("tiny-falcon40", 11):
        "6a860f867bcd2ae4b5eec17ce61654df7ea7b9418dccc8bdda33c4df9a2c5f4f",
    ("tiny-falcon40", 2147495993):
        "df3ea08181a348ca32563d3de486fa9e6f5fb53ded3cc0d2a45923b8ff464b44",
}


@pytest.mark.parametrize("name,seed", sorted(PINNED))
def test_the_move_changed_no_weight(name, seed):
    cfg = harness.load_json(harness.HERE, "tests", "tiny", "configs",
                            name + ".json")
    h = hashlib.sha256()
    trees = [weights.make_layer(cfg, seed, i) for i in range(2)]
    trees.append(weights.make_globals(cfg, seed))
    for tree in trees:
        for k in sorted(tree):
            h.update(k.encode())
            h.update(np.asarray(tree[k]).tobytes())
    assert h.hexdigest() == PINNED[(name, seed)]


def test_two_copies_in_one_process_keep_their_own_family(tiny_base):
    """A family is found by (copy, model_type): the rehearsal's copy and
    this tree each get their own module and reference, and this tree's is
    the one an import gives."""
    from benchmark import families
    import benchmark.reference.falcon as imported

    cfg = {"model_type": "falcon"}
    copy = families.find(cfg, tiny_base)
    assert families.find(cfg) is copy  # no base: the copy named last
    own = families.find(cfg, harness.HERE)
    assert own is not copy and own.reference is not copy.reference
    assert own.reference is imported
    assert copy.__file__.startswith(tiny_base)
    assert copy.reference.__file__.startswith(tiny_base)
    assert families.find(cfg, tiny_base) is copy
