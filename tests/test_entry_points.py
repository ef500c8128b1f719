"""The scripts that stay outside the package still name things it defines.

``chip_smoke.py``, ``__graft_entry__.py`` and the ``tools/`` drivers below run
on a chip or by hand and sit outside ``tests/test_examples.py``'s reach, and
most of their imports are inside functions, so nothing in tier-1 executes
them. Each must compile, and every ``from deeplearning4j_tpu... import name``
in it, at any depth (and every such import from another module of this
repository: ``tools``, a test file), must name a submodule or something the
module binds at its top level. Resolved from the syntax trees: nothing is
imported or run, so no optional dependency is needed and no case skips. A
library name deleted under a kept script fails here.
"""
import ast
import functools
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOTS = [REPO, os.path.join(REPO, "tests")]   # where the scripts' sys.path looks
SCRIPTS = ["chip_smoke.py", "__graft_entry__.py", "tools/bench_configs.py",
           "tools/bench_tf_import.py", "tools/check_import_parity.py",
           "tools/gen_goldens.py"]


def _module_file(dotted):
    """(path of the module's source, whether it is a package), or None for a
    module that is not this repository's."""
    for root in ROOTS:
        base = os.path.join(root, *dotted.split("."))
        if os.path.isfile(os.path.join(base, "__init__.py")):
            return os.path.join(base, "__init__.py"), True
        if os.path.isfile(base + ".py"):
            return base + ".py", False
    return None


def _top_level(body):
    """Top-level statements, through ``if`` / ``try`` / ``with`` blocks."""
    for node in body:
        yield node
        for field in ("body", "orelse", "finalbody"):
            yield from _top_level(getattr(node, field, []))
        for handler in getattr(node, "handlers", []):
            yield from _top_level(handler.body)


@functools.lru_cache(maxsize=None)
def _bound_names(dotted):
    """name -> None, or the (module, name) it is imported from."""
    path, is_package = _module_file(dotted)
    with open(path) as f:
        tree = ast.parse(f.read(), path)
    parent = dotted if is_package else dotted.rpartition(".")[0]
    bound = {}
    for node in _top_level(tree.body):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            bound[node.name] = None
        elif isinstance(node, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            targets = node.targets if isinstance(node, ast.Assign) \
                else [node.target]
            for target in targets:
                for leaf in ast.walk(target):
                    if isinstance(leaf, ast.Name):
                        bound[leaf.id] = None
        elif isinstance(node, ast.Import):
            for alias in node.names:
                bound[alias.asname or alias.name.partition(".")[0]] = None
        elif isinstance(node, ast.ImportFrom):
            source = node.module or ""
            if node.level:
                parts = parent.split(".")
                up = parts[:len(parts) - node.level + 1]
                source = ".".join(up + ([source] if source else []))
            for alias in node.names:
                bound[alias.asname or alias.name] = (source, alias.name)
    return bound


def _defines(dotted, name, seen=()):
    path, is_package = _module_file(dotted)
    if is_package:
        sub = os.path.join(os.path.dirname(path), name)
        if os.path.isfile(sub + ".py") \
                or os.path.isfile(os.path.join(sub, "__init__.py")):
            return True
    bound = _bound_names(dotted)
    if name not in bound:
        return False
    origin = bound[name]
    if origin is None or _module_file(origin[0]) is None \
            or (dotted, name) in seen:
        return True
    return _defines(origin[0], origin[1], seen + ((dotted, name),))


@pytest.mark.parametrize("script", SCRIPTS)
def test_script_compiles_and_names_what_the_package_defines(script):
    path = os.path.join(REPO, script)
    with open(path) as f:
        source = f.read()
    compile(source, path, "exec")
    wanted = [(node.module, alias.name, node.lineno)
              for node in ast.walk(ast.parse(source, path))
              if isinstance(node, ast.ImportFrom) and node.level == 0
              and _module_file(node.module) is not None
              for alias in node.names]
    assert wanted, f"{script} imports nothing from this repository"
    missing = [f"{script}:{line}: from {module} import {name}"
               for module, name, line in wanted
               if not _defines(module, name)]
    assert not missing, "\n".join(missing)
