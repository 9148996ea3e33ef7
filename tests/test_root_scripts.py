"""The two root scripts no test runs (`bench.py`'s host phases and
`chip_smoke.py` need a cluster, minutes or a chip): every name they read
is bound somewhere in the file, so code deleted from them or from under
them leaves no caller behind."""

import ast
import builtins
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("script", ["bench.py", "chip_smoke.py"])
def test_every_name_a_root_script_reads_is_bound_in_it(script):
    with open(os.path.join(REPO, script)) as f:
        tree = ast.parse(f.read(), script)
    bound = set(dir(builtins)) | {"__file__", "__name__", "__doc__"}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            bound.add(node.name)
            if not isinstance(node, ast.ClassDef):
                args = node.args
                bound.update(a.arg for a in args.posonlyargs + args.args
                             + args.kwonlyargs + [args.vararg, args.kwarg]
                             if a is not None)
        elif isinstance(node, ast.Lambda):
            bound.update(a.arg for a in node.args.args)
        elif isinstance(node, (ast.Import, ast.ImportFrom)):
            bound.update((a.asname or a.name).split(".")[0]
                         for a in node.names)
        elif isinstance(node, ast.ExceptHandler) and node.name:
            bound.add(node.name)
        elif isinstance(node, ast.Name) and not isinstance(node.ctx, ast.Load):
            bound.add(node.id)
    unbound = sorted({(node.id, node.lineno) for node in ast.walk(tree)
                      if isinstance(node, ast.Name)
                      and isinstance(node.ctx, ast.Load)
                      and node.id not in bound})
    assert not unbound, unbound
