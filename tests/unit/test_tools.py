"""The drills under ``tools/`` are run only by slow wrappers, so an import a
PR breaks would reach nobody: each drill loads, every module and name it
imports (at the top or inside a scenario) resolves, and every scenario it
lists is callable. None is run."""

import ast
import importlib
import os
import sys

import pytest

TOOLS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), "tools")
DRILLS = ("chaos", "comm", "decode_kernel", "elastic", "obs", "offload",
          "scaling", "serve", "trace")


@pytest.mark.parametrize("drill", DRILLS)
def test_a_drill_loads_and_every_scenario_it_lists_is_callable(drill,
                                                               monkeypatch):
    monkeypatch.syspath_prepend(TOOLS)
    mod = importlib.import_module(f"{drill}_drill")
    assert mod.SCENARIOS and callable(mod.run_scenario)
    for name, fn in mod.SCENARIOS.items():
        assert callable(fn), name
    with open(mod.__file__) as f:
        tree = ast.parse(f.read())
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            for a in node.names:
                importlib.import_module(a.name)
        elif isinstance(node, ast.ImportFrom) and node.level == 0:
            if node.module == "__future__":
                continue
            src = importlib.import_module(node.module)
            for a in node.names:
                if not hasattr(src, a.name):
                    importlib.import_module(f"{node.module}.{a.name}")
