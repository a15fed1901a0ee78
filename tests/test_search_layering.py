"""The cut of the query compiler held to its picture (`search/compiler.py`'s
docstring): five modules whose imports point one way,

    programs -> agg_compiler -> compiler -> plan -> planes

with `aggregations` (the host-side merge and finalize) beside `plan`, above
`planes` alone. Imports are read from the AST, function-local ones
included, so a lazy import cannot hide an arrow that points back; only an
`if TYPE_CHECKING:` block, which never runs, is left out. No JAX but in the
last case, which imports the modules to see the counter groups the
benchmark resolves on `search.compiler`."""

import ast
import glob
import os

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = "opensearch_tpu.search"
# a module imports only modules of a lower rank
RANK = {"planes": 0, "aggregations": 1, "plan": 1, "compiler": 2,
        "agg_compiler": 3, "programs": 4}
FIVE = ("planes", "plan", "compiler", "agg_compiler", "programs")
# the picture's own arrows: each is there, not only no arrow against them
NEXT = {"programs": "agg_compiler", "agg_compiler": "compiler",
        "compiler": "plan", "plan": "planes", "aggregations": "planes"}


def _path(module: str) -> str:
    return os.path.join(ROOT, *module.split(".")) + ".py"


def _type_checking_only(tree) -> set:
    out = set()
    for node in ast.walk(tree):
        if isinstance(node, ast.If) and "TYPE_CHECKING" in ast.dump(node.test):
            out.update(id(n) for stmt in node.body for n in ast.walk(stmt))
    return out


def _imports(module: str):
    """[(imported module, imported name or None, function-local?)] of
    `module`'s source, relative imports resolved."""
    with open(_path(module)) as fh:
        tree = ast.parse(fh.read())
    skipped = _type_checking_only(tree)
    local = {id(n) for f in ast.walk(tree)
             if isinstance(f, (ast.FunctionDef, ast.AsyncFunctionDef))
             for n in ast.walk(f)}
    package = module.split(".")[:-1]
    out = []
    for node in ast.walk(tree):
        if id(node) in skipped:
            continue
        if isinstance(node, ast.Import):
            out += [(a.name, None, id(node) in local) for a in node.names]
        elif isinstance(node, ast.ImportFrom):
            base = package[:len(package) - node.level + 1] if node.level \
                else []
            base = ".".join(base + ([node.module] if node.module else []))
            for a in node.names:
                # `from . import plan` names a module, `from .plan import
                # rewrite` a name of one
                sub = f"{base}.{a.name}"
                if os.path.exists(_path(sub)):
                    out.append((sub, None, id(node) in local))
                else:
                    out.append((base, a.name, id(node) in local))
    return out


def _siblings(module: str) -> dict:
    """{one of the six: is any import of it function-local} for
    `search/<module>.py`."""
    out = {}
    for target, _name, lazy in _imports(f"{PACKAGE}.{module}"):
        head, _, tail = target.rpartition(".")
        if head == PACKAGE and tail in RANK:
            out[tail] = out.get(tail, False) or lazy
    return out


@pytest.mark.parametrize("module", sorted(RANK))
def test_imports_point_one_way(module):
    siblings = _siblings(module)
    above = {m for m in siblings if RANK[m] >= RANK[module]}
    assert not above, f"search/{module}.py imports {sorted(above)}"
    assert module not in NEXT or NEXT[module] in siblings


def test_no_lazy_import_of_the_five():
    """A cut that needs a function-local import to load is the wrong cut."""
    lazy = {(module, m) for module in RANK
            for m, is_lazy in _siblings(module).items()
            if is_lazy and m in FIVE}
    assert not lazy, f"imported in a function: {sorted(lazy)}"


def test_planes_imports_nothing_of_search_but_the_error_type():
    got = {t for t, _n, _l in _imports(f"{PACKAGE}.planes")
           if t.startswith(PACKAGE)}
    assert got <= {f"{PACKAGE}.query_dsl"}, got


def test_plan_traces_nothing_itself():
    """`plan.py` names no `jax` module and, of `ops/`, `scoring` alone (two
    similarity ids and an idf, host arithmetic). `ops.scoring` does import
    `jax`, so a process that wants only `rewrite` still loads it: ROADMAP
    D17 (c)."""
    got = {t for t, _n, _l in _imports(f"{PACKAGE}.plan")}
    assert not {t for t in got if t.split(".")[0] == "jax"}, got
    assert {t for t in got if t.startswith("opensearch_tpu.ops")} \
        == {"opensearch_tpu.ops.scoring"}, got


def _package_modules():
    paths = glob.glob(os.path.join(ROOT, "opensearch_tpu", "**", "*.py"),
                      recursive=True)
    return sorted(os.path.relpath(p, ROOT)[:-3].replace(os.sep, ".")
                  for p in paths if not p.endswith("__init__.py"))


def _private(name: str) -> bool:
    return name.startswith("_") and not name.startswith("__")


def _private_names_reached(target: str) -> list:
    full = f"{PACKAGE}.{target}"
    found = []
    for module in _package_modules():
        if module == full:
            continue
        with open(_path(module)) as fh:
            src = fh.read()
        if target not in src:
            continue
        tree = ast.parse(src)
        aliases = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.Import):
                aliases |= {a.asname for a in node.names
                            if a.name == full and a.asname}
            elif isinstance(node, ast.ImportFrom):
                aliases |= {a.asname or a.name for a in node.names
                            if a.name == target and (node.module or "")
                            .split(".")[-1:] in ([], ["search"])}
        for imported, name, _lazy in _imports(module):
            if imported == full and name and _private(name):
                found.append(f"{module}: from {target} import {name}")
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) and _private(node.attr) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in aliases:
                found.append(f"{module}: {node.value.id}.{node.attr}")
    return found


def test_no_private_name_crosses():
    """No module of the package reaches an underscore name of one of the
    five, by `from ... import _name` or by `alias._name`."""
    for target in FIVE:
        assert os.path.exists(_path(f"{PACKAGE}.{target}")), target
    found = [f for target in FIVE for f in _private_names_reached(target)]
    assert not found, found


# where each counter group the benchmark reads is defined; the benchmark
# (`benchmark/deployments/*.py`) resolves all five on `search.compiler`
COUNTER_GROUPS = {"EXECUTOR_STATS": "compiler", "KNN_STATS": "compiler",
                  "AGG_STATS": "aggregations",
                  "BUCKET_PLANE_STATS": "planes",
                  "RANK_PLANE_STATS": "planes"}


def test_compiler_still_carries_the_benchmarks_counter_groups():
    import importlib
    compiler = importlib.import_module(f"{PACKAGE}.compiler")
    for group, module in COUNTER_GROUPS.items():
        home = importlib.import_module(f"{PACKAGE}.{module}")
        assert getattr(compiler, group) is getattr(home, group), group
        with open(_path(f"{PACKAGE}.{module}")) as fh:
            defined = [n for n in ast.parse(fh.read()).body
                       if isinstance(n, ast.Assign)
                       and any(isinstance(t, ast.Name) and t.id == group
                               for t in n.targets)]
        assert len(defined) == 1, f"{group} is not defined in {module}"
