"""Tier 1 runs every test the benchmark has. The benchmark's tests live in
``benchmark/tests`` and reach tier 1 by import into the
``test_benchmark_harness*`` modules beside this one (see
``test_benchmark_harness.py``); an import by name can leave a test out, and a
star import of two modules that define one name keeps the second only. Twice
that went unseen (the state-space roofline's byte count from PR 37 on, the
result line's window and settle from PR 39 on)."""

import ast
import importlib
import inspect
import subprocess
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
BENCH_TESTS = HERE.parent / "benchmark" / "tests"
WRAPPERS = sorted(HERE.glob("test_benchmark_harness*.py"))


def collected(module) -> dict:
    """name -> object, of what pytest collects from a module's namespace or
    resolves there by name: its tests and its fixtures."""
    def is_fixture(obj):
        return (type(obj).__name__ == "FixtureFunctionDefinition"
                or hasattr(obj, "_pytestfixturefunction"))
    return {name: obj for name, obj in vars(module).items()
            if (name.startswith("test_") and inspect.isfunction(obj)) or is_fixture(obj)}


def test_every_test_of_the_benchmark_is_collected_by_a_wrapper():
    held, redefined = set(), set()
    for w in (importlib.import_module(path.stem) for path in WRAPPERS):
        for name, obj in collected(w).items():
            held.add(id(obj))
            if getattr(obj, "__module__", None) == w.__name__:
                # a wrapper that defines the name itself says in its docstring
                # what it holds in the original's place
                redefined.add(name)
    missing = []
    for path in sorted(BENCH_TESTS.glob("test_*.py")):
        module = importlib.import_module(f"benchmark.tests.{path.stem}")
        for name, obj in collected(module).items():
            if (name.startswith("test_") and obj.__module__ == module.__name__
                    and id(obj) not in held and name not in redefined):
                missing.append(f"{path.name}::{name}")
    assert not missing, f"no tests/test_benchmark_harness* module imports: {missing}"


def test_no_wrapper_star_imports_one_name_from_two_modules():
    clashes = []
    for path in WRAPPERS:
        starred = [node.module for node in ast.parse(path.read_text()).body
                   if isinstance(node, ast.ImportFrom)
                   and any(alias.name == "*" for alias in node.names)]
        first = {}
        for modname in starred:
            module = importlib.import_module(modname)
            for name, obj in collected(module).items():
                if getattr(obj, "__module__", modname) != modname:
                    continue
                if name in first:
                    clashes.append(f"{path.name}: {name} of {first[name]} is shadowed by {modname}'s")
                first.setdefault(name, modname)
    assert not clashes, clashes


def test_two_runs_of_the_benchmark_keep_their_scratch_apart():
    """``benchmark/run.py`` empties and refills ``spans.RUN_DIR`` and nothing
    else, and that directory is the process's own: two runs in one checkout
    share no file but the compile cache. So tier 1's rehearsals need no lock
    (they held one until PR 50, for the single ``.cache/run`` of before PR 39)."""
    code = "from benchmark.lib import spans; print(spans.RUN_DIR)"
    procs = [subprocess.Popen([sys.executable, "-c", code], cwd=HERE.parent,
                              stdout=subprocess.PIPE, text=True) for _ in range(2)]
    a, b = (Path(p.communicate(timeout=60)[0].strip()) for p in procs)
    assert all(p.returncode == 0 for p in procs)
    assert a != b and a not in b.parents and b not in a.parents
    cache = HERE.parent / "benchmark" / ".cache"
    for run_dir in (a, b):
        assert run_dir.parent == cache and run_dir.name != "run"
