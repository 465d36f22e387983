"""The plain float32 reference of each architecture is written once, in
``benchmark/models/<name>.py`` beside the program it judges, and the tests
of the package import it from there. What a separate numpy-only module in
the package gave by construction is held here instead: nothing that
``reference_score`` can reach touches ``tpu_tfrecord``, importing the module
brings in neither jax nor the package, and the package never reaches for the
benchmark or the tests. No program is compiled here."""

import ast
import os
import subprocess
import sys

import pytest

from tools.graftlint.harness import iter_python_files

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MODELS = os.path.join(ROOT, "benchmark", "models")
REFERENCES = ["solar_open2", "kimi_vl_lm", "deepseek_v32", "trinity_large", "gigachat35", "nemotron_h",
              "olmo_hybrid", "sdar_moe"]
SIBLINGS = "benchmark.models."


def parsed(path):
    with open(path) as f:
        return ast.parse(f.read(), path)


def imports_of(node):
    """``(local name, module, name in it or None)`` of every import under ``node``."""
    for n in ast.walk(node):
        if isinstance(n, ast.Import):
            for a in n.names:
                yield (a.asname or a.name.split(".")[0]), a.name, None
        elif isinstance(n, ast.ImportFrom):
            for a in n.names:
                yield (a.asname or a.name), "." * n.level + (n.module or ""), a.name


def top_level(path):
    """One file's top level: the statement that defines each name, and the
    ``(module, name in it)`` each imported name came from."""
    defined, imported = {}, {}
    for stmt in parsed(path).body:
        if isinstance(stmt, (ast.FunctionDef, ast.ClassDef)):
            defined[stmt.name] = stmt
        elif isinstance(stmt, (ast.Import, ast.ImportFrom)):
            imported.update((local, (module, name)) for local, module, name in imports_of(stmt))
        else:
            defined.update((n.id, stmt) for n in ast.walk(stmt)
                           if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Store))
    return defined, imported


def reached_from(name, of, directory, forbidden="tpu_tfrecord"):
    """The call graph from ``name`` in ``<directory>/<of>.py``: every name a
    reached definition loads is followed, into a sibling module where it was
    imported from one. Returns the ``(module, name)`` reached and what among
    them touches ``forbidden``: an import inside a definition, or a name that
    the module's top level imported from it."""
    files, seen, faults, todo = {}, set(), [], [(of, name)]
    while todo:
        at = todo.pop()
        if at in seen:
            continue
        seen.add(at)
        mod, name = at
        if mod not in files:
            files[mod] = top_level(os.path.join(directory, mod + ".py"))
        defined, imported = files[mod]
        if name in imported:
            module, inner = imported[name]
            if module.split(".")[0] == forbidden:
                faults.append(f"{mod}.{name} is {module}'s")
            elif module.startswith(SIBLINGS) and inner is not None:
                todo.append((module[len(SIBLINGS):], inner))
        elif name in defined:                            # else a builtin, an argument, a local
            node = defined[name]
            faults += [f"{mod}.{name} imports {module}" for _, module, _ in imports_of(node)
                       if module.split(".")[0] == forbidden]
            todo += [(mod, n.id) for n in ast.walk(node)
                     if isinstance(n, ast.Name) and isinstance(n.ctx, ast.Load)]
    return seen, faults


@pytest.mark.parametrize("of", REFERENCES)
def test_the_reference_is_independent_of_the_code_it_judges(of):
    reached, faults = reached_from("reference_score", of, MODELS)
    assert not faults, faults
    # the walk went somewhere: the layers' own functions, the siblings' among them
    assert {"reference_score", "ref_norm", "_jitted"} <= {name for _, name in reached}
    assert len(reached) >= 15
    assert ({mod for mod, _ in reached} == {of}) == (of == "solar_open2")
    # and the oracle is as cheap to import as a numpy-only module: no jax, nothing of the package
    said = subprocess.run(
        [sys.executable, "-c",
         "import sys; sys.path.insert(0, sys.argv[1]); before = set(sys.modules)\n"
         f"import benchmark.models.{of}\n"
         "print(sorted({m.split('.')[0] for m in set(sys.modules) - before}"
         " & {'jax', 'jaxlib', 'tpu_tfrecord'}))", ROOT],
        capture_output=True, text=True, timeout=60)
    assert said.returncode == 0 and said.stdout.strip() == "[]", (said.stdout, said.stderr)


@pytest.mark.parametrize("body, fault", [
    # the program's own helper, imported where the reference runs
    ("def reference_score(x):\n    return _norm(x)\n\n"
     "def _norm(x):\n    from tpu_tfrecord.models import lm\n    return lm._norm(x)\n",
     "judge._norm imports tpu_tfrecord.models"),
    # a name the top level bound to the package, used two calls down
    ("from tpu_tfrecord.models import lm as program\n\n"
     "def reference_score(x):\n    return [inner(x)]\n\n"
     "def inner(x):\n    return (lambda y: program.rotary(y))(x)\n",
     "judge.program is tpu_tfrecord.models's"),
    # the same, behind a sibling module
    ("from benchmark.models.other import helper\n\n"
     "def reference_score(x):\n    return helper(x)\n",
     "other.helper imports tpu_tfrecord"),
    # the program beside the reference may use the package: nothing reaches it
    ("def reference_score(x):\n    return x\n\n"
     "def program(cfg):\n    from tpu_tfrecord.models import lm\n    return lm\n", None),
])
def test_the_walk_finds_what_it_is_there_to_find(tmp_path, body, fault):
    (tmp_path / "judge.py").write_text(body)
    (tmp_path / "other.py").write_text("def helper(x):\n    import tpu_tfrecord\n    return x\n")
    _, faults = reached_from("reference_score", "judge", str(tmp_path))
    assert faults == ([fault] if fault else [])


def test_the_package_imports_neither_the_benchmark_nor_the_tests():
    """What runs is not held up by what judges it: ``tpu_tfrecord`` reads no
    module of ``benchmark`` or ``tests``, at its top level or inside a function."""
    files = iter_python_files(["tpu_tfrecord"], ROOT)
    faults = [f"{rel} imports {module}" for path, rel in files
              for _, module, _ in imports_of(parsed(path))
              if module.split(".")[0] in ("benchmark", "tests")]
    assert not faults, faults
    assert len(files) >= 40
