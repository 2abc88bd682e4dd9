"""The benchmark's plain reference (`benchmark/reference_granite.py`) and
the program's copy of it are one text below their docstrings, and the
benchmark's imports nothing of the program."""

import ast
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def body(*parts):
    with open(os.path.join(ROOT, *parts)) as f:
        source = f.read()
    tree = ast.parse(source)
    assert isinstance(tree.body[0].value, ast.Constant)  # the docstring
    return "\n".join(source.splitlines()[tree.body[0].end_lineno:]), tree


def test_the_two_copies_are_one_text():
    mine, _ = body("benchmark", "reference_granite.py")
    theirs, _ = body("kungfu_tpu", "models", "granite_hybrid_reference.py")
    assert mine == theirs


def test_the_reference_imports_nothing_of_the_program():
    _, tree = body("benchmark", "reference_granite.py")
    imported = {n.module if isinstance(n, ast.ImportFrom)
                else a.name for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    assert imported <= {"__future__", "jax", "jax.numpy"}, imported
