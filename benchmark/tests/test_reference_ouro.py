"""The benchmark's plain reference (`benchmark/reference_ouro.py`) and
the program's copy of it are one text below their docstrings, and the
benchmark's imports nothing of the program."""

import ast

from test_reference_glm import body


def test_the_two_copies_are_one_text():
    mine, _ = body("benchmark", "reference_ouro.py")
    theirs, _ = body("kungfu_tpu", "models", "ouro_reference.py")
    assert mine == theirs


def test_the_reference_imports_nothing_of_the_program():
    _, tree = body("benchmark", "reference_ouro.py")
    imported = {n.module if isinstance(n, ast.ImportFrom)
                else a.name for n in ast.walk(tree)
                if isinstance(n, (ast.Import, ast.ImportFrom))
                for a in n.names}
    assert imported <= {"__future__", "jax", "jax.numpy"}, imported
