"""Which kernel runs and how the loss is scanned are decided from
shapes, backend and mesh, in one place each — by nothing a user can
export. The train path's modules therefore read no ``RAY_TPU_*``
environment variable: a tuning variable read at trace time is a second
program that no cell of the benchmark measures (ROADMAP C3)."""

import ast
import pathlib

import pytest

ROOT = pathlib.Path(__file__).resolve().parent.parent / "ray_tpu"


def _ray_tpu_variables_named(path: pathlib.Path) -> list[str]:
    """``file:line name`` of every string ``RAY_TPU_*`` in the module's
    code, docstrings aside: the key of an ``os.environ`` read, of
    ``os.getenv``, or a constant that holds one for a read elsewhere."""
    tree = ast.parse(path.read_text())
    docstrings = {
        id(node.body[0].value) for node in ast.walk(tree)
        if isinstance(node, (ast.Module, ast.ClassDef, ast.FunctionDef,
                             ast.AsyncFunctionDef))
        and node.body and isinstance(node.body[0], ast.Expr)
        and isinstance(node.body[0].value, ast.Constant)}
    return [f"{path.relative_to(ROOT.parent)}:{node.lineno} {node.value}"
            for node in ast.walk(tree)
            if isinstance(node, ast.Constant)
            and isinstance(node.value, str)
            and node.value.startswith("RAY_TPU_")
            and id(node) not in docstrings]


@pytest.mark.parametrize("where", [
    "ops", "models", "parallel", "train/step.py", "train/prefetch.py"])
def test_the_train_path_reads_no_ray_tpu_environment_variable(where):
    target = ROOT / where
    modules = sorted(target.rglob("*.py")) if target.is_dir() else [target]
    assert modules
    found = [hit for m in modules for hit in _ray_tpu_variables_named(m)]
    assert not found, found
