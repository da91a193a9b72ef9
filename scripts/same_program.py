"""Is a change to the train path the same program? Cell by cell, with no
chip.

For every language cell of ``BENCHMARK.json`` (ResNet's step holds no
kernel and no file of ``ray_tpu/ops``: left out by name) the cell's step
is built as the benchmark builds it (``benchmark/builders/<b>.build`` at
real size on the cell's mesh, over the described chips of a ``v5e:2x2``,
as ``tests/conftest.py::lower_real_size_step`` lowers one: abstract state
and batch, ``jax.default_backend`` steered to ``tpu``), traced and
lowered, not compiled. Two hashes a cell:

- ``step``: the lowered text with every Mosaic body taken out. The text
  is printed without locations, so a line that moved in a source file
  moves nothing here; the numbers jax gives private functions
  (``@_take_69``) are renumbered in order of appearance.
- ``kernels``: each ``tpu_custom_call``'s body, decoded and printed with
  ``get_asm(enable_debug_info=False)``: a Mosaic body is serialized with
  the file and line of every Python frame above it, which is what makes
  two trees' raw texts differ when nothing else does. One hash a custom
  call in the order of the text, and their count.

    python scripts/same_program.py                       # this tree's table
    python scripts/same_program.py --against <checkout>  # two trees

``--against`` lowers both trees (a process a tree: a module loads once)
and names the first cell and line that differ; exit code 1 where any
does. ``--cells a,b`` picks cells; ``--keep <dir>`` leaves both trees'
stripped texts there to ``diff``. ~20 s a cell a tree.
"""

from __future__ import annotations

import argparse
import base64
import hashlib
import json
import os
import re
import subprocess
import sys
import tempfile

LEFT_OUT = ("resnet50.b128-dev-input",)     # no kernel, no ops/ file
_BODY = re.compile(r'\\22body\\22: \\22([A-Za-z0-9+/=]+)\\22')
_PRIVATE = re.compile(r"@(_?[A-Za-z][\w.]*?)_(\d+)\b")


def _sha(text: str) -> str:
    return hashlib.sha256(text.encode()).hexdigest()[:16]


def _kernel_asm(body: str) -> str:
    """A custom call's Mosaic body without its source locations."""
    from jax._src.interpreters import mlir
    from jax._src.lib import tpu
    from jax._src.lib.mlir import ir
    ctx = mlir.make_ir_context()
    tpu.register_dialect(ctx)
    ctx.allow_unregistered_dialects = True      # ``stable_mosaic``
    with ctx:
        module = ir.Module.parse(base64.b64decode(body))
        return module.operation.get_asm(enable_debug_info=False)


def _renumbered(text: str) -> str:
    """Private functions' numbers in order of appearance: a
    ``checkpoint_name`` or a jitted helper more in shared code moves
    them all and changes nothing."""
    seen: dict[str, str] = {}

    def one(m):
        key = m.group(0)
        if key not in seen:
            seen[key] = f"@{m.group(1)}_n{len(seen)}"
        return seen[key]
    return _PRIVATE.sub(one, text)


def strip(text: str) -> tuple[str, list[str]]:
    """(the lowered text with each body replaced by its place in the
    list, the bodies' location-free texts)."""
    kernels: list[str] = []

    def take(m):
        kernels.append(_kernel_asm(m.group(1)))
        return f'\\22body\\22: \\22<kernel {len(kernels) - 1}>\\22'
    return _renumbered(_BODY.sub(take, text)), kernels


def lower_cell(tree: str, name: str) -> str:
    """The cell's real-size step from the checkout ``tree``, lowered for
    the described chips: its text."""
    from unittest import mock

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import NamedSharding, PartitionSpec as P

    from benchlib import manifest
    from ray_tpu.parallel import make_mesh
    from ray_tpu.parallel.sharding import shard_params
    from ray_tpu.train.step import batch_spec

    cell = manifest.find_cell(manifest.load_manifest(tree), name, tree,
                              os.path.join(tree, "benchmark"))
    cfg, traffic = cell["config_file"], cell["traffic_file"]
    chips = cell["chips"]
    devices = list(topologies.get_topology_desc(
        platform="tpu", topology_name="v5e:2x2").devices)[:chips]
    mesh = make_mesh(dict(traffic["mesh"]), devices=devices)
    with mock.patch.object(jax, "default_backend", lambda: "tpu"), \
            mock.patch.object(jax, "device_count", lambda: chips):
        built = manifest.load_builder(
            cfg["builder"], os.path.join(tree, "benchmark")).build(
                cfg, traffic, mesh, 0, False)
        state = jax.eval_shape(built["init_state"])
        # as ``init_train_state`` places it: the parameters by the rule
        # table, the moments as their parameters, the counters whole
        whole = NamedSharding(mesh, P())
        like = jax.tree.structure(state.params)
        at = shard_params(state.params, mesh)
        is_params = lambda t: jax.tree.structure(t) == like  # noqa: E731
        shardings = state.replace(
            step=whole, params=at,
            opt_state=jax.tree.map(
                lambda t: at if is_params(t) else whole, state.opt_state,
                is_leaf=is_params),
            extra=jax.tree.map(lambda _: whole, state.extra))
        state = jax.tree.map(
            lambda z, s: jax.ShapeDtypeStruct(z.shape, z.dtype, sharding=s),
            state, shardings)
        rows = NamedSharding(mesh, batch_spec(mesh))
        shape = (traffic["batch_per_chip"] * chips,
                 built["shapes"]["seq_len"])
        batch = {k: jax.ShapeDtypeStruct(shape, jnp.int32, sharding=rows)
                 for k in ("tokens", "targets")}
        return built["step"].lower(state, batch).as_text()


def table(tree: str, cells: list[str], keep: str | None) -> dict:
    """{cell: {"step", "kernels", "n_kernels"}} of one tree; with
    ``keep`` the stripped texts go to ``<keep>/<cell>.{step,kernels}``."""
    out = {}
    for name in cells:
        text, kernels = strip(lower_cell(tree, name))
        out[name] = {"step": _sha(text), "n_kernels": len(kernels),
                     "kernels": [_sha(k) for k in kernels]}
        if keep:
            os.makedirs(keep, exist_ok=True)
            with open(os.path.join(keep, name + ".step"), "w") as f:
                f.write(text)
            with open(os.path.join(keep, name + ".kernels"), "w") as f:
                f.write("\n".join(f"// kernel {i}\n{k}"
                                  for i, k in enumerate(kernels)))
        print(f"{name:42s} step {out[name]['step']}  {len(kernels):3d} "
              f"kernels {_sha(''.join(out[name]['kernels']))}",
              file=sys.stderr, flush=True)
    return out


def _first_difference(ours: str, theirs: str, cell: str, what: str):
    with open(os.path.join(ours, f"{cell}.{what}")) as f:
        a = f.read().splitlines()
    with open(os.path.join(theirs, f"{cell}.{what}")) as f:
        b = f.read().splitlines()
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i + 1, x[:200], y[:200]
    return min(len(a), len(b)) + 1, "<end>", "<end>"


def _one_tree(tree: str, cells: list[str], keep: str) -> dict:
    """``table`` of ``tree`` in a process of its own, its modules that
    tree's."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "ALLOW_MULTIPLE_LIBTPU_LOAD": "1", "TPU_LOG_DIR": "disabled"}
    done = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--tree", tree,
         "--cells", ",".join(cells), "--keep", keep, "--json"],
        env=env, stdout=subprocess.PIPE, check=True)
    return json.loads(done.stdout)


def main() -> int:
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--against", help="another checkout to compare with")
    ap.add_argument("--cells", help="comma-separated; default: every "
                                    "language cell of BENCHMARK.json")
    ap.add_argument("--keep", help="a directory for the stripped texts")
    ap.add_argument("--tree", default=here, help=argparse.SUPPRESS)
    ap.add_argument("--json", action="store_true", help=argparse.SUPPRESS)
    args = ap.parse_args()
    tree = os.path.abspath(args.tree)
    with open(os.path.join(tree, "BENCHMARK.json")) as f:
        every = [w["name"] for w in json.load(f)["workloads"]
                 if w["name"] not in LEFT_OUT]
    cells = args.cells.split(",") if args.cells else every

    if args.against is None and not args.json:
        # one process a tree also for one tree: the platform is pinned
        # before jax is imported
        with tempfile.TemporaryDirectory() as tmp:
            _one_tree(tree, cells, args.keep or tmp)
        return 0
    if args.json:
        sys.path[:0] = [tree, os.path.join(tree, "benchmark")]
        print(json.dumps(table(tree, cells, args.keep)))
        return 0

    other = os.path.abspath(args.against)
    with tempfile.TemporaryDirectory() as tmp:
        keep = args.keep or tmp
        ours_dir, theirs_dir = (os.path.join(keep, d)
                                for d in ("this", "against"))
        theirs = _one_tree(other, cells, theirs_dir)
        ours = _one_tree(tree, cells, ours_dir)
        differ = 0
        print(f"| cell | step | kernels | {other} |\n|---|---|---|---|")
        for name in cells:
            a, b = ours[name], theirs[name]
            same = (a["step"] == b["step"], a["kernels"] == b["kernels"])
            differ += not all(same)
            print(f"| {name} | {a['step']} | {a['n_kernels']} "
                  f"{_sha(''.join(a['kernels']))} | "
                  f"{'same' if all(same) else 'DIFFERS'} |")
            for ok, what in zip(same, ("step", "kernels")):
                if not ok:
                    line, x, y = _first_difference(ours_dir, theirs_dir,
                                                   name, what)
                    print(f"{name}: {what} line {line}\n  this:    {x}\n"
                          f"  against: {y}")
    return 1 if differ else 0


if __name__ == "__main__":
    sys.exit(main())
