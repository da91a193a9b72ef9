"""``BENCHMARK.json`` and the files it names.

Everything that belongs to one configuration, one traffic mix or one
per-layer metric is a file of its own, found by the name in the
manifest: ``configs/<config>.json`` (the manifest gives the path),
``traffic/<traffic>.json``, ``layer_metrics/<metric>.py``,
``builders/<builder>.py``, ``references/<reference>.py``. Adding a cell, a configuration or a metric
adds files and manifest entries and edits no file that is there.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

BENCH_DIR = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ROOT = os.path.dirname(BENCH_DIR)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
MANIFEST_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
                 "end_to_end", "per_layer"}


def load_manifest(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


def traffic_path(traffic: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "traffic", f"{traffic}.json")


def reader_path(metric: str, bench_dir: str = BENCH_DIR) -> str:
    return os.path.join(bench_dir, "layer_metrics", f"{metric}.py")


def _load_module(path: str):
    """A file found by name, as a module (its name may hold dots)."""
    spec = importlib.util.spec_from_file_location(
        "bench_" + re.sub(r"\W", "_", os.path.basename(path)), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(metric: str, bench_dir: str = BENCH_DIR):
    """The ``read(run)`` function of ``layer_metrics/<metric>.py``."""
    return _load_module(reader_path(metric, bench_dir)).read


def load_builder(name: str, bench_dir: str = BENCH_DIR):
    """``builders/<name>.py``: ``host_dataset`` and ``build``."""
    return _load_module(os.path.join(bench_dir, "builders", f"{name}.py"))


def load_reference(name: str, bench_dir: str = BENCH_DIR):
    """``references/<name>.py``: a configuration's plain float32
    reference, ``loss_and_grad_norm``."""
    return _load_module(os.path.join(bench_dir, "references", f"{name}.py"))


def effective_traffic(traffic: dict, tiny: bool) -> dict:
    """The traffic file as run: in a rehearsal its ``tiny`` group
    overrides the sizes."""
    return {**traffic, **(traffic.get("tiny", {}) if tiny else {})}


def find_cell(manifest: dict, name: str, root: str = ROOT,
              bench_dir: str = BENCH_DIR) -> dict:
    """One cell with its configuration and traffic files read in."""
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    cell = dict(cells[name])
    entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    cell["config_file"] = load_json(os.path.join(root, entry["file"]))
    cell["traffic_file"] = load_json(traffic_path(cell["traffic"], bench_dir))
    return cell


def metrics_of(manifest: dict, group: str, cell_name: str) -> list[dict]:
    """The metrics of ``end_to_end`` or ``per_layer`` this cell reports:
    those with no ``workloads`` key and those that list the cell."""
    return [m for m in manifest[group]
            if "workloads" not in m or cell_name in m["workloads"]]


def check_manifest(manifest: dict, root: str = ROOT,
                   bench_dir: str = BENCH_DIR) -> list[str]:
    """Every way this manifest breaks the contract the harness can
    see: names, units, sources, the ``moves`` arrows, the files."""
    bad: list[str] = []
    if set(manifest) != MANIFEST_KEYS:
        bad.append(f"keys {sorted(set(manifest) ^ MANIFEST_KEYS)} "
                   f"missing or unknown")
        return bad
    configs = [c["name"] for c in manifest["configs"]]
    cells = {w["name"]: w for w in manifest["workloads"]}
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    every = manifest["end_to_end"] + manifest["per_layer"]
    names = ([m["name"] for m in every], configs, list(cells))
    for group in names:
        for n in group:
            if not NAME.match(n):
                bad.append(f"name {n!r} is not 1-64 of [A-Za-z0-9_.-]")
        if len(set(group)) != len(group):
            bad.append(f"a name appears twice among {group}")
    if len({(w["config"], w["traffic"]) for w in cells.values()}) != len(cells):
        bad.append("a pair of configuration and traffic appears twice")
    for c in manifest["configs"]:
        path = os.path.join(root, c["file"])
        if not c["file"].startswith(tuple(p + "/" for p in manifest["paths"])):
            bad.append(f"config file {c['file']} is outside paths")
        elif not os.path.isfile(path):
            bad.append(f"config file {c['file']} is missing")
        if c["name"] not in {w["config"] for w in cells.values()}:
            bad.append(f"config {c['name']} is used by no cell")
    for w in cells.values():
        if w["config"] not in configs:
            bad.append(f"cell {w['name']}: unknown config {w['config']}")
        if not NAME.match(w["traffic"]):
            bad.append(f"cell {w['name']}: traffic name {w['traffic']!r}")
        if not os.path.isfile(traffic_path(w["traffic"], bench_dir)):
            bad.append(f"cell {w['name']}: no traffic file for "
                       f"{w['traffic']}")
        if w["chips"] not in (1, 4):
            bad.append(f"cell {w['name']}: chips {w['chips']}")
        if not 1 <= len(w["why"]) <= 200:
            bad.append(f"cell {w['name']}: why has {len(w['why'])} chars")
    four = sum(w["chips"] == 4 for w in cells.values())
    if four > max(1, len(cells) // 4):
        bad.append(f"{four} of {len(cells)} cells ask for four chips")
    if "setup_s" not in e2e:
        bad.append("no setup_s among the end-to-end metrics")
    for m in every:
        if not UNIT.match(m["unit"]):
            bad.append(f"{m['name']}: unit {m['unit']!r}")
        if m["better"] not in ("lower", "higher"):
            bad.append(f"{m['name']}: better {m['better']!r}")
        if m["source"] not in SOURCES:
            bad.append(f"{m['name']}: source {m['source']!r}")
        for w in m.get("workloads", []):
            if w not in cells:
                bad.append(f"{m['name']}: lists unknown cell {w}")
    for m in manifest["end_to_end"]:
        if m["source"] not in ("host_clock", "device_trace"):
            bad.append(f"{m['name']}: an end-to-end metric reads "
                       f"{m['source']}")
        if not 0 < m["bound"] <= 0.1:
            bad.append(f"{m['name']}: bound {m['bound']}")
    for m in manifest["per_layer"]:
        if not os.path.isfile(reader_path(m["name"], bench_dir)):
            bad.append(f"{m['name']}: no reader layer_metrics/"
                       f"{m['name']}.py")
        if m["moves"] not in e2e:
            bad.append(f"{m['name']}: moves unknown {m['moves']}")
            continue
        target = e2e[m["moves"]]
        for w in m.get("workloads", cells):
            if "workloads" in target and w not in target["workloads"]:
                bad.append(f"{m['name']} moves {m['moves']}, which cell "
                           f"{w} does not report")
    for w in cells:
        got = [m["name"] for m in metrics_of(manifest, "end_to_end", w)]
        if "setup_s" not in got or len(got) < 2:
            bad.append(f"cell {w} reports {got}: setup_s and one more "
                       f"are required")
        if not metrics_of(manifest, "per_layer", w):
            bad.append(f"cell {w} reports no per-layer metric")
    return bad
