"""The manifest checker, and discovery of a configuration, a cell, a
traffic mix and a per-layer metric added as new files with no edit to
a file that is there."""

import copy
import json
import os
import shutil

import pytest

from benchlib import manifest as mf
from benchlib import report


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest()


def test_the_committed_manifest_passes(man):
    assert mf.check_manifest(man) == []


def test_mfu_is_not_an_end_to_end_metric(man):
    e2e = [m["name"] for m in man["end_to_end"]]
    assert "setup_s" in e2e and not any("mfu" in n for n in e2e)
    assert "model.mfu_pct" in [m["name"] for m in man["per_layer"]]


def _broken(man, edit):
    m = copy.deepcopy(man)
    edit(m)
    return mf.check_manifest(m)


@pytest.mark.parametrize("edit,says", [
    (lambda m: m["end_to_end"][0].update(unit="tokens per second"), "unit"),
    (lambda m: m["end_to_end"][0].update(unit="x" * 17), "unit"),
    (lambda m: m["per_layer"][0].update(name="has space"), "name"),
    (lambda m: m["per_layer"][0].update(name="x" * 65), "name"),
    (lambda m: m["workloads"][0].update(name="a/b"), "name"),
    (lambda m: m["per_layer"][0].update(moves="nothing"), "moves unknown"),
    # a metric reported in every cell may not move one that only the
    # GPT-2 cells report
    (lambda m: m["per_layer"][0].update(moves="tokens_per_s_per_chip"),
     "does not report"),
    (lambda m: m["per_layer"][0].update(source="guess"), "source"),
    (lambda m: m["end_to_end"][0].update(source="program_counter"),
     "end-to-end metric reads"),
    (lambda m: m["end_to_end"][0].update(bound=0.2), "bound"),
    (lambda m: m["per_layer"].append(dict(m["per_layer"][0])), "twice"),
    (lambda m: m["workloads"].append(
        dict(m["workloads"][0], name="again")), "appears twice"),
    (lambda m: m["workloads"][0].update(chips=2), "chips"),
    (lambda m: [w.update(chips=4) for w in m["workloads"]], "four chips"),
    (lambda m: m["workloads"][0].update(why="w" * 201), "why"),
    (lambda m: m["workloads"][0].update(traffic="no-such-mix"),
     "no traffic file"),
    (lambda m: m["per_layer"].append(
        dict(m["per_layer"][0], name="no.reader")), "no reader"),
    (lambda m: m["configs"].append(
        dict(m["configs"][0], name="unused")), "used by no cell"),
    (lambda m: m["end_to_end"].pop(), "setup_s"),
    (lambda m: m.update(extra=1), "unknown"),
])
def test_the_checker_refuses(man, edit, says):
    bad = _broken(man, edit)
    assert any(says in b for b in bad), bad


def test_every_per_layer_metric_moves_something_each_of_its_cells_reports(
        man):
    for cell in man["workloads"]:
        e2e = {m["name"] for m in mf.metrics_of(man, "end_to_end",
                                                 cell["name"])}
        for m in mf.metrics_of(man, "per_layer", cell["name"]):
            assert m["moves"] in e2e, (cell["name"], m["name"])


def test_new_files_are_found_with_no_edit_to_an_existing_one(man, tmp_path):
    root = tmp_path / "checkout"
    bench = root / "benchmark"
    shutil.copytree(mf.BENCH_DIR, bench,
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = {p: p.read_bytes() for p in bench.rglob("*") if p.is_file()}
    # a later PR's additions: one file each, plus manifest entries
    cfg = json.loads((bench / "configs" / "gpt2-124m.json").read_text())
    cfg["name"] = "gpt2-355m"
    (bench / "configs" / "gpt2-355m.json").write_text(json.dumps(cfg))
    mix = json.loads((bench / "traffic" / "b32-t1024.json").read_text())
    mix["batch_per_chip"] = 8
    (bench / "traffic" / "b8-t1024.json").write_text(json.dumps(mix))
    (bench / "layer_metrics" / "step.max_ms.py").write_text(
        '"""step: the slowest step of the window."""\n\n\n'
        'def read(run):\n    return run.window["step_ms_max"]\n')
    new = copy.deepcopy(man)
    new["configs"].append({
        "name": "gpt2-355m", "source": "https://example.org/gpt2-medium",
        "file": "benchmark/configs/gpt2-355m.json", "reduced": [],
        "why": "a wider model"})
    new["workloads"].append({
        "name": "gpt2-355m.b8-t1024", "config": "gpt2-355m",
        "traffic": "b8-t1024", "chips": 1, "why": "a smaller batch"})
    new["end_to_end"][0]["workloads"].append("gpt2-355m.b8-t1024")
    new["per_layer"].append({
        "name": "step.max_ms", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "step", "moves": "step_ms_p90"})
    (root / "BENCHMARK.json").write_text(json.dumps(new))

    assert mf.check_manifest(new, str(root), str(bench)) == []
    cell = mf.find_cell(new, "gpt2-355m.b8-t1024", str(root), str(bench))
    assert cell["config_file"]["name"] == "gpt2-355m"
    assert cell["traffic_file"]["batch_per_chip"] == 8
    run = report.Run(cell, {}, {}, {"step_ms_max": 12.5}, None)
    assert mf.load_reader("step.max_ms", str(bench))(run) == 12.5
    names = [m["name"] for m in mf.metrics_of(new, "per_layer",
                                              "gpt2-355m.b8-t1024")]
    assert "step.max_ms" in names and "collective.ms_per_step" not in names
    # and nothing that was there changed
    assert all(p.read_bytes() == b for p, b in before.items())


def test_every_file_name_is_made_of_name_characters():
    import re
    ok = re.compile(r"^[A-Za-z0-9_.\-/]+$")
    for dirpath, dirs, files in os.walk(mf.BENCH_DIR):
        dirs[:] = [d for d in dirs if d != "__pycache__"]
        for f in files:
            rel = os.path.relpath(os.path.join(dirpath, f), mf.ROOT)
            assert ok.match(rel), rel
