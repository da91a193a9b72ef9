"""``run.py`` end to end: a CPU rehearsal of each cell's loop at the
models' tiny presets (control flow and the shape of the last line; no
device metric is written), and the refusal to measure without chips."""

import json
import os
import subprocess
import sys

import pytest

from benchlib import manifest as mf

RUN = os.path.join(mf.BENCH_DIR, "run.py")
CELLS = [w["name"] for w in mf.load_manifest()["workloads"]]


def _run(args, tmp_path, **env):
    e = {**os.environ, "JAX_PLATFORMS": "cpu", "RAY_TPU_CHIPS": "",
         # the CPU backend's cache entries are not for sharing with the
         # repo's own tests
         "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache"), **env}
    e.pop("RAY_TPU_CHIPS")
    return subprocess.run(
        [sys.executable, RUN, *args, "--out", str(tmp_path / "out")],
        capture_output=True, text=True, env=e, timeout=300)


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal(cell, trace, tmp_path):
    p = _run(["--workload", cell, "--seed", "3000000001", "--seconds", "1",
              "--trace", str(trace), "--rehearse"], tmp_path)
    assert p.returncode == 0, p.stderr[-3000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert set(line) >= {"correct", "attempted", "failed", "metrics",
                         "device"}
    assert line["correct"] is True, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    man = mf.load_manifest()
    chips = {w["name"]: w["chips"] for w in man["workloads"]}[cell]
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == chips
    group = "per_layer" if trace else "end_to_end"
    assert set(line["metrics"]) == {m["name"] for m in
                                    mf.metrics_of(man, group, cell)}
    # a CPU run is never written under a device metric's name
    assert all(m["value"] is None for m in line["metrics"].values())
    run_dir = tmp_path / "out" / cell / f"seed3000000001.trace{trace}"
    iv = json.loads((run_dir / "intervals.json").read_text())
    assert iv["open"] < iv["close"] < len(iv["stamps_s"])
    assert iv["stamps_s"][iv["open"]] == 0.0
    assert iv["stamps_s"][iv["close"]] >= 1.0 > iv["stamps_s"][iv["close"] - 1]
    assert len(iv["loss"]) == len(iv["stamps_s"]) + 1
    if trace:
        assert iv["trace_from"] > iv["close"]
        assert list(run_dir.glob("trace/**/*.xplane.pb"))


def test_no_chip_no_number(tmp_path):
    p = _run(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
              "--trace", "0"], tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "needs 1 TPU chip" in p.stderr


def test_without_the_program_there_is_no_result(tmp_path):
    """A directory that holds only BENCHMARK.json and benchmark/."""
    import shutil
    root = tmp_path / "bare"
    shutil.copytree(mf.BENCH_DIR, root / "benchmark",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(mf.ROOT, "BENCHMARK.json"), root)
    p = subprocess.run(
        [sys.executable, str(root / "benchmark" / "run.py"), "--workload",
         CELLS[0], "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, timeout=120, cwd=root,
        env={k: v for k, v in os.environ.items() if k != "PYTHONPATH"})
    assert p.returncode != 0 and p.stdout.strip() == ""
