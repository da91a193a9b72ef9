"""bench.py must never hang: a bench child that hangs (backend init
that never returns) or dies yields an error JSON line and a non-zero
exit, quickly; and the CPU smoke lane proves the fused-step claims."""

import importlib.util
import json
import os
import subprocess
import sys
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
BENCH = REPO / "bench.py"


def _load_bench():
    spec = importlib.util.spec_from_file_location("bench", BENCH)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_default_probe_budget_under_90s():
    b = _load_bench()
    worst = sum(b.PROBE_TIMEOUTS) + b.PROBE_BACKOFF_S * (
        len(b.PROBE_TIMEOUTS) - 1)
    # Leave margin for process spawn/kill overhead on top.
    assert worst <= 85, worst


def test_dead_backend_emits_error_json_and_exits_nonzero():
    env = dict(os.environ)
    env.update({
        "RAY_TPU_BENCH_FAKE_HANG": "1",
        "RAY_TPU_BENCH_PROBE_TIMEOUT": "3",
        "RAY_TPU_BENCH_PROBE_BACKOFF": "1",
        "RAY_TPU_BENCH_SKIP_SCALING": "1",
        "RAY_TPU_BENCH_SKIP_RESNET": "1",
    })
    t0 = time.time()
    out = subprocess.run(
        [sys.executable, str(BENCH)], capture_output=True, text=True,
        env=env, timeout=60)
    dt = time.time() - t0
    assert out.returncode == 1
    line = json.loads(out.stdout.strip().splitlines()[-1])
    assert line["metric"] == "gpt2_tokens_per_sec_per_chip"
    assert line["value"] == 0.0
    assert "error" in line and "hung" in line["error"]
    assert dt < 45, dt


def test_smoke_lane_proves_fused_step_claims():
    """`bench.py --smoke` (the CPU tier-1 lane) must pass end to end:
    fused step donates, compile count stable, prefetcher feeds the hot
    loop, xplane parser reads back a real capture — one JSON line,
    rc 0. No device-time claims are made or checked."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    out = subprocess.run(
        [sys.executable, str(BENCH), "--smoke"], capture_output=True,
        text=True, timeout=420, env=env)
    lines = [l for l in out.stdout.strip().splitlines()  # noqa: E741
             if l.strip().startswith("{")]
    assert lines, f"no JSON line: {out.stdout!r} / {out.stderr[-300:]!r}"
    line = json.loads(lines[-1])
    assert out.returncode == 0, (line, out.stderr[-300:])
    assert line["metric"] == "bench_smoke" and line["ok"] is True
    extra = line["extra"]
    assert extra["donated"] is True
    assert extra["compiles_stable"] is True
    assert extra["fused_step_compiles"] <= 2
    assert extra["prefetched_all"] is True
    assert extra["xplane_parsed"] is True


def test_child_crash_reports_json():
    # A child that raises (not hangs) must still print a JSON line.
    out = subprocess.run(
        [sys.executable, str(BENCH), "--probe"], capture_output=True,
        text=True, timeout=30,
        env={**os.environ, "RAY_TPU_BENCH_FAKE_FAIL": "1"})
    assert out.returncode == 1
    line = json.loads(
        [l for l in out.stdout.strip().splitlines()  # noqa: E741
         if l.strip().startswith("{")][-1])
    assert "error" in line
