"""The device boundary refuses the CPU where a TPU was asked for.

CPU-side guards of what ``chip_smoke.py`` proves on the chip: the smoke
fails fast and says where on a machine with no chip, a worker granted a
TPU never lands on the CPU, a TPU request the cluster cannot meet fails
at once, and the compile cache lives where the caller (or the checkout)
says — in the driver and in the workers alike.
"""

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

import ray_tpu
from ray_tpu import train

REPO = Path(__file__).resolve().parent.parent


def _run(args, env_over, timeout=60):
    env = {k: v for k, v in os.environ.items()
           if k not in ("RAY_TPU_CHIPS", "JAX_COMPILATION_CACHE_DIR")}
    for k, v in env_over.items():
        if v is None:
            env.pop(k, None)
        else:
            env[k] = v
    t0 = time.time()
    out = subprocess.run([sys.executable, *args], capture_output=True,
                         text=True, env=env, cwd=REPO, timeout=timeout)
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return out, lines, time.time() - t0


@pytest.mark.parametrize("env_over,failed_phase", [
    ({"JAX_PLATFORMS": "cpu"}, "detect"),
    ({"JAX_PLATFORMS": None}, "detect"),
    # Chips claimed that are not there: the worker is pinned to the
    # TPU and jax raises in it; nothing falls back to the CPU.
    ({"JAX_PLATFORMS": "cpu", "RAY_TPU_CHIPS": "1"}, "worker"),
], ids=["cpu", "unset", "claimed_chip"])
def test_chip_smoke_fails_fast_without_a_chip(env_over, failed_phase):
    out, lines, dt = _run(["chip_smoke.py"], env_over)
    assert out.returncode != 0
    assert dt < 60, dt
    assert json.loads(out.stdout.splitlines()[-1]) == {
        "ok": False, "device": None}
    by_phase = {x["phase"]: x for x in lines if "phase" in x}
    assert "detect_tpu_chips" in by_phase["detect"]
    assert by_phase[failed_phase]["ok"] is False
    assert not any(x["ok"] for x in lines if x.get("phase") == "driver")


_HANG_PROBE = """
import sys
import time

import chip_smoke as cs


def hang(config):
    rep = cs._Reporter(config["phase_log"])
    with rep.phase("worker") as f:
        f.update(platform="tpu", kind="described", count=1)
    with rep.phase("kernel"):
        time.sleep(600)


cs.DEADLINE_S = 8
cs.train_loop = hang
sys.argv = ["chip_smoke.py"]
cs.main()
"""


def test_chip_smoke_names_the_phase_that_hung():
    """The deadline fires while a worker phase is still running (a hung
    compile, on the chip): stdout still carries the phases that ended
    and names the one that did not, ahead of the fit and the last line."""
    out, lines, dt = _run(["-c", _HANG_PROBE],
                          {"JAX_PLATFORMS": "cpu", "RAY_TPU_CHIPS": "1"})
    assert out.returncode != 0
    assert dt < 60, dt
    assert [(x.get("phase"), x["ok"]) for x in lines] == [
        ("detect", True), ("worker", True), ("kernel", False),
        ("fit", False), (None, False)], out.stdout
    assert "never ended" in lines[2]["error"]
    assert "exceeded 8 s" in lines[3]["error"]


def test_tpu_actor_never_lands_on_the_cpu():
    """The parent's JAX_PLATFORMS=cpu (conftest) must not reach a worker
    that holds a TPU: it is pinned to the TPU, so with no chip here
    jax.devices() raises instead of returning a CPU device."""
    ray_tpu.init(num_cpus=2, num_tpus=1)
    try:
        @ray_tpu.remote(num_tpus=1)
        class OnChip:
            def devices(self):
                import jax
                return os.environ["JAX_PLATFORMS"], str(jax.devices())

        @ray_tpu.remote
        def off_chip():
            import jax
            return os.environ["JAX_PLATFORMS"], jax.devices()[0].platform

        assert ray_tpu.get(off_chip.remote(), timeout=60) == ("cpu", "cpu")
        with pytest.raises(Exception, match="initialize backend 'tpu'"):
            ray_tpu.get(OnChip.remote().devices.remote(), timeout=60)
    finally:
        ray_tpu.shutdown()


def test_fit_fails_at_once_when_no_node_holds_the_chips():
    """A static cluster (no autoscaler) that holds no chips: the first
    attempt fails in seconds, as a worker-group failure under
    FailureConfig (max_failures=0: a Result with the error)."""
    ray_tpu.init(num_cpus=2, num_tpus=0)
    try:
        trainer = train.JaxTrainer(
            lambda: None, scaling_config=train.ScalingConfig(
                num_workers=2, tpu_chips_per_worker=4))
        t0 = time.time()
        result = trainer.fit()
        assert time.time() - t0 < 10
        msg = result.error
        assert "asks for 8 TPU chips" in msg and "hold 0" in msg, msg
        assert "no autoscaler is attached" in msg, msg
    finally:
        ray_tpu.shutdown()


def test_fit_scales_tpu_nodes_up_from_zero_under_an_autoscaler():
    """With an autoscaler attached the same request is demand, not an
    error: the placement group is created unplaced, the reconciler
    reads its bundle and launches the slice, and fit() runs on it."""
    from ray_tpu.autoscaler import (
        Autoscaler, AutoscalerConfig, LocalNodeProvider, NodeTypeConfig,
    )
    from ray_tpu.core.api import get_runtime

    ray_tpu.init(num_cpus=2, num_tpus=0)
    asc = Autoscaler(AutoscalerConfig(
        node_types=[NodeTypeConfig("v5e-1", {"CPU": 2, "TPU": 1},
                                   min_workers=0, max_workers=1)],
        update_interval_s=0.2), LocalNodeProvider(get_runtime()))
    try:
        assert ray_tpu.cluster_resources().get("TPU", 0) == 0
        asc.start()

        def loop():
            train.report({"platforms": os.environ["JAX_PLATFORMS"]})

        result = train.JaxTrainer(
            loop, scaling_config=train.ScalingConfig(
                num_workers=1, tpu_chips_per_worker=1)).fit()
        assert result.error is None, result.error
        assert result.metrics["platforms"] == "tpu"   # granted a chip
        assert asc.launched_total == 1
    finally:
        asc.stop()
        ray_tpu.shutdown()


def test_error_result_keeps_everything_reported_before_the_failure(rt):
    def loop():
        for i in range(40):     # more than one poll drains at a time
            train.report({"i": i})
        raise RuntimeError("boom after 40 reports")

    result = train.JaxTrainer(loop).fit()
    assert "boom after 40 reports" in result.error
    assert [m["i"] for m in result.metrics_history] == list(range(40))


def test_detect_names_its_source(monkeypatch):
    from ray_tpu.core.accelerator import detect_tpu_chips_with_source
    monkeypatch.setenv("RAY_TPU_CHIPS", "4")
    assert detect_tpu_chips_with_source() == (4, "RAY_TPU_CHIPS")
    monkeypatch.setenv("RAY_TPU_CHIPS", "four")
    with pytest.raises(ValueError, match="RAY_TPU_CHIPS"):
        detect_tpu_chips_with_source()
    monkeypatch.delenv("RAY_TPU_CHIPS")
    n, source = detect_tpu_chips_with_source()
    assert (n == 0) == (source == "none")


_CACHE_PROBE = """
import json
import jax
import ray_tpu
from ray_tpu.util import compile_cache
compile_cache.enable()
ray_tpu.init(num_cpus=1)

@ray_tpu.remote
def worker_dir():
    import jax
    return jax.config.jax_compilation_cache_dir

print(json.dumps({"driver": jax.config.jax_compilation_cache_dir,
                  "worker": ray_tpu.get(worker_dir.remote(), timeout=60)}))
ray_tpu.shutdown()
"""


@pytest.mark.parametrize("placed", [True, False],
                         ids=["env_set", "env_unset"])
def test_compile_cache_dir_is_one_path_for_driver_and_workers(
        placed, tmp_path):
    """Set: exactly the caller's path (created). Unset: the same fixed
    path under the checkout in two separate processes."""
    want = str(tmp_path / "placed") if placed else str(REPO / ".jax_cache")
    env = {"JAX_COMPILATION_CACHE_DIR": want if placed else None,
           "JAX_PLATFORMS": "cpu"}
    for _ in range(2):
        out, lines, _ = _run(["-c", _CACHE_PROBE], env, timeout=120)
        assert out.returncode == 0, out.stderr[-2000:]
        assert lines[-1] == {"driver": want, "worker": want}
    assert os.path.isdir(want)
