"""Run one cell of the benchmark once and print its result line.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic mix and its per-layer metrics
are found by name from ``BENCHMARK.json`` (``benchlib/manifest.py``);
see ``benchmark/README.md``. This process starts the cluster, makes the
input data from the seed and calls ``JaxTrainer.fit`` with
``benchlib/loop.py::train_loop``; it never initialises a jax backend —
the one worker holds the chips. The last line of stdout is the result;
everything else goes to stderr. No chips, no number: the run exits
non-zero and prints no result.

``--rehearse`` runs the same control flow at the models' tiny presets
on virtual CPU devices and prints a line whose metrics are all null: a
check of the plumbing, never a measurement.
"""

from __future__ import annotations

import time

T_START = time.monotonic()      # before anything else: setup_s starts here

import argparse  # noqa: E402
import contextlib  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import sys  # noqa: E402
import traceback  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
DEADLINE_S = 1150               # the first run of a cell may take 1200 s


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--out", default=os.path.join(ROOT, "bench_out"),
                    help="where the run's directory goes")
    ap.add_argument("--rehearse", action="store_true")
    return ap.parse_args(argv)


def measure(args) -> dict:
    """Everything up to the result line, as a dict."""
    from benchlib import manifest as mf
    from benchlib import checks, loop, report

    man = mf.load_manifest()
    cell = mf.find_cell(man, args.workload)
    chips = cell["chips"]
    traffic = mf.effective_traffic(cell["traffic_file"], args.rehearse)
    seconds = man["run_seconds"] if args.seconds is None else args.seconds
    run_dir = os.path.join(os.path.abspath(args.out), cell["name"],
                           f"seed{args.seed}.trace{args.trace}")
    shutil.rmtree(run_dir, ignore_errors=True)
    if args.trace:      # keep one trace a cell: they are tens of MB each
        for old in glob.glob(os.path.join(os.path.dirname(run_dir),
                                          "seed*.trace1", "trace")):
            shutil.rmtree(old, ignore_errors=True)
    os.makedirs(run_dir)

    # The compile cache: where the caller placed it, else a fixed
    # directory of this checkout. The runtime forwards it to the worker.
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(ROOT, ".jax_cache"))
    if args.rehearse:
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + f" --xla_force_host_platform_device_count={chips}").strip()

    import ray_tpu
    from ray_tpu import data, train
    from ray_tpu.core.accelerator import detect_tpu_chips_with_source
    from ray_tpu.native.build import ensure_built

    driver: dict = {"t_start": T_START}
    found, source = detect_tpu_chips_with_source()
    if not args.rehearse and found < chips:
        raise RuntimeError(f"cell {cell['name']} needs {chips} TPU chip(s); "
                           f"found {found} (source: {source})")
    t0 = time.monotonic()
    if ensure_built() is None:
        raise RuntimeError("the native library did not build")
    driver["native_build_s"] = time.monotonic() - t0
    t0 = time.monotonic()
    with contextlib.redirect_stdout(sys.stderr):   # the log monitor's sink
        ray_tpu.init()
    driver["cluster_up_s"] = time.monotonic() - t0

    builder = mf.load_builder(cell["config_file"]["builder"])
    t0 = time.monotonic()
    arrays = builder.host_dataset(cell["config_file"], traffic, chips,
                                  args.seed, args.rehearse, seconds)
    datasets = {"train": data.from_numpy(arrays)} if arrays else {}
    driver["dataset_s"] = time.monotonic() - t0

    trainer = train.JaxTrainer(
        loop.train_loop,
        train_loop_config={
            "chips": chips, "seed": args.seed, "seconds": seconds,
            "trace": args.trace, "tiny": args.rehearse, "out_dir": run_dir,
            "config": cell["config_file"], "traffic": traffic},
        scaling_config=train.ScalingConfig(
            num_workers=1,
            tpu_chips_per_worker=0 if args.rehearse else chips),
        run_config=train.RunConfig(
            name="fit", storage_path=os.path.join(run_dir, "experiments")),
        datasets=datasets)
    t0 = time.monotonic()
    result = trainer.fit()
    driver["fit_s"] = time.monotonic() - t0
    ray_tpu.shutdown()

    worker_file = os.path.join(run_dir, "worker.json")
    facts = mf.load_json(worker_file) if os.path.exists(worker_file) else {}
    if result.error or facts.get("phase") != "done":
        raise RuntimeError(f"fit() failed in phase {facts.get('phase')!r}: "
                           f"{(result.error or '')[-3000:]}")

    import jax._src.xla_bridge as xb
    bad = checks.failed_checks(
        facts, cell, cell["config_file"], len(result.metrics_history),
        xb.backends_are_initialized(), args.rehearse)
    for b in bad:
        log("check failed:", b)
    line = report.result_line(man, cell, facts, driver, run_dir,
                              bool(args.trace), not bad, args.rehearse)
    with open(os.path.join(run_dir, "result.json"), "w") as f:
        json.dump({**line, "failed_checks": bad, "driver": driver}, f)
    return line


def main() -> None:
    args = parse_args()
    sys.path.insert(0, ROOT)

    def on_deadline(*_):
        raise TimeoutError(f"benchmark/run.py exceeded {DEADLINE_S} s")

    signal.signal(signal.SIGALRM, on_deadline)
    signal.alarm(DEADLINE_S)
    line, code = None, 1
    try:
        line = measure(args)
        code = 0
    except BaseException:  # noqa: BLE001 — no result line, non-zero exit
        traceback.print_exc()
    finally:
        signal.alarm(0)
        with contextlib.suppress(Exception):
            import ray_tpu
            with contextlib.redirect_stdout(sys.stderr):
                ray_tpu.shutdown()      # stops every process it started
    if line is not None:
        print(json.dumps(line), flush=True)
    sys.exit(code)


if __name__ == "__main__":
    main()
