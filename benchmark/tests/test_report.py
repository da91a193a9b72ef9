"""Checks, per-layer readers and the result line, on made-up facts."""

import math

import pytest

from benchlib import checks, flops, manifest as mf, report


def _facts(**over):
    stamps = [100.0 + 0.25 * i for i in range(60)]
    n = len(stamps)
    losses = [10.98 - 0.002 * i for i in range(n + 1)]
    facts = {
        "platform": "tpu", "kind": "TPU v5 lite", "count": 1,
        "backend_init_s": 6.5, "t_enter": 90.0, "t_exit": 130.0,
        "tpu_custom_calls": 24, "steps_per_dispatch": 1,
        "samples_per_step": 32768, "uniform_over": 50257,
        "flops_per_sample": flops.gpt2_train_flops_per_token(
            12, 768, 1024, 50257),
        "kernel_cost_per_step": flops.flash_attention_train_cost(
            32, 12, 1024, 64, 12),
        "program_bytes": {"total": 13_542_135_808},
        "memory_stats_peak_bytes": 1_390_000_000,
        "devices_spanned": {"params": [1, 1], "batch": [1, 1]},
        "open_i": 4, "close_i": 44, "trace_from": 47, "trace_to": 53,
        "stamps": stamps, "losses": losses,
        "host_s": {k: [v] * n for k, v in
                   (("input", 1e-4), ("dispatch", 2e-3), ("sync", 0.247),
                    ("report", 5e-5))},
        "dispatched": n + 1, "state_step": n + 1, "reports_sent": n + 1,
        "compiles_at_open": 7, "compiles_at_close": 7,
        "compile_s_at_open": 14.0,
        "reference": {"plain_f32": {"loss": 10.9760, "grad_norm": 1.5000},
                      "program": {"loss": 10.9763, "grad_norm": 1.5031},
                      "program_from": "first dispatch", "seconds": 3.0},
    }
    facts.update(over)
    return facts


@pytest.fixture(scope="module")
def man():
    return mf.load_manifest()


@pytest.fixture(scope="module")
def cell(man):
    return mf.find_cell(man, "gpt2-124m.b32-t1024")


def test_a_sound_run_is_correct(cell):
    f = _facts()
    assert checks.failed_checks(f, cell, cell["config_file"], 61, False) == []


@pytest.mark.parametrize("over,reports,touched,says", [
    ({"platform": "cpu"}, 61, False, "worker holds"),
    ({"count": 4}, 61, False, "worker holds"),
    ({"tpu_custom_calls": 0}, 61, False, "tpu_custom_call"),
    ({"losses": [12.5] + [10.98 - 0.002 * i for i in range(60)]}, 61, False,
     "first loss"),
    ({"losses": [10.9] * 30 + [math.nan] + [10.8] * 30}, 61, False,
     "not finite"),
    ({"losses": [10.9] * 61}, 61, False, "not below"),
    ({"state_step": 60}, 61, False, "state.step"),
    ({"compiles_at_close": 8}, 61, False, "inside the window"),
    # a gradient 1% off the float32 reference: rtol is 2**-7
    ({"reference": {"plain_f32": {"loss": 10.976, "grad_norm": 1.5},
                    "program": {"loss": 10.976, "grad_norm": 1.515},
                    "program_from": "first dispatch"}}, 61, False,
     "grad_norm of the program"),
    ({"reference": {"plain_f32": {"loss": 10.976, "grad_norm": 1.5},
                    "program": {"loss": 10.85, "grad_norm": 1.5},
                    "program_from": "probe"}}, 61, False,
     "loss of the program"),
    ({}, 60, False, "train.report delivered"),
    ({}, 61, True, "driver process"),
])
def test_each_check_can_fail(cell, over, reports, touched, says):
    bad = checks.failed_checks(_facts(**over), cell, cell["config_file"],
                               reports, touched)
    assert len(bad) == 1 and says in bad[0], bad


def test_four_chips_must_span_four_devices(man):
    cell = mf.find_cell(man, "gpt2-124m.dp4-b128-t1024")
    f = _facts(count=4)
    bad = checks.failed_checks(f, cell, cell["config_file"], 61, False)
    assert len(bad) == 1 and "span" in bad[0]
    f["devices_spanned"] = {"params": [4, 4], "batch": [4, 4]}
    assert checks.failed_checks(f, cell, cell["config_file"], 61, False) == []


def test_end_to_end_values_come_from_the_stamps(man, cell):
    f = _facts()
    w = report.window_summary(f, 1)
    got = report.end_to_end(man, cell, f, {"t_start": 40.0}, w)
    assert set(got) == {"tokens_per_s_per_chip", "step_ms_p90", "setup_s"}
    assert got["tokens_per_s_per_chip"]["value"] == pytest.approx(
        32768 / 0.25)
    assert got["step_ms_p90"]["value"] == pytest.approx(250.0)
    assert got["setup_s"]["value"] == pytest.approx(101.0 - 40.0)
    assert got["tokens_per_s_per_chip"]["unit"] == "tokens/s/chip"


def test_a_stall_in_the_window_lowers_the_rate_and_the_mfu(man, cell):
    """The end-to-end rate is all the work over all the time of the
    window, and model.mfu_pct follows it; the median does not move."""
    steady = _facts()
    late = [s + (1.0 if i > 20 else 0.0)       # one step takes 1.25 s
            for i, s in enumerate(steady["stamps"])]
    stalled = _facts(stamps=late)
    rates, mfus = [], []
    for f in (steady, stalled):
        w = report.window_summary(f, 1)
        got = report.end_to_end(man, cell, f, {"t_start": 40.0}, w)
        rates.append(got["tokens_per_s_per_chip"]["value"])
        mfus.append(mf.load_reader("model.mfu_pct")(
            report.Run(cell, f, {}, w, None)))
        assert w["step_ms_p50"] == pytest.approx(250.0)
    assert rates[0] == pytest.approx(32768 / 0.25)
    assert rates[1] == pytest.approx(40 * 32768 / 11.0)   # 40 steps, 11 s
    assert mfus[1] / mfus[0] == pytest.approx(10.0 / 11.0)
    w = report.window_summary(stalled, 1)
    assert w["stall_pct"] == pytest.approx(100 * (1 - 0.25 / (11 / 40)))


TRACE = {"devices": 1, "steps": 6, "window_s": 1.5, "busy_s": 1.4985,
         "class_s": {"mxu": 0.9, "kernel": 0.285, "other": 0.3135,
                     "collective": 0.0},
         "collective_s": 0.0, "collective_exposed_s": 0.0,
         "idle_by_span_s": {"bench.sync": 0.0012, "bench.input": 0.0003},
         "device_ops": [], "idle_gaps": []}


def test_every_reader_of_the_cell_reads(man, cell):
    f = _facts()
    run = report.Run(cell, f, {"cluster_up_s": 0.07, "fit_s": 45.0},
                     report.window_summary(f, 1), TRACE)
    got = report.per_layer(man, run, mf.BENCH_DIR)
    want = {m["name"] for m in mf.metrics_of(man, "per_layer", cell["name"])}
    assert set(got) == want
    v = {k: x["value"] for k, x in got.items()}
    assert v["fit.overhead_s"] == pytest.approx(5.0)
    assert v["step.ms_p50"] == pytest.approx(250.0)
    assert v["step.stall_pct"] == pytest.approx(0.0, abs=1e-9)
    assert v["model.mxu_ms_per_step"] == pytest.approx(150.0)
    assert v["kernel.flash_ms_per_step"] == pytest.approx(47.5)
    assert v["input.exposed_ms_per_step"] == pytest.approx(0.05)
    assert v["input.wait_ms_per_step"] == pytest.approx(0.1)
    assert v["fit.report_ms"] == pytest.approx(0.05)
    assert v["device.idle_pct"] == pytest.approx(0.1)
    assert v["device.program_gb"] == pytest.approx(13.542135808)
    # 133.0M x 6 operations a token x 131,072 tokens/s over 197e12
    assert v["model.mfu_pct"] == pytest.approx(53.08, abs=0.05)
    # 9.42 ms at the compute peak over 47.5 ms
    assert v["flash_attention_roofline"] == pytest.approx(19.83, abs=0.05)
    assert 0 < v["flash_attention_roofline"] < 100


def test_a_reader_with_nothing_to_read_is_left_out(man, cell):
    f = _facts()
    run = report.Run(cell, f, {"cluster_up_s": 0.07, "fit_s": 45.0},
                     report.window_summary(f, 1), None)
    got = report.per_layer(man, run, mf.BENCH_DIR)
    assert "model.mxu_ms_per_step" not in got and "step.ms_p50" in got


def test_an_unknown_device_has_no_peak(man, cell):
    f = _facts(kind="TPU v9")
    run = report.Run(cell, f, {}, report.window_summary(f, 1), TRACE)
    with pytest.raises(LookupError, match="TPU v9"):
        mf.load_reader("model.mfu_pct")(run)


def test_required_operations():
    assert flops.resnet_forward_flops_per_image(
        (3, 4, 6, 3), 64, 224, 1000) == pytest.approx(8.18e9, rel=0.01)
    assert flops.gpt2_train_flops_per_token(
        12, 768, 1024, 50257) / 6 == pytest.approx(132.97e6, rel=1e-3)
    r = flops.roofline(1.855e12, 6.7e9, 197e12, 819e9)
    assert r["bound"] == "compute"
    assert r["least_s"] == pytest.approx(9.416e-3, rel=1e-3)
