"""The two readers of the memory layer: ``device.hbm_held_gb`` on recorded
``train.worker.loop`` spans and ``model.recompute_ms_per_step`` on a
hand-made ``under_s``; None where there is nothing to read; the manifest
with their two entries; and the rehearsal of a cell whose blocks are
recomputed, after which both return what a CPU gives: nothing."""

import json
import os
import subprocess
import sys

import pytest

from benchlib import manifest as mf
from benchlib import path_trace, report

HELD, RECOMPUTE = "device.hbm_held_gb", "model.recompute_ms_per_step"
CELL = "granite-4.0-h-micro.b1-t8192"
REMAT_CELLS = {"kimi-linear-48b-a3b.b1-t16384",
               "phi-4-mini-flash-reasoning.b1-t4096",
               "laguna-xs.2.b1-t16384", "xing4.0-29b-a4b.b1-t4096",
               "ouro-2.6b.b1-t4096", CELL}


def _run(traced=True):
    cell = mf.find_cell(mf.load_manifest(), CELL)
    return report.Run(cell, {}, {}, {}, {"steps": 4} if traced else None)


# -- device.hbm_held_gb ---------------------------------------------------

def _fit_in_ring(loops, gangs=((1.0, 9.0),)):
    """A finished fit in the process tracer's ring: ``gangs`` as (start,
    end) of each ``train.fit.gang_start``, ``loops`` as (start, the
    loop span's attributes)."""
    from ray_tpu.util import tracing
    tid = os.urandom(8).hex()
    # newer than any fit an earlier test of this process left behind
    t0 = max([s.mono_end for s in tracing.get_spans()
              if s.name == "train.fit"], default=0.0) + 1000.0

    def span(name, a, b, **attributes):
        return tracing.Span(
            name=name, trace_id=tid, span_id=os.urandom(8).hex(),
            parent_id=None if name == "train.fit" else "root",
            start=t0 + a, end=t0 + b, attributes=attributes,
            mono_start=t0 + a, mono_end=t0 + b)

    spans = [span("train.fit", 0.0, 500.0, trial_dir="/nowhere/x/fit"),
             *(span("train.fit.gang_start", a, b) for a, b in gangs),
             *(span("train.worker.loop", a, a + 50.0, **attributes)
               for a, attributes in loops)]
    tracing.get_tracer().add_spans([s.to_dict() for s in spans])


@pytest.mark.parametrize("loops, gangs, want", [
    ([(5.0, {"rank": 0, "hbm_held_bytes": 15_820_000_000})],
     [(1.0, 9.0)], 15.82),
    # the largest over the workers
    ([(5.0, {"rank": 0, "hbm_held_bytes": 15_700_000_000}),
      (5.1, {"rank": 1, "hbm_held_bytes": 15_820_000_000})],
     [(1.0, 9.0)], 15.82),
    # a gang that was restarted: the newest gang's loops alone
    ([(5.0, {"rank": 0, "hbm_held_bytes": 16_500_000_000}),
      (105.0, {"rank": 0, "hbm_held_bytes": 13_040_000_000})],
     [(1.0, 9.0), (101.0, 109.0)], 13.04),
    # the CPU, or a program from before the samples: no such attribute
    ([(5.0, {"rank": 0, "stalls": 0})], [(1.0, 9.0)], None),
    ([(5.0, {"rank": 0, "hbm_held_bytes": 16_500_000_000}),
      (105.0, {"rank": 0})], [(1.0, 9.0), (101.0, 109.0)], None),
    ([], [(1.0, 9.0)], None),
], ids=["one worker", "two workers", "restarted", "no attribute",
        "restarted onto no attribute", "no loop"])
def test_held_is_the_newest_gangs_fullest_worker(loops, gangs, want):
    _fit_in_ring(loops, gangs)
    got = mf.load_reader(HELD)(_run())
    assert got == (want if want is None else pytest.approx(want))
    # a counter of the program's: read in an untraced run's ring too
    assert mf.load_reader(HELD)(_run(traced=False)) == got


# -- model.recompute_ms_per_step ------------------------------------------

UNDER_S = {
    # a recomputed block's second forward, and what it nests
    "blocks/checkpoint/rematted_computation/h_0/mamba/in_proj": 0.004,
    "blocks/checkpoint/rematted_computation/h_0/mlp/down": 0.002,
    "blocks/checkpoint/rematted_computation/h_1/kda/scan/checkpoint/"
    "rematted_computation/body": 0.008,      # nested: counted once
    # an inner checkpoint of a block that is not recomputed, and the loss
    "blocks/h_2/attn/core/checkpoint/rematted_computation": 0.001,
    "loss/checkpoint/rematted_computation/while/body": 0.0005,
    # not recomputed: the first forward, the backward, a name that only
    # holds the word
    "blocks/h_0/mamba/in_proj": 0.1,
    "blocks/checkpoint/h_0/mlp/down": 0.2,
    "blocks/h_0/not_rematted_computation_at_all/mul": 0.4,
    "optimizer": 0.8,
}


@pytest.mark.parametrize("under_s, want", [
    (UNDER_S, (0.004 + 0.002 + 0.008 + 0.001 + 0.0005) / 4 * 1e3),
    ({k: v for k, v in UNDER_S.items() if "/rematted_computation" not in k},
     None),
    ({}, None),
], ids=["recomputed", "nothing recomputed", "empty"])
def test_recompute_is_every_path_with_a_rematted_part(
        monkeypatch, under_s, want):
    monkeypatch.setattr(path_trace, "of_run", lambda run: {
        "devices": 1, "steps": 4, "under_s": under_s})
    got = mf.load_reader(RECOMPUTE)(_run())
    assert got == (want if want is None else pytest.approx(want))


def test_recompute_is_what_path_table_sums_by_hand(monkeypatch):
    """``tools/path_table.py <run> rematted_computation`` keeps the
    paths that contain the word; no scope of the repo's models holds it
    inside a longer name, so the two agree."""
    monkeypatch.setattr(path_trace, "of_run", lambda run: {
        "devices": 1, "steps": 4, "under_s": {
            k: v for k, v in UNDER_S.items() if "not_" not in k}})
    by_hand = sum(v for k, v in UNDER_S.items()
                  if "rematted_computation" in k and "not_" not in k)
    assert mf.load_reader(RECOMPUTE)(_run()) == pytest.approx(
        by_hand / 4 * 1e3)


@pytest.mark.parametrize("name", [HELD, RECOMPUTE])
def test_a_reader_is_none_with_nothing_to_read(name, monkeypatch):
    """No ``train.fit`` in the ring (a program from before the spans),
    and no trace."""
    from ray_tpu.util import tracing
    monkeypatch.setattr(tracing, "_tracer", tracing.Tracer())
    assert mf.load_reader(name)(_run()) is None
    assert mf.load_reader(name)(_run(traced=False)) is None


# -- the manifest ---------------------------------------------------------

def test_the_manifest_is_clean_with_the_two_entries():
    man = mf.load_manifest()
    assert mf.check_manifest(man) == []
    by_name = {m["name"]: m for m in man["per_layer"]}
    held, recompute = by_name[HELD], by_name[RECOMPUTE]
    assert [m["name"] for m in man["per_layer"]][-2:] == [HELD, RECOMPUTE]
    assert held == {
        "name": HELD, "unit": "GB", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "step_ms_p90",
        "workloads": [w["name"] for w in man["workloads"][:14]]}
    assert {k: recompute[k] for k in recompute if k != "workloads"} == {
        "name": RECOMPUTE, "unit": "ms", "better": "lower",
        "source": "device_trace", "layer": "model", "moves": "step_ms_p90"}
    # every cell whose configuration recomputes its blocks
    remat = {w["name"] for w in man["workloads"]
             if mf.find_cell(man, w["name"])["config_file"].get(
                 "model", {}).get("remat") is True}
    assert remat == REMAT_CELLS <= set(recompute["workloads"])
    # the twin it stands beside is as it was
    assert by_name["device.program_gb"] == {
        "name": "device.program_gb", "unit": "GB", "better": "lower",
        "source": "program_counter", "layer": "device",
        "moves": "step_ms_p90"}


# -- a rehearsal ----------------------------------------------------------

REHEARSE = """
import json, sys
sys.path[:0] = [{bench!r}, {root!r}]
import run
from benchlib import manifest as mf, report
args = run.parse_args(["--workload", {cell!r}, "--seed", "3000000007",
                       "--seconds", "1", "--trace", "1", "--rehearse",
                       "--out", {out!r}])
line = run.measure(args)
cell = mf.find_cell(mf.load_manifest(), {cell!r})
facts = mf.load_json({out!r} + "/" + {cell!r}
                     + "/seed3000000007.trace1/worker.json")
steps = ((facts["trace_to"] - facts["trace_from"])
         * facts["steps_per_dispatch"])
a_run = report.Run(cell, facts, {{}}, {{}}, {{"steps": steps}})
print(json.dumps({{"line": line, "read": {{
    name: mf.load_reader(name)(a_run) for name in {names!r}}}}}))
"""


def test_a_recomputed_cell_rehearses_and_both_readers_read_nothing(tmp_path):
    """The CPU's allocator has no counters and its profile no device
    plane: the run is correct, the line names both metrics with null,
    and each reader, asked in the process that ran the fit, says None
    and does not raise."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "JAX_COMPILATION_CACHE_DIR": str(tmp_path / "jax_cache")}
    env.pop("RAY_TPU_CHIPS", None)
    p = subprocess.run(
        [sys.executable, "-c", REHEARSE.format(
            bench=mf.BENCH_DIR, root=mf.ROOT, cell=CELL,
            out=str(tmp_path / "out"), names=[HELD, RECOMPUTE])],
        capture_output=True, text=True, env=env, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    got = json.loads(p.stdout.strip().splitlines()[-1])
    line = got["line"]
    assert line["correct"] is True, p.stderr[-3000:]
    assert line["attempted"] > 0 and line["failed"] == 0
    assert {HELD, RECOMPUTE} <= set(line["metrics"])
    assert all(m["value"] is None for m in line["metrics"].values())
    assert got["read"] == {HELD: None, RECOMPUTE: None}
