"""What decides ``correct``: ``chip_smoke.py``'s checks and the
comparison with the configuration's plain float32 reference, held to
every run. Each check has a name; a run is correct when none fails."""

from __future__ import annotations

import math


def failed_checks(facts: dict, cell: dict, config: dict,
                  reports_received: int, driver_touched_backend: bool,
                  rehearsal: bool = False) -> list[str]:
    """Names (with the numbers) of the checks this run fails. A CPU
    rehearsal holds no chip and no kernel by design, so those two
    checks are skipped there; its line carries no number anyway."""
    bad: list[str] = []

    def check(ok: bool, what: str) -> None:
        if not ok:
            bad.append(what)

    chips, k = cell["chips"], facts["steps_per_dispatch"]
    losses = facts["losses"]
    o, c = facts["open_i"], facts["close_i"]
    check(rehearsal
          or (facts["platform"] == "tpu" and facts["count"] == chips),
          f"worker holds {facts['count']} x {facts['platform']}, "
          f"the cell asks for {chips} x tpu")
    if config["kernel"]["tpu_custom_call"] and not rehearsal:
        check(facts["tpu_custom_calls"] > 0,
              "no tpu_custom_call in the lowered step: the Pallas "
              "kernel is not in the program")
    spec = {**config["loss"],
            **(config["tiny"].get("loss", {}) if rehearsal else {})}
    want = math.log(facts["uniform_over"])
    check(abs(losses[0] - want) < spec["first_within"],
          f"first loss {losses[0]:.4f} not within {spec['first_within']} "
          f"of ln {facts['uniform_over']} = {want:.4f}")
    check(all(math.isfinite(x) for x in losses), "a loss is not finite")
    window = losses[o:c + 1]
    if spec["declines"] and len(window) >= 20:
        first, last = sum(window[:10]) / 10, sum(window[-10:]) / 10
        check(last < first, f"mean loss of the window's last ten steps "
                            f"{last:.4f} not below its first ten {first:.4f}")
    ref = facts["reference"]
    rtol = config["reference"]["rtol"]
    for what, want in ref["plain_f32"].items():
        got = ref["program"][what]
        check(abs(got - want) <= rtol * abs(want),
              f"{what} of the program ({ref['program_from']}) {got:.6f} "
              f"is not within {rtol} of the float32 reference's "
              f"{want:.6f}")
    check(facts["state_step"] == facts["dispatched"] * k,
          f"state.step {facts['state_step']} != {facts['dispatched']} "
          f"dispatches x {k}")
    check(facts["compiles_at_close"] == facts["compiles_at_open"],
          f"{facts['compiles_at_close'] - facts['compiles_at_open']} "
          f"compile(s) inside the window")
    if chips > 1:
        spans = facts["devices_spanned"]
        check(spans["params"] == [chips, chips]
              and spans["batch"] == [chips, chips],
              f"parameters and batch span {spans}, not {chips} devices")
    check(reports_received == facts["reports_sent"],
          f"train.report delivered {reports_received} of "
          f"{facts['reports_sent']} lines")
    check(not driver_touched_backend,
          "the driver process initialised a jax backend")
    return bad
