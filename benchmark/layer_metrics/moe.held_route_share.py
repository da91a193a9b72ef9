"""model: of all the routes of all expert layers, the share (in %) that
landed on the experts this chip holds, as the step's first dispatch
reported it: 100 x (1 - ``moe_absent_route_share``) of
``nemotron_h_loss_fn``'s report, which worker.json keeps under
``reference.program`` because the reference is held to it. 6.25 is an even
load at 8 of 128 held. The held experts' grouped matmuls and the rows they
gather scale with it; the router, the sort and the scatter into the tokens'
rows do not. Moves step_ms_p90."""


def read(run):
    absent = (run.worker.get("reference", {}).get("program", {})
              .get("moe_absent_route_share"))
    return None if absent is None else (1.0 - absent) * 100.0
