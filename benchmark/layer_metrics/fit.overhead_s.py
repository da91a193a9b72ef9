"""Train orchestration: JaxTrainer.fit()'s wall minus the worker loop's wall
(gang placement, session start, the last poll, tear-down). Moves setup_s."""


def read(run):
    return run.driver["fit_s"] - (run.worker["t_exit"] - run.worker["t_enter"])
