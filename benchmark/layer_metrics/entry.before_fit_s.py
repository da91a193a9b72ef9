"""entry / head: seconds from run.py's first line (``driver.t_start``) to the
start of the newest train.fit span: imports, chip detection, the native
library, ray_tpu.init() (the span core.init), the host dataset, the trainer.
Same monotonic clock. Moves setup_s."""


def read(run):
    from benchlib import setup_trace
    return setup_trace.part(run, "before_fit_s")
