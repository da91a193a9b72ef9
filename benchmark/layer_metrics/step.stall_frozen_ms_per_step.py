"""step: of step.stalled_ms_per_step, the milliseconds a step in which no
thread of the worker's process ran: the frozen_s of the window's train.stall
spans (the watch thread's beats that came late while the process used no
CPU), clipped to each span's excess_s. The machine's share, which no change
to the program can move and which a reader of a spread subtracts first.
Moves step_ms_p90."""


def read(run):
    from benchlib import manifest
    return manifest.load_reader("step.stalled_ms_per_step")(
        run, lambda a: min(a["frozen_s"], a["excess_s"]))
