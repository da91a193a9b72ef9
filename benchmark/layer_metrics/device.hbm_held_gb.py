"""device: what the job held on its fullest chip with the step's executable
loaded, by the chip's own allocator: the largest, over the workers of the
newest gang, of ``hbm_held_bytes`` on the fit's ``train.worker.loop`` span
(``ray_tpu/train/stall.py``: the most ``bytes_in_use + bytes_reserved`` of
any device at any of the watch thread's three samples, after the first
report, after the eighth and at the loop's end), in GB. The
footprint to hold against ``bytes_limit`` (``hbm_limit_bytes`` on
``train.worker.backend_init``): device.program_gb is a compiler's sum for a
second lowering and reads over the limit in a cell that runs. None where the
span has no such attribute (the CPU; a program from before the samples).
Moves step_ms_p90."""


def read(run):
    from benchlib import program_trace
    spans = program_trace.fit_spans() or ()
    gangs = [s.mono_start for s in spans if s.name == "train.fit.gang_start"]
    held = [s.attributes["hbm_held_bytes"] for s in spans
            if s.name == "train.worker.loop"
            and "hbm_held_bytes" in s.attributes
            and (not gangs or s.mono_start >= max(gangs))]
    return max(held) / 1e9 if held else None
