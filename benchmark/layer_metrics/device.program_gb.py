"""device: what the compiler says the step program holds on each chip
(memory_analysis(): arguments + temporaries + outputs that alias no
argument). memory_stats() does not see temporaries. Moves step_ms_p90."""


def read(run):
    total = run.worker["program_bytes"].get("total")
    return None if not total else total / 1e9
