"""model: device self time per step under ``blocks/.../exit_gate`` and
``loss/exit`` of a looped stack (ray_tpu/models/ouro.py): the gate's 2048 -> 1
product over every pass's rows, and the sigmoid, the survival products, the
entropy and the weighting of the loss by the exit distribution, both passes.
None for a step without the scopes. Moves step_ms_p90."""


def read(run):
    from benchlib import ouro_trace
    return ouro_trace.exit_gate_ms_per_step(run)
