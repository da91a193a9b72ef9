"""device boundary: seconds the worker's first jax.devices() took. Moves setup_s."""


def read(run):
    return run.worker["backend_init_s"]
