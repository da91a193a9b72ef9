"""step: backend compile (or cache load) seconds before the window opened,
from jax.monitoring. Moves setup_s."""


def read(run):
    return run.worker["compile_s_at_open"]
