"""Worker process entry point.

Analog of the reference's ``default_worker.py``
(``python/ray/_private/workers/default_worker.py:289``): a dedicated
module run as ``python -m ray_tpu.core.worker_entry <socket> <token>``,
so worker processes never re-import the driver's ``__main__`` (the
multiprocessing-spawn hazard) and carry no inherited interpreter state.
"""

from __future__ import annotations

import time

T_FIRST_LINE = time.monotonic()     # before this module imports anything else

import sys  # noqa: E402


def main() -> None:
    import os

    # Where a ``TrainWorker``'s ``train.worker.process`` span begins.
    from ray_tpu.util import tracing
    tracing.process_start = T_FIRST_LINE

    # The runtime set JAX_PLATFORMS for this worker (cpu without a TPU
    # resource, tpu with one). jax reads it at import; where something
    # imported jax before this point, apply it to the live config too —
    # backends initialize lazily, so this still wins.
    # The compile cache likewise: the caller's directory where the
    # runtime forwarded one, else the one under this host's checkout.
    from ray_tpu.util import compile_cache
    cache = os.environ[compile_cache.ENV_VAR] = compile_cache.cache_dir()
    platforms = os.environ.get("JAX_PLATFORMS")
    if "jax" in sys.modules:
        import jax
        if platforms:
            jax.config.update("jax_platforms", platforms)
        jax.config.update("jax_compilation_cache_dir", cache)

    # runtime_env working_dir: staged driver-side, applied here so
    # user code sees it as cwd AND an import root (PYTHONPATH already
    # carries it for module resolution).
    wd = os.environ.get("RAY_TPU_WORKING_DIR")
    if wd and os.path.isdir(wd):
        os.chdir(wd)

    # honor a driver-exported structured-logging config, if any
    from ray_tpu.core.logging_config import apply_from_env
    apply_from_env()

    address, token = sys.argv[1], sys.argv[2]
    from ray_tpu.core import wire
    conn = wire.dial(address, family="AF_UNIX", kind=wire.K_EXEC,
                     peer="exec listener")
    conn.send(("hello", "exec", token))
    from ray_tpu.core.worker import worker_main
    worker_main(conn, address)


if __name__ == "__main__":
    main()
