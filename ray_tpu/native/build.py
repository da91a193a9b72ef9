"""Build the native library (g++; no pybind11 in this image, ctypes ABI).

Compiles lazily into ``ray_tpu/native/_build/`` on first use; rebuilt
when any source is newer than the library. Safe under concurrent
processes (atomic rename).
"""

from __future__ import annotations

import os
import subprocess
import tempfile
import time

_SRC_DIR = os.path.dirname(os.path.abspath(__file__))
_BUILD_DIR = os.path.join(_SRC_DIR, "_build")
_SOURCES = ["store.cpp", "channel.cpp", "tfrec.cpp"]
_LIB = "libraytpu_native.so"


def lib_path() -> str:
    return os.path.join(_BUILD_DIR, _LIB)


def _needs_build() -> bool:
    lib = lib_path()
    if not os.path.exists(lib):
        return True
    lib_mtime = os.path.getmtime(lib)
    return any(
        os.path.getmtime(os.path.join(_SRC_DIR, s)) > lib_mtime
        for s in _SOURCES)


def ensure_built() -> str | None:
    """Returns the library path, building if needed; None on failure."""
    if not _needs_build():
        return lib_path()
    t0 = time.monotonic()
    built = _build()
    # Only a process that compiles records it (the first run of a
    # tree): ``native.build`` in the process ring, which a fit writes
    # beside its own spans (docs/observability.md).
    from ray_tpu.util import tracing
    tracing.record_train_span("native.build", t0, time.monotonic(),
                              {"built": built is not None})
    return built


def _build() -> str | None:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    srcs = [os.path.join(_SRC_DIR, s) for s in _SOURCES]
    fd, tmp = tempfile.mkstemp(suffix=".so", dir=_BUILD_DIR)
    os.close(fd)
    cmd = ["g++", "-O2", "-shared", "-fPIC", "-std=c++17",
           "-o", tmp, *srcs, "-lpthread", "-lrt"]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=120)
        os.replace(tmp, lib_path())
        return lib_path()
    except (subprocess.CalledProcessError, subprocess.TimeoutExpired,
            FileNotFoundError) as e:
        try:
            os.unlink(tmp)
        except OSError:
            pass
        # Say so whatever the cause (no g++, a timeout, a compile
        # error): callers carry on without the library.
        import sys
        stderr = getattr(e, "stderr", None) or b""
        print(f"[ray_tpu.native] build failed: {type(e).__name__}: "
              f"{e}\n{stderr.decode(errors='replace')[:2000]}",
              file=sys.stderr)
        return None
