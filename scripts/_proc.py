"""Child-run contract for the perf harness scripts.

One implementation of: spawn the child in its OWN session, kill the
whole process group on timeout (wedged jax threads survive a plain
terminate), and scan stdout bottom-up for the last parseable JSON
line. perf_snapshot runs its children under this contract.
"""

from __future__ import annotations

import json
import os
import signal
import subprocess
import sys


def run_child(args: list[str], timeout: float,
              extra_env: dict | None = None,
              cwd: str | None = None
              ) -> tuple[str, str, int | None, bool]:
    """Returns (stdout, stderr, returncode, timed_out)."""
    env = None
    if extra_env is not None:
        env = dict(os.environ)
        env.update(extra_env)
    proc = subprocess.Popen(
        args, stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        start_new_session=True, cwd=cwd, env=env, text=True)
    try:
        out, err = proc.communicate(timeout=timeout)
        return out or "", err or "", proc.returncode, False
    except subprocess.TimeoutExpired:
        try:
            os.killpg(proc.pid, signal.SIGKILL)
        except (ProcessLookupError, PermissionError):
            pass
        proc.wait()
        return "", f"timeout after {timeout:.0f}s", None, True


def last_json_line(out: str) -> dict | None:
    for line in reversed((out or "").strip().splitlines()):
        line = line.strip()
        if line.startswith("{"):
            try:
                return json.loads(line)
            except json.JSONDecodeError:
                continue
    return None


def tail_error(err: str, out: str, rc) -> str:
    tail = (err or out or "").strip().splitlines()[-3:]
    return f"rc={rc}: " + (" | ".join(tail) or "no output")[:300]


def _self_test() -> None:
    out, err, rc, to = run_child(
        [sys.executable, "-c", "print('x'); print('{\"ok\": 1}')"], 10)
    assert last_json_line(out) == {"ok": 1} and rc == 0 and not to
    out, err, rc, to = run_child(
        [sys.executable, "-c", "import time; time.sleep(60)"], 0.5)
    assert to and "timeout" in err
    print("ok")


if __name__ == "__main__":
    _self_test()
