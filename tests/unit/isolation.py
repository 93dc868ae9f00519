"""Subprocess isolation + stall watchdog for mesh-churny tests.

XLA's emulated-CPU collective executor can deadlock (every thread
futex-parked, 0% CPU, no stuck-collective watchdog fire) on this 1-core box.
Observed round 3 on EP programs and round 4 on the NVMe-offload step, the
autotuner sweep, and even fresh subprocesses running two meshes back-to-back.
It is probabilistic and an artifact of ``--xla_force_host_platform_device_count``
emulation, not a framework property: the identical scenarios pass standalone
and on real hardware, and a retried run virtually always succeeds.

Two tools:
- :func:`run_isolated` — run a scenario in a fresh python subprocess, with a
  CPU-progress watchdog that kills and RETRIES a wedged child instead of
  hanging the suite.
- :func:`tree_cpu_ticks` / :func:`run_with_stall_watchdog` — the same
  watchdog for arbitrary commands (the suite shard runner in
  tests/conftest.py uses it).
"""

from __future__ import annotations

import os
import subprocess
import sys
import time

PREAMBLE = """
import os
os.environ["XLA_FLAGS"] = os.environ.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
import jax
jax.config.update("jax_platforms", "cpu")
jax.config.update("jax_cpu_enable_async_dispatch", False)
jax.config.update("jax_disable_most_optimizations", True)  # as tests/conftest.py
# no persistent compile cache is set here: cache-deserialized CPU collective
# programs deadlock on this VM (see tests/conftest.py)
import numpy as np
"""


def tree_cpu_ticks(pid: int) -> int:
    """utime+stime of ``pid`` and every descendant (a parent blocked on a
    working child must count as progressing)."""
    total = 0
    stack = [pid]
    while stack:
        p = stack.pop()
        try:
            with open(f"/proc/{p}/stat") as f:
                parts = f.read().rsplit(")", 1)[1].split()
            total += int(parts[11]) + int(parts[12])  # utime, stime
            for tid in os.listdir(f"/proc/{p}/task"):
                with open(f"/proc/{p}/task/{tid}/children") as f:
                    stack.extend(int(c) for c in f.read().split())
        except (OSError, IndexError, ValueError):
            continue
    return total


def run_with_stall_watchdog(cmd, env=None, stall_seconds: int = 120,
                            timeout: int = 900, poll: int = 1, **popen_kw):
    """Run ``cmd``; kill it if its process tree makes no CPU progress for
    ``stall_seconds`` (the wedge signature). Returns
    ``(returncode_or_None, stalled: bool)`` — ``stalled=True`` means it was
    killed by the watchdog and is worth retrying."""
    proc = subprocess.Popen(cmd, env=env, **popen_kw)
    deadline = time.monotonic() + timeout
    last_ticks = -1
    last_progress = time.monotonic()
    while True:
        rc = proc.poll()
        if rc is not None:
            return rc, False
        now = time.monotonic()
        ticks = tree_cpu_ticks(proc.pid)
        if ticks != last_ticks:
            last_ticks = ticks
            last_progress = now
        if now - last_progress > stall_seconds or now > deadline:
            stalled = now - last_progress > stall_seconds
            proc.kill()
            proc.wait()
            return None, stalled
        time.sleep(poll)


def run_isolated(body: str, marker: str, timeout: int = 600,
                 attempts: int = 3) -> None:
    """Run ``PREAMBLE + body`` in a fresh python subprocess; assert it exits
    0 and prints ``marker``. A child wedged by the emulation deadlock (no
    CPU progress for 90 s) is killed and retried."""
    import tempfile

    env = dict(os.environ)
    env.pop("PYTEST_CURRENT_TEST", None)
    repo = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    for attempt in range(attempts):
        with tempfile.TemporaryFile("w+") as fh:
            rc, stalled = run_with_stall_watchdog(
                [sys.executable, "-c", PREAMBLE + body], env=env,
                stall_seconds=90, timeout=timeout, cwd=repo,
                stdout=fh, stderr=subprocess.STDOUT)
            fh.seek(0)
            text = fh.read()
        if rc == 0:
            assert marker in text, text[-2000:]
            return
        if not stalled:
            raise AssertionError(
                f"isolated scenario failed rc={rc}:\n{text[-3000:]}")
        print(f"isolated scenario wedged (attempt {attempt + 1}/{attempts}); "
              "retrying", file=sys.stderr)
    raise AssertionError(
        f"isolated scenario wedged {attempts} times (XLA CPU-emulation "
        "collective deadlock; see tests/unit/isolation.py)")
