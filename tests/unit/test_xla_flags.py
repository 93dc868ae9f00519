"""XLA_FLAGS hygiene: probing optional flags and sanitizing inherited ones.

The failure under test is the MULTICHIP dryrun crash: a parent environment
(or stale probe adoption) leaves flags in ``XLA_FLAGS`` that the pure-CPU
child's flag registry does not know, and ``parse_flags_from_env.cc`` F-aborts
the child with ``Unknown flag in XLA_FLAGS: ...`` before any user code runs.
These tests fake the probe subprocess so no real interpreter is spawned.
"""

import subprocess
from types import SimpleNamespace

import pytest

from deepspeed_tpu.utils import xla_flags as xf


class FakeRun:
    """Stand-in for subprocess.run that judges each probe by the flags the
    child would have parsed, and records every probe's flag set."""

    def __init__(self, rejected=(), transient=()):
        self.rejected = set(rejected)
        self.transient = set(transient)
        self.calls = []

    def __call__(self, argv, env=None, capture_output=True, timeout=None):
        flags = set((env or {}).get("XLA_FLAGS", "").split())
        self.calls.append(flags)
        if flags & self.transient:
            raise subprocess.TimeoutExpired(argv, timeout or 0)
        bad = flags & self.rejected
        if bad:
            marker = f"Unknown flag in XLA_FLAGS: {sorted(bad)[0]}"
            return SimpleNamespace(returncode=1, stdout=b"",
                                   stderr=marker.encode())
        return SimpleNamespace(returncode=0, stdout=b"", stderr=b"")


@pytest.fixture
def fake(monkeypatch):
    def install(**kw):
        runner = FakeRun(**kw)
        monkeypatch.setattr(xf.subprocess, "run", runner)
        return runner
    return install


class TestProbeExtraFlags:
    def test_clean_probe_adopts_all(self, fake):
        fake()
        got = xf.probe_extra_xla_flags(["--a=1", "--b=2"], use_cache=False)
        assert got == ["--a=1", "--b=2"]

    def test_rejection_bisects_to_the_bad_flag(self, fake):
        fake(rejected={"--bad=1"})
        got = xf.probe_extra_xla_flags(["--ok=1", "--bad=1"], use_cache=False)
        assert got == ["--ok=1"]

    def test_transient_default_drops(self, fake):
        fake(transient={"--flaky=1"})
        got = xf.probe_extra_xla_flags(["--flaky=1"], use_cache=False)
        assert got == []

    def test_transient_keep_transient_adopts(self, fake):
        fake(transient={"--flaky=1"})
        got = xf.probe_extra_xla_flags(["--flaky=1"], use_cache=False,
                                       keep_transient=True)
        assert got == ["--flaky=1"]

    def test_keep_transient_still_drops_definitive_rejections(self, fake):
        fake(rejected={"--bad=1"}, transient={"--flaky=1"})
        got = xf.probe_extra_xla_flags(
            ["--ok=1", "--bad=1", "--flaky=1"],
            use_cache=False, keep_transient=True)
        assert got == ["--ok=1", "--flaky=1"]


class TestSanitizeXlaFlags:
    def test_empty_is_empty(self, fake):
        runner = fake()
        assert xf.sanitize_xla_flags("", use_cache=False) == ""
        assert runner.calls == []  # no probe subprocess for nothing

    def test_wrong_platform_prefixes_dropped_without_probe(self, fake):
        runner = fake()
        got = xf.sanitize_xla_flags(
            "--xla_tpu_scoped_vmem_limit_kib=1024 --xla_gpu_autotune_level=2",
            target_platform="cpu", use_cache=False)
        assert got == ""
        # statically dropped: the probe child is never spawned for them
        assert runner.calls == []

    def test_unknown_inherited_flag_is_removed(self, fake):
        """The multichip dry-run crash: an inherited flag the CPU child's
        registry rejects must be filtered out, valid neighbors kept."""
        fake(rejected={"--xla_cpu_collective_call_warn_stuck_seconds=120"})
        got = xf.sanitize_xla_flags(
            "--xla_force_host_platform_device_count=8 "
            "--xla_cpu_collective_call_warn_stuck_seconds=120",
            target_platform="cpu", use_cache=False)
        assert got == "--xla_force_host_platform_device_count=8"

    def test_transient_probe_keeps_inherited_flags(self, fake):
        """Sanitizing must not silently strip the user's flags on a flaky
        probe — only a definitive rejection removes an inherited flag."""
        fake(transient={"--xla_cpu_enable_fast_math=true"})
        got = xf.sanitize_xla_flags(
            "--xla_cpu_enable_fast_math=true", target_platform="cpu",
            use_cache=False)
        assert got == "--xla_cpu_enable_fast_math=true"

    def test_order_preserved_and_tpu_target_keeps_tpu_flags(self, fake):
        fake()
        flags = ("--xla_tpu_scoped_vmem_limit_kib=1024 "
                 "--xla_force_host_platform_device_count=4")
        got = xf.sanitize_xla_flags(flags, target_platform="tpu",
                                    use_cache=False)
        assert got == flags


@pytest.mark.parametrize("sleep_s,ends", [(30, False), (0, True)],
                         ids=["past_the_limit", "inside_the_limit"])
def test_a_test_past_the_limit_ends_with_every_threads_stack(tmp_path, sleep_s,
                                                             ends):
    """``tests/conftest.py``'s limit on one test: past it the process writes
    every thread's stack and exits non-zero (a wedged collective sleeps in
    C++ just as ``time.sleep`` does); inside it nothing is seen."""
    import os
    import sys

    conftest = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "conftest.py")
    case = tmp_path / "test_sleeps.py"
    case.write_text(f"import time\ndef test_sleeps(): time.sleep({sleep_s})\n")
    driver = (
        "import importlib.util, sys, pytest\n"
        f"spec = importlib.util.spec_from_file_location('limit_conftest', {conftest!r})\n"
        "mod = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(mod)\n"
        "mod.TEST_LIMIT_S = 1\n"
        f"sys.exit(pytest.main([{str(case)!r}, '-q', '-p', 'no:cacheprovider'],"
        " plugins=[mod]))\n")
    done = subprocess.run([sys.executable, "-c", driver], capture_output=True,
                          text=True, timeout=120, cwd=tmp_path)
    if ends:
        assert done.returncode == 0, done.stdout + done.stderr
        assert "Timeout (" not in done.stderr
    else:
        assert done.returncode != 0
        assert "Timeout (0:00:01)!" in done.stderr
        assert "most recent call first" in done.stderr
        assert "test_sleeps.py" in done.stderr
