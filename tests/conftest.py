"""Test harness: simulate an 8-device mesh on CPU.

Mirrors the reference's distributed-in-one-box strategy
(``tests/unit/common.py DistributedExec`` spawns N processes + NCCL/gloo): here a
single process hosts N XLA CPU devices via
``--xla_force_host_platform_device_count`` and all collectives run for real
through the CPU backend. Must be set before jax initializes its backend.
"""

import faulthandler
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    _flags += " --xla_force_host_platform_device_count=8"

# On a 1-core box the 8 simulated device threads time-slice one CPU and XLA's
# collective-rendezvous watchdog can abort heavy tests.  The flags that relax
# it are NOT safe to hardcode: every library that links XLA parses XLA_FLAGS
# with its own registry and F-aborts on flags unknown to it.
# Probe in a subprocess and adopt only what this environment accepts.
from deepspeed_tpu.utils.xla_flags import probe_extra_xla_flags  # noqa: E402

_flags += "".join(
    " " + f
    for f in probe_extra_xla_flags(
        [
            "--xla_cpu_collective_call_warn_stuck_seconds=120",
            # a wedged collective must FAIL loudly (surfacing the emulation
            # artifact, see tests/unit/isolation.py) instead of eating the
            # whole suite window as a silent 0%-CPU hang
            "--xla_cpu_collective_call_terminate_timeout_seconds=600",
        ],
        base_flags=_flags,
    )
)
os.environ["XLA_FLAGS"] = _flags
os.environ["DSTPU_ACCELERATOR"] = "cpu"

# The tests run on the CPU whatever the caller's JAX_PLATFORMS says: the
# backend initializes lazily, so redirecting the config here still works.
import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")
# Synchronous CPU dispatch: with async dispatch, multiple in-flight 8-device
# collective programs time-slicing ONE core can wedge XLA's in-process
# collective rendezvous (observed as 0%-CPU hangs deep into long sessions).
# CPU-only knob; TPU async stepping is unaffected.
jax.config.update("jax_cpu_enable_async_dispatch", False)
# These tests are compile time: their shapes are tiny, and a step a test runs
# a few times costs seconds to compile. Unoptimized CPU code compiles a third
# faster (files PR 49 did not touch fell by 27-40% in the whole run, ROADMAP
# D9) and every test passes with its tolerance as it was; the TPU programs
# test_compile_tpu.py reads are byte for byte the same (the option reaches
# XLA:CPU's pipeline only).
jax.config.update("jax_disable_most_optimizations", True)

# NO persistent compilation cache for the CPU test mesh, and none is set
# here. This VM's CPUID advertises features the kernel doesn't enable (XLA's
# AOT loader warns "Compile machine features ... vs host machine features ...
# could lead to execution errors such as SIGILL"); cache-DESERIALIZED CPU
# collective programs then deadlock with every thread futex-parked (cold runs
# pass deterministically, cache-hit runs wedge). A machine that loads its own
# cache entries cleanly opts in the one way every entry point does: by
# setting JAX_COMPILATION_CACHE_DIR (deepspeed_tpu/utils/compile_cache.py).

import pytest  # noqa: E402


# ---------------------------------------------------------------- sharding
# A FULL-SUITE invocation (`pytest tests/ ...`) transparently runs as a few
# sequential fresh-process shards. Reason: XLA's emulated-CPU collective
# executor can deadlock (all threads futex-parked, 0% CPU, no watchdog fire)
# after enough DISTINCT multi-device programs have run in one process on this
# 1-core box. Empirically, file subsets of ~1/3 of the suite pass reliably
# while single-process full runs wedge at probabilistic points (three round-4
# runs: the NVMe step, the autotuner sweep, ...). Sharding keeps the
# advertised `python -m pytest tests/ -x -q` entry point working; targeted
# invocations (specific files/tests) are never sharded.
_N_SHARDS = 4


def pytest_cmdline_main(config):
    if os.environ.get("DSTPU_SUITE_SHARD"):
        return None  # we ARE a shard child: run normally
    args = list(config.invocation_params.args)
    positional = [a for a in args if not a.startswith("-")]
    tests_dir = os.path.dirname(os.path.abspath(__file__))
    # shard only the full-suite spelling: `pytest tests/` (or the repo root)
    roots = {tests_dir, os.path.dirname(tests_dir)}
    if not positional or not all(
            os.path.abspath(p.rstrip("/")) in roots for p in positional):
        return None

    import glob
    import importlib.util

    spec = importlib.util.spec_from_file_location(
        "dstpu_test_isolation", os.path.join(tests_dir, "unit", "isolation.py"))
    isolation = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(isolation)

    files = sorted(glob.glob(os.path.join(tests_dir, "unit", "test_*.py")))
    if len(files) < _N_SHARDS + 1:
        return None
    flags = [a for a in args if a.startswith("-")]
    # round-robin by position: spreads the heavy engine files across shards
    shards = [files[i::_N_SHARDS] for i in range(_N_SHARDS)]
    env = dict(os.environ)
    env["DSTPU_SUITE_SHARD"] = "1"
    rc = 0
    for i, shard in enumerate(shards):
        for attempt in range(3):
            print(f"\n=== suite shard {i + 1}/{len(shards)} "
                  f"({len(shard)} files"
                  + (f", retry {attempt}" if attempt else "") + ") ===",
                  flush=True)
            shard_rc, stalled = isolation.run_with_stall_watchdog(
                [sys.executable, "-m", "pytest", *flags, *shard],
                env=env, stall_seconds=180, timeout=1500)
            if shard_rc is not None:
                rc = max(rc, shard_rc)
                break
            print(f"=== shard {i + 1} "
                  + ("stalled (emulation deadlock, see tests/unit/"
                     "isolation.py); retrying" if stalled else "timed out"),
                  flush=True)
        else:
            rc = max(rc, 1)
        if rc and ("-x" in flags or "--exitfirst" in flags):
            break
    return rc


# One test may take this long and no longer. A wedged emulated collective
# parks every thread in C++ (0% CPU), where no signal handler runs;
# faulthandler's watchdog thread still does: it writes every thread's stack
# and ends the process, xdist reports the test as failed on a crashed worker
# and starts another. Over three times the slowest test of the slowest whole
# run under `-n 6` (69 s; ROADMAP D9).
TEST_LIMIT_S = 240
_STDERR_FD = pytest.StashKey[int]()


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: over a minute of CPU; the tier-1 command leaves it out")
    # the process's own stderr: while a test runs, pytest's capture has put a
    # temporary file on descriptor 2 (it is suspended during configure)
    config.stash[_STDERR_FD] = os.dup(sys.__stderr__.fileno())


@pytest.fixture(autouse=True)
def _limit_each_test(request):
    faulthandler.dump_traceback_later(
        TEST_LIMIT_S, exit=True, file=request.config.stash[_STDERR_FD])
    yield
    faulthandler.cancel_dump_traceback_later()


@pytest.fixture(autouse=True)
def _reset_global_state():
    """Each test builds its own topology; reset the module-level singletons."""
    yield
    from deepspeed_tpu.comm.topology import reset_topology
    from deepspeed_tpu.utils.faults import get_fault_injector
    from deepspeed_tpu.telemetry import TELEMETRY
    from deepspeed_tpu.utils.comms_logging import COMMS_LOGGER

    reset_topology()
    COMMS_LOGGER.reset()
    COMMS_LOGGER.enabled = False
    TELEMETRY.reset()
    get_fault_injector().reset()


# A process may hold 65,530 memory mappings (``vm.max_map_count``) and every
# compiled program keeps a few: a module of a serving family leaves ~10,000
# behind in its worker's caches, and a worker that has run some sixty modules
# met the limit near the end of the suite: the compiler's next ``mmap`` failed
# and the worker died inside ``backend_compile`` ("Fatal Python error:
# Aborted" / "Segmentation fault", a `node down` and one lost test, in
# whatever module came next: twice of two runs once PR 60's modules had
# joined). Past this many mappings a module's end drops jax's caches; what a
# later module shares with an earlier one (the kernels' wrappers) compiles
# once more, seconds a worker.
MAPS_HIGH_WATER = 40_000


@pytest.fixture(autouse=True, scope="module")
def _release_compiled_programs():
    yield
    try:
        with open("/proc/self/maps") as f:
            held = sum(1 for _ in f)
    except OSError:   # no procfs: nothing to count, nothing to do
        return
    if held > MAPS_HIGH_WATER:
        import gc

        jax.clear_caches()
        gc.collect()


@pytest.fixture
def mesh8():
    """A data=8 topology over the simulated devices."""
    from deepspeed_tpu.comm.comm import init_distributed
    from deepspeed_tpu.config.config import MeshConfig

    return init_distributed(MeshConfig(data=8))
