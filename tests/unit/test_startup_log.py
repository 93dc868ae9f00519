"""The compile watch (``telemetry/compile_watch.py``): one set of
``jax.monitoring`` listeners a process, a record for every outermost program
build with the program's and the thread's name, ``utils/tracing.phase``, and
that none of it changes what is traced."""

import hashlib
import os
import threading

import jax
import jax.numpy as jnp
import pytest
from jax._src import monitoring as jax_monitoring

from deepspeed_tpu import telemetry
from deepspeed_tpu.inference.ragged import RaggedConfig, RaggedInferenceEngine
from deepspeed_tpu.models import llama
from deepspeed_tpu.telemetry import compile_watch
from deepspeed_tpu.telemetry.compile_watch import WATCH
from deepspeed_tpu.utils.tracing import phase, span

CFG = llama.LlamaConfig(
    vocab_size=97, hidden_size=32, intermediate_size=64,
    num_layers=2, num_heads=4, num_kv_heads=2, max_seq_len=128,
)
RCFG = RaggedConfig(max_tokens_per_step=16, max_seqs=4, block_size=4,
                    num_blocks=49, max_blocks_per_seq=16, prefill_tile=4)


def _engine():
    return RaggedInferenceEngine(lambda ctx: llama.build(CFG, ctx=ctx), RCFG,
                                 dtype=jnp.float32, seed=0)


def _ours(listeners) -> int:
    return sum(1 for cb in listeners if getattr(cb, "__self__", None) is WATCH)


def _listener_counts() -> tuple:
    return (_ours(jax_monitoring._scalar_listeners),
            _ours(jax_monitoring._event_duration_secs_listeners),
            _ours(jax_monitoring._event_listeners))


@pytest.fixture()
def watch():
    WATCH.install()
    WATCH.reset()
    yield WATCH
    WATCH.install()   # a test that uninstalled it


def _builds(name=None):
    builds = WATCH.snapshot()["builds"]
    return [b for b in builds if name is None or b["program"] == name]


def test_a_build_keeps_the_name_jax_gives_it_and_its_stages(watch):
    def startup_named(x):
        return x * 2 + 1

    jax.jit(startup_named)(jnp.ones(3))
    (b,) = _builds("jit_startup_named")
    assert b["thread"] == threading.current_thread().name
    assert b["compiled"] and b["t1"] > b["t0"]
    assert b["trace_s"] > 0 and b["lower_s"] > 0 and b["compile_s"] > 0
    assert b["cache"] == "unasked" and not b["written"]  # no cache directory
    assert b["retrieval_s"] == 0 and b["inner_builds"] == 0


def test_a_nested_jits_trace_is_its_outer_builds_and_nobody_elses(watch):
    @jax.jit
    def startup_inner(x):
        return x * 3

    def startup_outer(x):
        return startup_inner(x) + startup_inner(x + 1)

    jax.jit(startup_outer)(jnp.ones(5))
    (outer,) = _builds("jit_startup_outer")
    assert outer["inner_traces"] >= 1
    assert not _builds("jit_startup_inner") and not _builds("startup_inner")
    # the inner function, called by itself, is a build of its own
    startup_inner(jnp.ones(7))
    (inner,) = _builds("jit_startup_inner")
    assert inner["inner_traces"] >= 1   # its multiply
    assert _builds("jit_startup_outer") == [outer]


def test_a_second_dispatch_of_a_built_program_adds_no_record(watch):
    f = jax.jit(lambda x: x - 4)
    f(jnp.ones(6))
    n = WATCH.snapshot()["builds_total"]
    f(jnp.ones(6))
    assert WATCH.snapshot()["builds_total"] == n


def test_a_trace_no_compile_follows_does_not_swallow_the_next_build(watch):
    def startup_lowered(x):
        return x + 5

    def startup_built(x):
        return x * 7

    lowered = jax.jit(startup_lowered).lower(jnp.ones(4))
    jax.eval_shape(startup_built, jnp.ones(4))
    jax.jit(startup_built)(jnp.ones(4))
    (only_lowered,) = _builds("jit_startup_lowered")
    assert not only_lowered["compiled"] and only_lowered["compile_s"] == 0
    assert only_lowered["trace_s"] > 0 and only_lowered["lower_s"] > 0
    (traced,) = _builds("startup_built")        # eval_shape: a trace alone
    assert not traced["compiled"] and traced["lower_s"] == 0
    (built,) = _builds("jit_startup_built")
    assert built["compiled"] and built["t0"] >= traced["t1"]
    assert (built["trace_s"] + built["lower_s"] + built["compile_s"]
            <= built["t1"] - built["t0"] + 1e-3)     # its own stages only
    lowered.compile()   # the compile alone is a build of that name
    assert [b["compiled"] for b in _builds("jit_startup_lowered")] == [False, True]


def test_asked_hit_and_written_follow_the_persistent_cache(watch, tmp_path):
    """jax fires ``cache_misses`` only where it writes an entry: a build that
    asked and did not hit is a miss whether or not it was written; the
    cold-cache probe's counter counts the written ones."""
    from jax._src import compilation_cache

    def program(shift):
        def startup_cached(x):
            return jnp.sin(x) * 13 + shift
        return jax.jit(startup_cached)

    x = jnp.ones(3)     # built here, before anybody can be asked
    keep = {k: getattr(jax.config, k) for k in (
        "jax_enable_compilation_cache",
        "jax_compilation_cache_dir",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    try:
        # ``test_compile_tpu.py``'s module fixture turns the cache off while
        # its tests run, and under ``--dist load`` one of them may be this
        # worker's neighbour
        jax.config.update("jax_enable_compilation_cache", True)
        jax.config.update("jax_compilation_cache_dir", str(tmp_path))
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 3600.0)
        compilation_cache.reset_cache()
        program(1.0)(x)     # asked, missed, under the threshold
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        program(2.0)(x)     # asked, missed, written
        program(2.0)(x)     # a new trace of the same program: hit
    finally:
        for k, v in keep.items():
            jax.config.update(k, v)
        compilation_cache.reset_cache()
    a, b, c = _builds("jit_startup_cached")
    assert (a["cache"], a["written"]) == ("miss", False)
    assert (b["cache"], b["written"]) == ("miss", True)
    assert (c["cache"], c["written"]) == ("hit", False)
    assert c["retrieval_s"] > 0 and a["retrieval_s"] == b["retrieval_s"] == 0
    snap = WATCH.snapshot()
    assert (snap["cache_hits"], snap["cache_misses"]) == (1, 2)
    assert WATCH.cache_writes() == snap["cache_writes"] == 1


def test_a_build_carries_the_name_of_the_thread_that_made_it(watch):
    def startup_threaded(x):
        return x * 11

    t = threading.Thread(target=lambda: jax.jit(startup_threaded)(jnp.ones(3)),
                         name="ragged-compile_0")
    t.start()
    t.join()
    (b,) = _builds("jit_startup_threaded")
    assert b["thread"] == "ragged-compile_0" and b["compiled"]


def test_the_log_is_bounded_and_says_that_it_overflowed(watch, monkeypatch):
    monkeypatch.setattr(compile_watch, "LOG_BOUND", 3)
    for i in range(5):
        jax.jit(lambda x, i=i: x + i)(jnp.ones(2 + i))
    snap = WATCH.snapshot()
    assert len(snap["builds"]) == 3 and snap["overflowed"]
    assert snap["builds_total"] >= 5
    assert snap["builds_dropped"] == snap["builds_total"] - 3


def test_phase_records_without_a_profiler_session_and_span_does_not(watch):
    with span("engine/stage", rows=3):
        pass
    assert WATCH.snapshot()["phases"] == []
    with phase("engine/init", slots=4):
        jax.jit(lambda x: x / 3)(jnp.ones(8))
    (p,) = WATCH.snapshot()["phases"]
    assert p["name"] == "engine/init" and p["args"] == {"slots": 4}
    assert p["thread"] == threading.current_thread().name
    inside = [b for b in _builds() if p["t0"] <= b["t0"] and b["t1"] <= p["t1"]]
    assert any(b["program"] == "jit_<lambda>" for b in inside)
    with pytest.raises(ValueError):     # a phase that fails is put down too
        with phase("engine/warmup"):
            raise ValueError("refused")
    assert [q["name"] for q in WATCH.snapshot()["phases"]] == [
        "engine/init", "engine/warmup"]


@pytest.mark.parametrize("enabled", [False, True], ids=["telemetry-off",
                                                        "telemetry-on"])
def test_two_engines_leave_one_set_of_listeners(watch, enabled):
    """However many engines, and whether or not ``telemetry`` feeds a
    registry; the engines' own phases are in the log either way."""
    if enabled:
        telemetry.configure(enabled=True)
    engines = [_engine(), _engine()]
    for eng in engines:
        eng.warmup()
    if enabled:
        telemetry.configure(enabled=True)   # a reconfigure re-attaches
    assert _listener_counts() == (1, 1, 1)
    assert (telemetry.get_telemetry().compile_watch is WATCH) == enabled
    names = [p["name"] for p in telemetry.snapshot()["startup"]["phases"]]
    assert names == ["engine/init", "engine/init", "engine/warmup",
                     "engine/warmup"]
    page = telemetry.get_telemetry().registry.render_prometheus()
    assert ('startup_phase_seconds{phase="engine/init"}' in page) == enabled


def test_the_builds_of_a_served_request_are_on_the_metrics_page(watch):
    telemetry.configure(enabled=True)
    eng = _engine()
    eng.put("a", [5, 6, 7, 8, 9], max_new_tokens=2)
    eng.generate_all()
    steps = [b for b in _builds() if b["program"].startswith("jit_ragged_step_")]
    assert steps and all(b["compiled"] and b["inner_traces"] > 0 for b in steps)
    page = telemetry.get_telemetry().registry.render_prometheus()
    for stage in ("trace", "lower", "compile", "retrieve"):
        assert (f'program_build_seconds{{program="{steps[0]["program"]}",'
                f'stage="{stage}"}}') in page
    assert 'jit_cache_misses_total{source="monitoring"}' in page
    # no cache directory: nobody was asked, so neither counter moved
    assert "persistent_cache_misses_total" not in page
    snap = telemetry.snapshot()["startup"]
    assert snap["cache_hits"] == snap["cache_misses"] == snap["cache_writes"] == 0


def test_uninstall_takes_exactly_our_listeners(watch):
    other = lambda event, **kw: None  # noqa: E731
    jax.monitoring.register_event_listener(other)
    try:
        WATCH.uninstall()
        assert _listener_counts() == (0, 0, 0)
        assert other in jax_monitoring._event_listeners
        assert WATCH.snapshot() is None
        assert telemetry.snapshot()["startup"] is None
        WATCH.install().install()
        assert _listener_counts() == (1, 1, 1)
    finally:
        jax.monitoring.unregister_event_listener(other)


def _lowered_shas(eng) -> dict:
    """sha256 of the lowered text of a step program and of both row updaters,
    each traced here for the first time."""
    def abstract(tree):
        return jax.tree_util.tree_map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)

    t, nd, nt, w = key = eng._step_zoo()[-2]
    fixed = (abstract(eng.params), abstract(eng.cache),
             abstract(eng._dev_state), abstract(eng._bt_dev))
    staged = jax.ShapeDtypeStruct((4 * t + 3 * max(nt, 1),), jnp.int32)
    rows = 2
    texts = {
        "step": eng._build_dev_step(*key, False, False, False).lower(
            *fixed, staged, abstract(eng._sample_root)).as_text(),
        "bt_rows": eng._bt_row_jit.lower(
            abstract(eng._bt_dev), jax.ShapeDtypeStruct((rows,), jnp.int32),
            jax.ShapeDtypeStruct((rows, eng.cfg.max_blocks_per_seq),
                                 jnp.int32)).as_text(),
    }
    assert "ragged_step_d" in texts["step"]
    return {k: hashlib.sha256(v.encode()).hexdigest() for k, v in texts.items()}


def test_the_programs_are_the_same_with_and_without_the_watch(watch):
    """What refused PR 23 was instrumentation that changed what is traced:
    the lowered text of a step program and of a row updater is the same
    with the watch installed as with every listener unregistered."""
    watched = _lowered_shas(_engine())
    assert any(b["program"].startswith("jit_ragged_step_d")
               and not b["compiled"] for b in _builds())
    WATCH.uninstall()
    assert _listener_counts() == (0, 0, 0)
    bare = _lowered_shas(_engine())
    assert bare == watched


REPO = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _sources(*tops):
    for top in tops:
        for folder, _, files in os.walk(os.path.join(REPO, top)):
            for name in files:
                if name.endswith((".py", ".md")):
                    path = os.path.join(folder, name)
                    with open(path, encoding="utf-8") as f:
                        yield os.path.relpath(path, REPO), f.read()


def test_the_package_registers_listeners_in_one_file():
    found = {path for path, text in _sources("deepspeed_tpu")
             if "register_event" in text or "register_scalar" in text}
    assert found == {"deepspeed_tpu/telemetry/compile_watch.py"}


@pytest.mark.parametrize("gone", [
    "note_" + "cache_size", "note_program_" + "cache_size",
    "cache_size" + "_delta", "_persistent_cache_" + "miss_counter"])
def test_what_was_removed_is_found_nowhere(gone):
    assert [path for path, text in _sources("deepspeed_tpu", "docs", "tests",
                                            "benchmark")
            if gone in text] == []
