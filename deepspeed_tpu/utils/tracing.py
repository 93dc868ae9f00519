"""Profiler tracing hooks (the reference's nvtx ranges, TPU-native).

The role of ``deepspeed/utils/nvtx.py`` and the accelerator's
``range_push/pop``, expressed with ``jax.profiler``: host-side spans are
``TraceAnnotation``s (:func:`span`, :func:`instant`), every training step is
a ``StepTraceAnnotation`` whoever opened the profiler session, and whole
training windows are captured with ``start_trace``/``stop_trace`` driven by
the engine's ``tracing`` config (viewable in TensorBoard/XProf/Perfetto).
"""

from __future__ import annotations

import contextlib
import time

import jax


def span(name: str, **args):
    """A host span on the profiler's clock: ``with span("engine/stage"):``.
    Recorded only while a profiler session is open (a capture of
    :class:`StepTracer`, ``/debug/profile``, the benchmark's traced slice);
    with none the annotation is built and dropped. ``args`` become the
    event's arguments in the trace."""
    return jax.profiler.TraceAnnotation(name, **args)


@contextlib.contextmanager
def phase(name: str, **args):
    """A :func:`span` that is ALSO put down in the compile watch's start-up
    log (``telemetry.snapshot()["startup"]["phases"]``: name, thread, begin
    and end on ``time.perf_counter()``, ``args``) whether or not a profiler
    session is open. For work a process does ONCE (an engine's construction,
    its warm-up, a server's assembly): it takes a lock and keeps a record, so
    no step path calls it. The program builds that fall inside it are in the
    same log (``telemetry/compile_watch.py``) and are told from the rest of
    the phase by their times."""
    from deepspeed_tpu.telemetry.compile_watch import WATCH

    WATCH.install()
    t0 = time.perf_counter()
    try:
        with span(name, **args):
            yield
    finally:
        WATCH.note_phase(name, t0, time.perf_counter(), args)


def instant(name: str, **args) -> None:
    """A span of no length: an event whose arguments carry the reading."""
    with jax.profiler.TraceAnnotation(name, **args):
        pass


class StepTracer:
    """Drives a bounded ``jax.profiler`` capture window over training steps
    (config ``tracing``: start at ``start_step``, run ``num_steps``, write to
    ``trace_dir``), annotating each step for the trace viewer's step view."""

    def __init__(self, cfg, sync_fn=None):
        self.cfg = cfg
        # called before stop_trace: block on in-flight device work so the
        # capture contains the traced steps' device activity (the engine
        # pipelines steps without per-step sync)
        self.sync_fn = sync_fn
        self._active = False
        self._done = False
        self._started_at = 0
        self._step_ann = None
        if cfg.enabled:
            # the capture is only written at stop_trace; guarantee it lands
            # even if the run ends inside the window
            import atexit

            atexit.register(self.close)

    def before_step(self, step: int) -> None:
        # a step that raised never reached after_step: exit the stale
        # annotation before opening a new one
        self._exit_step_ann()
        # >= so a resumed run (global step already past start_step) still
        # captures its first window
        if (self.cfg.enabled and not self._done and not self._active
                and step >= self.cfg.start_step):
            try:
                jax.profiler.start_trace(self.cfg.trace_dir)
            except Exception as e:
                from deepspeed_tpu.utils.logging import logger

                logger.warning(f"StepTracer: start_trace failed ({e}); "
                               "capture disabled for this run")
                self._finish()
            else:
                self._active = True
                self._started_at = step
        # on every step, not only inside a capture of our own: a session
        # someone else opened (the benchmark, /debug/profile) sees the steps
        # too, and with no session the annotation records nothing
        self._step_ann = jax.profiler.StepTraceAnnotation(
            "train_step", step_num=step)
        self._step_ann.__enter__()

    def after_step(self, step: int) -> None:
        self._exit_step_ann()
        if self._active and step >= self._started_at + self.cfg.num_steps - 1:
            self.stop_trace()
            self._finish()

    def stop_trace(self) -> None:
        """End the capture window if one is open. Idempotent and
        exception-safe: a failed step inside the window must not leave an
        unmatched ``jax.profiler.start_trace`` wedging the next capture."""
        self._exit_step_ann()
        if not self._active:
            return
        # flip first: even if the sync or the profiler raises, we never
        # attempt a second stop on the same window
        self._active = False
        try:
            if self.sync_fn is not None:
                self.sync_fn()
        except Exception:
            # device work from the failed step may be poisoned; still try to
            # finalize the capture file
            pass
        try:
            jax.profiler.stop_trace()
        except Exception as e:
            from deepspeed_tpu.utils.logging import logger

            logger.warning(f"StepTracer: stop_trace failed ({e}); "
                           "capture for this window is lost")

    def _exit_step_ann(self) -> None:
        if self._step_ann is not None:
            ann, self._step_ann = self._step_ann, None
            try:
                ann.__exit__(None, None, None)
            except Exception:
                pass

    def _finish(self) -> None:
        """Capture complete: drop the engine-capturing sync closure and the
        atexit registration so the tracer doesn't pin the engine (and its
        device arrays) for process lifetime."""
        self._done = True
        self.sync_fn = None
        import atexit

        try:
            atexit.unregister(self.close)
        except Exception:
            pass

    def close(self) -> None:
        self.stop_trace()
        self._finish()
