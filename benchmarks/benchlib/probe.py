"""What an entry adapter's wrappers report to: host spans, the window's
marks, the first rounds' inputs and states for ``correct``, the profiler's
slice in a traced run, and the count of programs built inside the window.

Nothing here knows a model or an entry point: the adapter hands over two
functions that read the optimizer state and the weights off its learner.
"""

from __future__ import annotations

import contextlib
import time

import numpy as np

from benchlib.trace import ANNOTATION_PREFIX, TRACED_WINDOW

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class StopWindow(Exception):
    """Raised by the dispatch wrapper to leave the entry point's loop."""


def now() -> float:
    return time.perf_counter()


class Probe:
    def __init__(self, t_process, seconds, warmup_rounds, samples_per_round,
                 trace, trace_dir, trace_rounds, keep_rounds, opt_state,
                 weights, trace_skip=5):
        self.t_process = t_process
        self.seconds = float(seconds)
        self.warmup_rounds = warmup_rounds
        self.samples_per_round = samples_per_round
        self.trace = trace
        self.trace_dir = trace_dir
        self.trace_rounds = trace_rounds
        self.trace_skip = trace_skip
        self.keep_rounds = keep_rounds
        self._opt_state, self._weights = opt_state, weights
        if warmup_rounds <= keep_rounds:
            raise ValueError("the warm-up has to outlast the rounds that "
                             "correct follows")
        self.learner = None
        self.spec = None
        self.w0 = None
        self.dispatched = 0
        self.spans = {}            # name -> [(t0, t1)]
        self.boundary_waits = []   # data_wait intervals that held an epoch end
        self.push_returns = []
        self.finalized = []        # per-round metric dicts, in round order
        self.batches = []          # first rounds, host copies
        self.batch_shapes = None
        self.opt_after_1 = None
        self.w_after = None
        self.t_start = self.t_end = None
        self.compiles = []         # host times of programs built
        self.marks = {}            # set-up phase name -> host time
        self._idle_since = None
        self._boundary = False
        self.trace_t0 = self.trace_t1 = None
        self.trace_round0 = self.trace_round1 = None
        self._trace_span = self._wait_ann = None
        self.aborted = None
        self.memory_stats = None

    # ------------------------------------------------------------ spans
    def mark(self, name):
        self.marks.setdefault(name, now())

    @contextlib.contextmanager
    def span(self, name):
        ann = self._annotation(name)
        t0 = now()
        try:
            yield
        finally:
            t1 = now()
            if ann is not None:
                ann.__exit__(None, None, None)
            self.spans.setdefault(name, []).append((t0, t1))
            if name == "eval":
                self._boundary = True

    def _annotation(self, name):
        if self.trace_t0 is None or self.trace_t1 is not None:
            return None
        import jax
        ann = jax.profiler.TraceAnnotation(ANNOTATION_PREFIX + name)
        ann.__enter__()
        return ann

    @contextlib.contextmanager
    def compile_events(self):
        import jax

        def on_duration(event, duration, **kw):
            if event == COMPILE_EVENT:
                self.compiles.append(now())

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        try:
            yield
        finally:
            jax.monitoring.unregister_event_duration_listener(on_duration)

    # --------------------------------------------------------- the loop
    def before_dispatch(self, ids, cols, mask):
        import jax
        r = self.dispatched
        t = now()
        if self._idle_since is not None:
            wait = (self._idle_since, t)
            (self.boundary_waits if self._boundary
             else self.spans.setdefault("data_wait", [])).append(wait)
            self._idle_since, self._boundary = None, False
            if self._wait_ann is not None:
                self._wait_ann.__exit__(None, None, None)
                self._wait_ann = None
        if r == 0:
            self.mark("first_dispatch")
        if r < self.keep_rounds:
            self.batches.append(jax.device_get((ids, cols, mask)))
            self.batch_shapes = (
                (tuple(np.shape(ids)),),
                tuple((tuple(c.shape), c.dtype) for c in cols),
                (tuple(np.shape(mask)),))
        if r == 1:
            self.opt_after_1 = np.asarray(jax.device_get(
                self._opt_state(self.learner)))
        if r == self.keep_rounds:
            self.w_after = np.asarray(jax.device_get(
                self._weights(self.learner)))
        if r == self.warmup_rounds:
            jax.block_until_ready(self.learner.state)
            self.t_start = now()
        elif self.t_start is not None:
            self._in_window(r, now())
        self.dispatched += 1

    def _in_window(self, r, t):
        import jax
        if self.trace and self.trace_t1 is None:
            first = self.warmup_rounds + self.trace_skip
            if r == first:
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0
                opts.host_tracer_level = 1
                jax.profiler.start_trace(self.trace_dir,
                                         profiler_options=opts)
                self._trace_span = jax.profiler.TraceAnnotation(
                    TRACED_WINDOW)
                self._trace_span.__enter__()
                self.trace_t0, self.trace_round0 = now(), r
            elif r == first + self.trace_rounds:
                self._stop_trace(r)
        if t - self.t_start >= self.seconds and (
                not self.trace or self.trace_t1 is not None
                or self.trace_t0 is None):
            raise StopWindow

    def _stop_trace(self, r):
        import jax
        self.trace_t1, self.trace_round1 = now(), r
        self._trace_span.__exit__(None, None, None)
        self._trace_span = None
        jax.profiler.stop_trace()

    def after_push(self, out):
        t = now()
        self.push_returns.append(t)
        if out is not None:
            self.finalized.append(out)
        self._idle_since = t
        if self.trace_t0 is not None and self.trace_t1 is None:
            import jax
            self._wait_ann = jax.profiler.TraceAnnotation(
                ANNOTATION_PREFIX + "data_wait")
            self._wait_ann.__enter__()

    def after_flush(self, out):
        if out is not None:
            self.finalized.append(out)
        self._boundary = True

    def close_window(self):
        import jax
        if self.t_start is None:
            raise RuntimeError("the window never opened")
        jax.block_until_ready(self.learner.state)
        self.t_end = now()
        if self.trace_t0 is not None and self.trace_t1 is None:
            self._stop_trace(self.dispatched)
        self.aborted = bool(jax.device_get(self.learner.state.aborted))
        dev = jax.devices()[0]
        self.memory_stats = dev.memory_stats() or {}

    # ---------------------------------------------------------- results
    @property
    def window_rounds(self):
        return self.dispatched - self.warmup_rounds

    @property
    def peak_bytes(self):
        """Peak device memory: the peak of live buffers plus the peak the
        runtime reserved for programs' temporaries. On this TPU runtime the
        two are disjoint, and the first alone leaves out the round program's
        4.7 GB of activations (PERF.md, PR 25)."""
        stats = self.memory_stats
        if stats.get("peak_bytes_in_use") is None:
            return None
        return stats["peak_bytes_in_use"] + stats.get("peak_bytes_reserved",
                                                      0)

    def in_window(self, name):
        """Spans of ``name`` that began inside the window."""
        return [(a, b) for a, b in self.spans.get(name, [])
                if self.t_start <= a <= self.t_end]

    def setup_phases(self):
        """Seconds of set-up up to each mark the adapter left, in order, then
        to the window's start."""
        marks = sorted(self.marks.items(), key=lambda kv: kv[1])
        out, last = {}, self.t_process
        for name, t in marks + [("window_start", self.t_start)]:
            out[name], last = t - last, t
        return out

    def compiles_in_window(self):
        return sum(1 for t in self.compiles
                   if self.t_start <= t <= self.t_end)
