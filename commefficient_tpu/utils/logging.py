"""Console/TSV loggers and wall-clock timer (reference utils.py:14-99)."""

from __future__ import annotations

import contextlib
import os
import time
from datetime import datetime


class Logger:
    def __init__(self, verbose: bool = True):
        self.verbose = verbose

    def debug(self, *args, **kwargs):
        if self.verbose:
            print(*args, **kwargs)

    def info(self, *args, **kwargs):
        print(*args, **kwargs)


class TableLogger:
    """Fixed-width column table; header printed on first append."""

    def __init__(self):
        self.keys = None

    def append(self, output: dict):
        if self.keys is None:
            self.keys = list(output.keys())
            print(*(f"{k:>12s}" for k in self.keys))
        filtered = [output.get(k, "") for k in self.keys]
        print(*(f"{v:12.4f}" if isinstance(v, float) else f"{str(v):>12s}"
                for v in filtered))


class TSVLogger:
    def __init__(self):
        self.log = ["epoch\thours\ttop1Accuracy"]

    def append(self, output: dict):
        epoch = output.get("epoch", -1)
        hours = output.get("total_time", 0) / 3600
        acc = output.get("test_acc", 0) * 100
        self.log.append(f"{epoch}\t{hours:.8f}\t{acc:.2f}")

    def __str__(self):
        return "\n".join(self.log)


class ScalarWriter:
    """Structured scalar export for ``--tensorboard`` (reference
    cv_train.py:150-158, gpt2_train.py:233-235).

    Uses torch.utils.tensorboard's SummaryWriter when the tensorboard
    package is importable; otherwise falls back to an append-only
    ``scalars.tsv`` (step, tag, value) in the same log dir — the data is
    identical, only the container differs."""

    def __init__(self, logdir: str):
        os.makedirs(logdir, exist_ok=True)
        self.logdir = logdir
        self._tb = None
        self._file = None
        try:
            from torch.utils.tensorboard import SummaryWriter
            self._tb = SummaryWriter(log_dir=logdir)
        except Exception:
            self._file = open(os.path.join(logdir, "scalars.tsv"), "a")

    def add_scalar(self, tag: str, value, step: int):
        if self._tb is not None:
            self._tb.add_scalar(tag, value, step)
        else:
            self._file.write(f"{step}\t{tag}\t{float(value)}\n")
            self._file.flush()  # scalars trickle in; survive a killed run

    def close(self):
        if self._tb is not None:
            self._tb.flush()
            self._tb.close()
        else:
            self._file.close()


class Timer:
    def __init__(self, synch=None):
        self.synch = synch or (lambda: None)
        self.times = [time.perf_counter()]
        self.total_time = 0.0

    def __call__(self, include_in_total: bool = True):
        self.synch()
        self.times.append(time.perf_counter())
        delta_t = self.times[-1] - self.times[-2]
        if include_in_total:
            self.total_time += delta_t
        return delta_t


@contextlib.contextmanager
def profile_ctx(trace_dir):
    """jax.profiler trace context, or a no-op when ``trace_dir`` is falsy
    (the TPU analog of the reference's cProfile hooks, SURVEY.md §5). On
    exit the program's own spans, counters and round stamps
    (utils/tracing.py) are left in ``trace_dir/spans.json``, beside the
    device trace that holds the same spans as ``fed:*`` annotations. The
    whole session is one ``fed:profile`` annotation, and ``spans.json``'s
    ``anchors["fed:profile"]`` is the ``perf_counter_ns`` at which it
    opened: its start on the trace minus that anchor maps every stamp of
    ``spans.json`` onto the trace's clock."""
    if not trace_dir:
        yield
        return
    import jax

    from commefficient_tpu.utils import tracing
    name = tracing.SPAN_PREFIX + "profile"
    try:
        with jax.profiler.trace(trace_dir), \
                jax.profiler.TraceAnnotation(name):
            tracing.anchor(name)
            yield
    finally:
        tracing.write(trace_dir)


def make_logdir(cfg) -> str:
    """runs/<timestamp>_<workers>/<clients>_<mode> (ref utils.py:51-64)."""
    current_time = datetime.now().strftime("%b%d_%H-%M-%S")
    run_name = f"{current_time}_{cfg.num_workers}"
    detail = f"{cfg.num_clients}_{cfg.mode}"
    return os.path.join("runs", run_name, detail)
