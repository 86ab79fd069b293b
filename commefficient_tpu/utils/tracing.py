"""The program's one tracing system: host spans, counters, phase scopes.

Always on, bounded, in memory; no flag, no environment variable.

* ``span(name)`` times a piece of host work on ``time.perf_counter_ns``
  and, while it is open, holds a ``jax.profiler.TraceAnnotation`` named
  ``fed:<name>``, so under ``--profile DIR`` the span sits on the
  profiler's clock beside the device operations. Durations add up in the
  totals of the current *round*: everything between one ``round_mark`` and
  the next shares that round's index.
* ``count(name, n)`` keeps monotone counters, each with the host time of its
  last change, and the growth of each inside the current round.
* ``phase(name)`` is the scope for code inside ``jit``; ``op_phases`` reads
  the scopes back from a compiled program, one phase per instruction.
  ``layer(name)`` is a second level beneath it, for the parts of a model
  (``layer:ssm_scan`` inside ``phase:client_grad``); ``op_layers`` reads it.
* ``compile_counters()`` feeds ``compile.*`` from JAX's own monitoring events.
* The round's timeline, on the same clock: ``round_mark`` stamps the
  dispatch (``t_ns``); ``round_enqueued(out)``, called by the dispatch site
  as soon as the jitted call returns, stamps ``t_enq_ns`` (the round is in
  the device's queue) and hands ``out``, one small output of the round, to
  the *waiter*: one daemon thread, started by the first ``round_mark``,
  that blocks on the outputs in dispatch order (the device's order; the
  wait releases the GIL), stamps ``t_done_ns`` when the device has
  finished the round, and drops the reference. The dispatching thread
  never waits on it. Each round also keeps ``intervals``, ``[name, t0_ns,
  t1_ns]`` of the main thread's top-level spans (opened with no span open:
  ``data.*``, ``round.dispatch``, ``round.sync``, ``eval``, ``offload.*``),
  at most ``MAX_INTERVALS`` a round; ``intervals_dropped`` counts the rest.
* ``anchors``: ``perf_counter_ns`` at which an annotation the program opens
  on the profiler's trace began (``utils.logging.profile_ctx``'s
  ``fed:profile``): the trace's start of that annotation minus the anchor
  puts every stamp above onto the trace's clock, as the benchmark does
  with ``bench:traced_window`` (``benchmarks/benchlib/timeline.py``).
* ``snapshot()`` is all of it as plain data; ``write(dir)`` leaves it as
  ``spans.json`` (``utils.logging.profile_ctx`` does, beside the device
  trace). PERF.md section 3 names every span, counter, stamp and scope.
"""

from __future__ import annotations

import json
import os
import queue
import re
import threading
import time
from collections import deque

import jax

RING_ROUNDS = 4096
#: top-level host spans kept as intervals a round; the rest are counted
MAX_INTERVALS = 32
WAITER = "tracing.waiter"
SPAN_PREFIX = "fed:"
PHASE_PREFIX = "phase:"
LAYER_PREFIX = "layer:"
#: the five phases of the federated round, in program order
PHASES = ("download_accounting", "client_grad", "reduce", "compress",
          "server_update")
OTHER = "other"

_COMPILE_DURATIONS = {
    "/jax/core/compile/jaxpr_trace_duration": "compile.trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "compile.lower_s",
    "/jax/core/compile/backend_compile_duration": "compile.backend_s",
}
_COMPILE_EVENTS = {
    "/jax/compilation_cache/cache_hits": "compile.cache_hits",
    "/jax/compilation_cache/cache_misses": "compile.cache_misses",
}


class _OpenSpans(threading.local):
    def __init__(self):                    # once a thread
        self.stack = []
        self.main = threading.current_thread() is threading.main_thread()


class _Recorder:
    def __init__(self):
        self.lock = threading.Lock()
        self.open_spans = _OpenSpans()
        self.listening = False
        self.waiting = None                # the waiter's queue, once started
        self.reset()

    def reset(self):
        with self.lock:
            self.ring = deque(maxlen=RING_ROUNDS)
            self.counters = {}             # name -> [value, t_ns last change]
            self.anchors = {}
            waiting, self.waiting = self.waiting, None
            self._open(None)
        if waiting is not None:            # it ends after what it was handed
            waiting.put(None)

    def _open(self, index):
        # before the first mark the open round is set-up: index None
        self.round = {"round": index, "t_ns": time.perf_counter_ns(),
                      "t_enq_ns": None, "t_done_ns": None,
                      "spans": {}, "counts": {}, "intervals": [],
                      "intervals_dropped": 0}

    def mark(self, index):
        with self.lock:
            self.ring.append(self.round)
            self._open(index)
            if self.waiting is None:
                self.waiting = queue.SimpleQueue()
                threading.Thread(target=_wait, args=(self.waiting,),
                                 name=WAITER, daemon=True).start()

    def enqueued(self, out):
        t = time.perf_counter_ns()
        with self.lock:
            rnd = self.round
            rnd["t_enq_ns"] = t
            waiting = self.waiting
        if waiting is not None:
            waiting.put((rnd, out))

    def add_span(self, name, ns, self_ns, t0_top):
        with self.lock:
            rnd = self.round
            tot = rnd["spans"].get(name)
            if tot is None:
                rnd["spans"][name] = [ns, 1, self_ns]
            else:
                tot[0] += ns
                tot[1] += 1
                tot[2] += self_ns
            if t0_top is None:
                return
            if len(rnd["intervals"]) < MAX_INTERVALS:
                rnd["intervals"].append((name, t0_top, t0_top + ns))
            else:
                rnd["intervals_dropped"] += 1

    def add_count(self, name, n):
        with self.lock:
            c = self.counters.setdefault(name, [0, 0])
            c[0] += n
            c[1] = time.perf_counter_ns()
            counts = self.round["counts"]
            counts[name] = counts.get(name, 0) + n


def _wait(waiting):
    """The waiter: stamp each handed round's ``t_done_ns`` once its output
    is ready, in the order handed (one thread, so dispatch order)."""
    while True:
        item = waiting.get()
        if item is None:
            return
        rnd, out = item
        del item
        try:
            out.block_until_ready()        # releases the GIL while it waits
        except Exception:  # failed or deleted: no stamp; the sync raises it
            pass
        else:
            t = time.perf_counter_ns()
            with _REC.lock:
                rnd["t_done_ns"] = t
        del rnd, out


_REC = _Recorder()


class span:
    """``with span("data.fetch"): ...`` — see the module docstring. The span
    open on this thread when it begins is its parent: a span's own time is
    its duration less its children's (third number of its totals)."""

    __slots__ = ("name", "_ann", "_t0", "_children_ns", "_parent", "_stack",
                 "_top")

    def __init__(self, name: str):
        self.name = name

    def __enter__(self):
        local = _REC.open_spans
        stack = self._stack = local.stack
        self._parent = stack[-1] if stack else None
        self._top = local.main and not stack
        self._children_ns = 0
        stack.append(self)
        self._ann = jax.profiler.TraceAnnotation(SPAN_PREFIX + self.name)
        self._ann.__enter__()
        self._t0 = time.perf_counter_ns()
        return self

    def __exit__(self, *exc):
        ns = time.perf_counter_ns() - self._t0
        self._ann.__exit__(*exc)
        self._stack.pop()
        if self._parent is not None:
            self._parent._children_ns += ns
        _REC.add_span(self.name, ns, ns - self._children_ns,
                      self._t0 if self._top else None)
        return False


_END = object()


def spanned(iterable, name: str):
    """``iterable``'s items, each step of its iterator inside
    ``span(name)`` (a generator's work happens in its ``next``)."""
    it = iter(iterable)
    while True:
        with span(name):
            item = next(it, _END)
        if item is _END:
            return
        yield item


def round_mark(index: int) -> None:
    """Close the current round's totals into the ring (the last
    ``RING_ROUNDS`` rounds) and open round ``index``: called once a
    dispatch, before the dispatch's own span. The first call starts the
    waiter."""
    _REC.mark(int(index))


def round_enqueued(out) -> None:
    """Stamp the open round's ``t_enq_ns`` and hand ``out`` (one small
    output of the round's program, never a buffer a later call donates) to
    the waiter, which stamps ``t_done_ns`` once the device has made it.
    Called by the dispatch site right after the jitted call returns."""
    _REC.enqueued(out)


def anchor(name: str) -> None:
    """Record ``perf_counter_ns`` now as the start of the trace annotation
    ``name`` the caller has just opened (module docstring)."""
    t = time.perf_counter_ns()
    with _REC.lock:
        _REC.anchors[name] = t


def count(name: str, n=1) -> None:
    _REC.add_count(name, n)


def phase(name: str):
    """Scope for the round's code inside ``jit``: metadata only, the
    compiled program is the same with or without it."""
    return jax.named_scope(PHASE_PREFIX + name)


def layer(name: str):
    """Scope for a part of a model, inside the loss and so inside a
    ``phase``: a prefix of its own, because an instruction's phase is its
    innermost ``phase:`` scope and the round's phases have to keep adding
    up to the round."""
    return jax.named_scope(LAYER_PREFIX + name)


def compile_counters() -> None:
    """Feed ``compile.trace_s``/``lower_s``/``backend_s``/``programs`` and
    ``compile.cache_hits``/``cache_misses`` from ``jax.monitoring``, from
    the first call on (later calls do nothing)."""
    with _REC.lock:
        if _REC.listening:
            return
        _REC.listening = True

    outer = {name: deque(maxlen=RING_ROUNDS)      # [(start, counted s)]
             for name in _COMPILE_DURATIONS.values()}

    def on_duration(event, duration, **kw):
        name = _COMPILE_DURATIONS.get(event)
        if name is None:
            return
        # JAX reports a jit traced inside another once on its own and once
        # more inside the outer one's duration: count the outermost only
        start = time.perf_counter() - duration
        with _REC.lock:
            nested = 0.0
            while outer[name] and outer[name][-1][0] >= start:
                nested += outer[name].pop()[1]
            counted = max(duration - nested, 0.0)
            outer[name].append((start, counted + nested))
        count(name, counted)
        if name == "compile.backend_s":
            count("compile.programs")

    def on_event(event, **kw):
        name = _COMPILE_EVENTS.get(event)
        if name is not None:
            count(name)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)


def snapshot() -> dict:
    """The ring, the open round, the counters and the anchors, as plain
    data (copies): ``{"rounds": [{"round", "t_ns", "t_enq_ns", "t_done_ns",
    "spans": {name: [ns, count, self_ns]}, "counts": {name: growth},
    "intervals": [[name, t0_ns, t1_ns]], "intervals_dropped"}], "open":
    {...}, "counters": {name: [value, t_ns of the last change]}, "anchors":
    {annotation: t_ns}}`` — times on ``time.perf_counter_ns``; a stamp not
    (yet) made is ``None``."""
    def plain(r):
        return {"round": r["round"], "t_ns": r["t_ns"],
                "t_enq_ns": r["t_enq_ns"], "t_done_ns": r["t_done_ns"],
                "spans": {k: list(v) for k, v in r["spans"].items()},
                "counts": dict(r["counts"]),
                "intervals": [list(i) for i in r["intervals"]],
                "intervals_dropped": r["intervals_dropped"]}

    with _REC.lock:
        return {"rounds": [plain(r) for r in _REC.ring],
                "open": plain(_REC.round),
                "counters": {k: list(v) for k, v in _REC.counters.items()},
                "anchors": dict(_REC.anchors)}


def write(directory: str) -> str:
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, "spans.json")
    with open(path, "w") as f:
        json.dump(snapshot(), f)
    return path


def reset() -> None:
    """Forget every round, counter and anchor (tests; a second ``train`` in
    one process that wants its own numbers). The waiter ends once the
    rounds it holds are done; the next ``round_mark`` starts another."""
    _REC.reset()


# ---------------------------------------------------------------- op_phases

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?(%[^\s(]+)\s*\(.*\{\s*$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_PHASE = re.compile(re.escape(PHASE_PREFIX) + r"([A-Za-z0-9_]+)")
_LAYER = re.compile(re.escape(LAYER_PREFIX) + r"([A-Za-z0-9_]+)")
_CALLED = re.compile(
    r"(?:calls|body|condition|to_apply|branch_computations|"
    r"called_computations|true_computation|false_computation)="
    r"(\{[^}]*\}|%[^\s,)]+)")
#: a collective the partitioner made out of the client gradient's
#: contraction over the cohort is the round's reduce
_COLLECTIVE = re.compile(r"\s(all-reduce|all-reduce-start|all-reduce-done|"
                         r"reduce-scatter|all-gather|all-gather-start|"
                         r"all-gather-done|collective-permute)\(")


def instruction_key(text: str) -> str:
    """What identifies an instruction in both the compiled program's text
    and a device trace's event name (on a TPU the instruction as the
    profiler prints it): ``%name = result type(%operand names)``. The two
    printers differ in the rest — the profiler adds the operands' types and
    drops ``metadata=``, ``backend_config=`` and ``sharding=``, and writes
    ``async-start`` where ``as_text`` writes ``slice-start`` — so opcode
    and attributes stay out of the key."""
    text = text.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    head, eq, rest = text.partition(" = ")
    if not eq:
        return text
    depth, end_of_type = 0, -1
    for i, ch in enumerate(rest):          # the type may hold ( [ { itself
        if ch in "([{":
            depth += 1
        elif ch in ")]}":
            depth -= 1
        elif ch == " " and depth == 0:
            end_of_type = i
            break
    start = rest.find("(", end_of_type + 1)
    if end_of_type < 0 or start < 0:
        return text
    depth, end = 0, len(rest) - 1
    for i in range(start, len(rest)):
        depth += (rest[i] == "(") - (rest[i] == ")")
        if depth == 0:
            end = i
            break
    operands = ", ".join(re.findall(r"%[^\s,(){}]+", rest[start:end + 1]))
    return f"{head} = {rest[:end_of_type]}({operands})"


def op_phases(compiled) -> dict:
    """``{instruction_key: phase}`` of every instruction of every
    computation of a compiled program (or of its text). An instruction's
    phase is, in this order: the innermost ``phase:`` scope in its own
    ``op_name``; for one the compiler made without a name (a fusion of a
    max-pool's mask, say), the phase most of the instructions it calls
    carry; the phase of the ``while``/``call``/``fusion`` that holds its
    computation; for bare data movement (a copy, a layout change), the phase
    of what it reads, else of what reads it; else ``"other"``."""
    return _op_scopes(compiled, _PHASE)


def op_layers(compiled) -> dict:
    """``{instruction_key: layer}`` by the innermost ``layer:`` scope, as
    ``op_phases`` by phase, but an instruction outside every layer (the
    sketch, the server update, the loss's own sums) stays ``"other"``
    whatever its neighbours carry."""
    return _op_scopes(compiled, _LAYER)


def _op_scopes(compiled, scope) -> dict:
    # the two rules that are the phases' alone: a collective inside the
    # client gradient is the reduce, and bare data movement takes its
    # neighbours' phase (every instruction belongs to some phase; most
    # belong to no layer)
    phase_scopes = scope is _PHASE
    text = compiled if isinstance(compiled, str) else compiled.as_text()
    own, comp_of, callees, operands = {}, {}, {}, {}   # by instruction key
    holder, members, by_name = {}, {}, {}     # by computation / %name
    comp = None
    for line in text.splitlines():
        m = _COMPUTATION.match(line)
        if m:
            comp = m.group(1)
            continue
        if comp is None or " = " not in line:
            if line.startswith("}"):
                comp = None
            continue
        key = instruction_key(line)
        comp_of[key] = comp
        members.setdefault(comp, []).append(key)
        by_name[key.partition(" = ")[0]] = key
        operands[key] = re.findall(r"%[^\s,(){}]+", key.partition(" = ")[2])
        names = _OP_NAME.search(line)
        found = scope.findall(names.group(1)) if names else []
        own[key] = found[-1] if found else None
        if (phase_scopes and own[key] == "client_grad"
                and _COLLECTIVE.search(line)):
            own[key] = "reduce"
        callees[key] = [c for group in _CALLED.findall(line)
                        for c in re.findall(r"%[^\s,{}]+", group)]
        for callee in callees[key]:
            holder.setdefault(callee, key)

    def most(phases):
        phases = [p for p in phases if p is not None]
        return max(sorted(set(phases)), key=phases.count) if phases else None

    def inside(key, seen):
        if own[key] is not None or key in seen:
            return own[key]
        return most(inside(k, seen | {key}) for c in callees[key]
                    for k in members.get(c, ()))

    def held(key):
        seen = set()
        while key is not None and key not in seen:
            seen.add(key)
            if phases[key] is not None:
                return phases[key]
            key = holder.get(comp_of[key])
        return None

    phases = {key: inside(key, frozenset()) for key in own}
    users = {}
    for key, names in operands.items():
        for name in names:
            if name in by_name:
                users.setdefault(by_name[name], []).append(key)
    for _ in range(4):                        # data movement: a few hops
        for key in [k for k, p in phases.items() if p is None]:
            phases[key] = held(key) or (phase_scopes and (
                most(phases.get(by_name.get(n)) for n in operands[key])
                or most(phases[u] for u in users.get(key, ())))) or None
    return {key: p or OTHER for key, p in phases.items()}
