"""The program's one span API: ``span(name, **args)``, two sinks.

Every ``with span("serve:admit"):`` enters a ``jax.profiler.TraceAnnotation``
of the same name and arguments, so under a profiler session (``--profile-dir``,
the benchmark's traced run) the span lands on the host plane of the
profiler's own trace, in the same file and on the same time axis as the
device's operations: an idle gap of the chip can be put down to what the
host was doing in it. The axis is shared, the clocks are two: on the v5e
the chip's events sat 0.3-2.4 ms EARLY against the host's, constant within
a trace and different in each (PERF.md section 6, PR 26). Before reading a
gap to the millisecond, find that trace's offset from the runtime's own
``DoEnqueueProgram`` / ``CompleteCallbacks`` host events, which carry the
``run_id`` of the chip's ``XLA Modules`` event (docs/OPERATIONS.md;
``benchmark/program_spans.clock_offset`` does it). When a :class:`Tracer`
is installed (``--trace``) the same call also
records a Chrome trace-event (load the file in chrome://tracing or
https://ui.perfetto.dev). With neither it costs the annotation's own no-op,
about a microsecond. There is no switch: a span is on when a sink is.

Reference parity: SURVEY.md §5 "Tracing / profiling" — the reference's only
observability was the Spark web UI's per-stage/task timing, external to the
repo.

Names are ``<layer>:<what>``, fixed strings; ids and sizes go in ``args``
(ints and short strings that are already at hand). A span keeps its two
``time.perf_counter()`` stamps (``start``, ``end``): a histogram or a
request's phase that covers the same lines reads them from the span, so the
two cannot drift apart.

The Tracer's memory is bounded: events live in a RING buffer
(``max_events``, default 200k) — a long serving run keeps the newest
events instead of growing without limit; ``dropped`` counts what the ring
displaced, and ``save`` records it in the trace.

Rows: events carry the FULL thread ident as ``tid`` (no truncation — the
old ``tid & 0xFFFF`` could collide two threads onto one row) and ``save``
emits ``thread_name`` metadata events so Perfetto labels each row with
the Python thread's name. :meth:`Tracer.set_tid_name` names synthetic
rows (e.g. one row per request id for serve timelines); :meth:`Tracer.
complete` records a span from explicit ``time.perf_counter()`` stamps —
how cross-iteration request phases are traced after the fact.
"""

from __future__ import annotations

import json
import os
import threading
import time
from collections import deque

import jax
from jax.profiler import TraceAnnotation


class Tracer:
    """Collects trace events; thread-safe appends; ``save`` writes the
    Chrome trace-event JSON ({"traceEvents": [...]})."""

    def __init__(self, max_events: int = 200_000) -> None:
        if max_events < 1:
            raise ValueError(f"max_events must be >= 1, got {max_events}")
        self.max_events = max_events
        self._events: deque[dict] = deque(maxlen=max_events)
        self._lock = threading.Lock()
        self._t0 = time.perf_counter()
        self._tid_names: dict[int, str] = {}
        self.dropped = 0

    def _record(self, ev: dict) -> None:
        tid = ev["tid"]
        with self._lock:
            if tid not in self._tid_names:
                # only real threads auto-name; synthetic tids (requests)
                # are named explicitly via set_tid_name
                if tid == threading.get_ident():
                    self._tid_names[tid] = threading.current_thread().name
            if len(self._events) >= self.max_events:
                self.dropped += 1
            self._events.append(ev)

    def set_tid_name(self, tid: int, name: str) -> None:
        """Name a (possibly synthetic) ``tid`` row — emitted as a
        ``thread_name`` metadata event at :meth:`save`."""
        with self._lock:
            self._tid_names[int(tid)] = name

    def complete(self, name: str, start_s: float, end_s: float, *,
                 tid: int | None = None, **args) -> None:
        """Record a complete event ("ph": "X") from explicit
        ``time.perf_counter()`` stamps (taken while the phase ran, recorded
        later) — the serve batcher emits each finished request's phase
        timeline this way, one synthetic ``tid`` row per request."""
        ev = {"name": name, "ph": "X",
              "ts": (start_s - self._t0) * 1e6,
              "dur": max((end_s - start_s) * 1e6, 0.0),
              "pid": os.getpid(),
              "tid": threading.get_ident() if tid is None else int(tid)}
        if args:
            ev["args"] = args
        self._record(ev)

    def save(self, path: str) -> None:
        d = os.path.dirname(path)
        if d:
            os.makedirs(d, exist_ok=True)
        with self._lock:
            events = list(self._events)
            names = dict(self._tid_names)
            dropped = self.dropped
        pid = os.getpid()
        meta = [
            {"name": "thread_name", "ph": "M", "pid": pid, "tid": tid,
             "args": {"name": name}}
            for tid, name in sorted(names.items())
        ]
        if dropped:
            meta.append({
                # tid -1: a sentinel row no real thread or synthetic
                # request id can own (request rows use non-negative ids)
                "name": "tracer_dropped_events", "ph": "i", "ts": 0.0,
                "s": "g", "pid": pid, "tid": -1,
                "args": {"dropped": dropped, "max_events": self.max_events},
            })
        with open(path, "w") as f:
            json.dump({"traceEvents": meta + events,
                       "displayTimeUnit": "ms"}, f)


_tracer: Tracer | None = None


def set_tracer(tracer: Tracer | None) -> None:
    global _tracer
    _tracer = tracer


def get_tracer() -> Tracer | None:
    return _tracer


class span:
    """``with span("engine:launch", program="window_fn"):`` — see the module
    docstring. ``start``/``end`` are the block's ``perf_counter`` stamps."""

    __slots__ = ("name", "args", "start", "end", "_annotation")

    def __init__(self, name: str, **args) -> None:
        self.name, self.args = name, args
        self._annotation = TraceAnnotation(name, **args)

    def __enter__(self) -> "span":
        self._annotation.__enter__()
        self.start = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.end = time.perf_counter()
        self._annotation.__exit__(*exc)
        if _tracer is not None:
            _tracer.complete(self.name, self.start, self.end, **self.args)


#: The train program's device scopes: every operation of a train step (and
#: of its gradient: JAX carries a scope into the backward as
#: ``transpose(jvp(head))``) traced under one of these names is that
#: layer's work, the innermost name winning. `utils/op_scopes.py` reads them
#: back from the compiled program into ``op_scopes.json`` beside a
#: ``--profile-dir`` trace, where the benchmark's per-layer device metrics
#: find them.
SCOPES = ("embed", "lstm", "head", "optimizer", "dp_sync")


def scope(name: str):
    """``with scope("head"):`` — `jax.named_scope` for one of `SCOPES`.
    Compile-time metadata only (the ``op_name`` of every HLO instruction
    traced inside): nothing runs, with or without a profiler."""
    if name not in SCOPES:
        raise ValueError(f"unknown device scope {name!r}: one of {SCOPES}")
    return jax.named_scope(name)
