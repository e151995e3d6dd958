"""The program's own spans, read from the profiler trace of a run, and what
they say about the chip's idle time.

`lstm_tensorspark_tpu/utils/tracing.py`'s ``span(name, **args)`` enters a
`jax.profiler.TraceAnnotation`, so a traced run (``--trace 1``) holds, on
the host plane of the same `*.xplane.pb` whose device planes
`trace_reduce.py` reads, one event per span: ``serve:*`` (the scheduler:
`serve/batcher.py`, `server.py`), ``engine:*`` (`serve/engine.py`) and
``train:*`` (`train/loop.py`), each with its thread's line, start, end and
``args`` (the event's ``stats``). PERF.md §3 lists them. Host and device
events share the trace's time axis, not a clock: the chip's sit a
millisecond or two off the host's, constant over a trace and different in
each. `clock_offset` brackets the difference from the runtime's own events,
`idle_by_span` takes it out, and ``samples.span_clock_check_ms`` shows the
bracket and whether it was found (``corrected``).

A reader under `layer_metrics/` calls ``load(result, cell)``: the file is
parsed once and kept on ``result``. A program without spans (the parent of
the PR that added them) gives None, and every reader built on it None.
"""

from __future__ import annotations

import dataclasses
import os

import trace_reduce

PREFIXES = ("serve:", "engine:", "train:")
#: the span whose line is the scheduler's / the trainer's (the trace names a
#: line by process and thread id, not by the Python thread's name)
SCHEDULER_ANCHOR = "serve:iteration"
TRAINER_ANCHOR = "train:dispatch"
#: idle time that no span of the thread covers
NO_SPAN = "(no span)"
#: the TPU runtime's own host events around one run of a program on one
#: chip, both carrying the ``run_id`` (and ``device_ordinal``) that the
#: chip's `XLA Modules` event of the run carries
ENQUEUED, COMPLETED = "DoEnqueueProgram", "CompleteCallbacks"


@dataclasses.dataclass(frozen=True)
class Span:
    name: str
    line: tuple             # (plane, index of the line in it): one thread
    start: float            # seconds from the trace's origin
    end: float
    args: dict

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclasses.dataclass
class ProgramSpans:
    spans: list[Span]       # every program span of the trace, by start
    #: `clock_offset` of the first chip; None for a trace without a chip
    clock: dict | None = None

    @property
    def device_ahead(self) -> float | None:
        """Seconds the first chip's clock is ahead of the host's in this
        trace; None where the trace cannot say."""
        return (self.clock or {}).get("offset")

    def named(self, name: str, line=None) -> list[Span]:
        return [s for s in self.spans
                if s.name == name and (line is None or s.line == line)]

    def line_of(self, anchor: str):
        """The line that holds most of the spans named ``anchor``; None
        when there is none."""
        lines = [s.line for s in self.spans if s.name == anchor]
        return max(set(lines), key=lines.count) if lines else None

    def on_line(self, line) -> list[Span]:
        return [s for s in self.spans if s.line == line]


def read(path: str) -> ProgramSpans:
    import jax.profiler

    spans, host, runs = [], {ENQUEUED: {}, COMPLETED: {}}, {}
    planes = list(jax.profiler.ProfileData.from_file(path).planes)
    chips = sorted(int(m.group(2)) for m in map(
        trace_reduce.DEVICE_PLANE.match, (p.name for p in planes)) if m)
    for plane in planes:
        m = trace_reduce.DEVICE_PLANE.match(plane.name)
        if m and int(m.group(2)) == chips[0]:
            for line in plane.lines:
                if line.name == trace_reduce.MODULES_LINE:
                    for e in line.events:
                        runs[dict(e.stats).get("run_id")] = (
                            e.start_ns / 1e9, (e.start_ns + e.duration_ns) / 1e9)
        if plane.name.startswith("/device:"):
            continue
        for i, line in enumerate(plane.lines):
            for e in line.events:
                if e.name.startswith(PREFIXES):
                    start = e.start_ns / 1e9
                    spans.append(Span(e.name, (plane.name, i), start,
                                      start + (e.duration_ns or 0) / 1e9,
                                      dict(e.stats)))
                elif e.name in host:
                    stats = dict(e.stats)
                    if stats.get("device_ordinal", chips[0]) == chips[0]:
                        host[e.name][stats.get("run_id")] = e.start_ns / 1e9
    spans.sort(key=lambda s: (s.start, -s.end))
    return ProgramSpans(spans, clock_offset(
        runs, host[ENQUEUED], host[COMPLETED]) if chips else None)


def clock_offset(runs: dict, enqueued: dict, completed: dict) -> dict:
    """How far the chip's clock is ahead of the host's, in seconds, from two
    things that cannot happen: a program starting on the chip before the
    runtime began to enqueue it (so the chip is ahead by at most ``hi``, the
    smallest start - enqueue) and the runtime handling a program's
    completion before it ended (so by at least ``lo``, the largest end -
    completion), each pair matched by ``run_id``. ``offset`` is the middle,
    ``programs`` the pairs; no ``offset`` where the trace has no such events
    or the two contradict each other."""
    his = [runs[r][0] - at for r, at in enqueued.items() if r in runs]
    los = [runs[r][1] - at for r, at in completed.items() if r in runs]
    if not his or not los:
        return {}
    clock = {"lo": max(los), "hi": min(his), "programs": min(len(his), len(los))}
    if clock["lo"] <= clock["hi"]:
        clock["offset"] = (clock["lo"] + clock["hi"]) / 2
    return clock


def load(result: dict, cell) -> ProgramSpans | None:
    """The run's program spans (parsed once, kept on ``result``); None when
    the run left no trace or the trace holds no program span."""
    if "program_spans" not in result:
        path = trace_reduce.find_xplane(os.path.join(cell.workdir, "profile"))
        program = read(path) if path else None
        result["program_spans"] = program if program and program.spans else None
        if result["program_spans"] and program.clock is not None:
            result["samples"]["span_clock_check_ms"] = clock_check(program.clock)
    return result["program_spans"]


def window(result: dict, cell):
    """``(lo, hi)`` of the traced window on the trace's clock: what
    `trace_reduce.traced_window` gives or, on a machine without a device
    plane (the CPU rehearsal), the same marks of the benchmark on the host
    plane (`run.report` places the window by them); None without marks."""
    if result.get("trace_window"):
        return result["trace_window"]
    trace = result.get("trace")
    if trace is None:
        return None
    marks = [m.start for m in trace.marks if m.name in (
        "bench:window_open", "bench:window_close", result.get("sync_mark"))]
    if len(marks) < 2 or max(marks) <= min(marks):
        return None
    return min(marks), max(marks)


def thread_in_window(result: dict, cell, anchor: str) -> list[Span]:
    """The spans of the thread that holds ``anchor`` lying wholly in the
    traced window; none where there is nothing to read."""
    program, span = load(result, cell), window(result, cell)
    line = program.line_of(anchor) if program else None
    if line is None or span is None:
        return []
    lo, hi = span
    return [s for s in program.on_line(line) if lo <= s.start and s.end <= hi]


# ---- attribution ------------------------------------------------------------


def innermost(spans) -> list[tuple[float, float, str]]:
    """One thread's spans (nested as `with` blocks nest) cut into disjoint
    pieces ``(start, end, name)``, each instant under the innermost span
    that covers it."""
    out, stack = [], []
    cursor = 0.0            # pieces are out up to here

    def piece(end, name):
        nonlocal cursor
        if end > cursor:
            out.append((cursor, end, name))
            cursor = end

    for s in sorted(spans, key=lambda s: (s.start, -s.end)):
        while stack and stack[-1].end <= s.start:
            top = stack.pop()
            piece(top.end, top.name)
        if stack:
            piece(s.start, stack[-1].name)
        else:
            cursor = s.start
        stack.append(s)
    while stack:
        top = stack.pop()
        piece(top.end, top.name)
    return out


def attribute(gaps, spans) -> dict[str, float]:
    """Seconds of the disjoint intervals ``gaps`` by the innermost of
    ``spans`` (one thread's) that overlaps each part; what no span covers
    goes to `NO_SPAN`. Largest first."""
    pieces, out, j = innermost(spans), {}, 0
    for lo, hi in sorted(gaps):
        covered = 0.0
        while j < len(pieces) and pieces[j][1] <= lo:
            j += 1
        k = j
        while k < len(pieces) and pieces[k][0] < hi:
            s, e, name = pieces[k]
            part = min(e, hi) - max(s, lo)
            if part > 0:
                out[name] = out.get(name, 0.0) + part
                covered += part
            k += 1
        if hi - lo > covered:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (hi - lo) - covered
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def idle_by_span(result: dict, cell, anchor: str) -> dict[str, float] | None:
    """Chip 0's idle seconds in the traced window by the innermost span of
    the thread that holds ``anchor`` (kept in ``samples.idle_by_span``);
    None without a device plane or without that thread."""
    w = trace_reduce.traced_window(result, cell)
    program = load(result, cell)
    line = program.line_of(anchor) if program else None
    if w is None or line is None:
        return None
    if "idle_by_span" not in result["samples"]:
        _, lo, hi, chips = w
        # onto the host's clock; as the trace has them where no offset was
        # found (``span_clock_check_ms.corrected`` 0: not to be trusted)
        ahead = program.device_ahead or 0.0
        gaps = [(s - ahead, e - ahead)
                for s, e, _ in trace_reduce.idle_gaps(chips[0], lo, hi)]
        result["samples"]["idle_by_span"] = attribute(gaps, program.on_line(line))
    return result["samples"]["idle_by_span"]


def idle_attributed_share(result: dict, cell, anchor: str) -> float | None:
    """Percent of chip 0's idle time in the window that falls in some span
    of the anchor's thread."""
    split = idle_by_span(result, cell, anchor)
    idle = sum(split.values()) if split else 0.0
    return 100.0 * (1.0 - split.get(NO_SPAN, 0.0) / idle) if idle else None


# ---- two clocks, shown --------------------------------------------------------


def clock_check(clock: dict) -> dict:
    """``samples.span_clock_check_ms``: the first chip's lead over the
    host's clock in milliseconds, as `clock_offset` bracketed it from
    ``programs`` runs matched by ``run_id`` (between ``device_ahead_lo``
    and ``_hi``; ``device_ahead``, the middle, is what `idle_by_span` took
    out), and ``corrected``: 1 where it was found, 0 where the trace lacks
    the runtime's events or they contradict each other: the idle split is
    then laid on the spans as the trace has them and is not to be trusted
    to the millisecond. A value beyond -1 or +1 ms says the two clocks
    disagree by more than that."""
    check = {"device_ahead" + ("_" + k if k != "offset" else ""): 1e3 * clock[k]
             for k in ("lo", "hi", "offset") if k in clock}
    return {**check, "programs": clock.get("programs", 0),
            "corrected": int("offset" in clock)}
