"""From a profiler trace (`*.xplane.pb`) to numbers: busy and idle time of
each chip, seconds per device operation, idle gaps and what the host was
doing in them, and the exposed part of a set of operations.

Reads the file with `jax.profiler.ProfileData` and nothing else. What a TPU
trace looks like (looked at by hand before this was written; PERF.md §3):

- one plane per chip, ``/device:TPU:<n>``. Its line ``XLA Ops`` holds one
  event per executed HLO operation, named by its whole HLO text
  (``%fusion.592 = bf16[64,128,1024]{...} fusion(...), kind=kOutput, ...``):
  the label before `` = `` and the opcode before the operands are parsed out
  of it here. A Mosaic (Pallas) kernel is an operation whose opcode is
  ``custom-call``. Events of the line do not overlap except by nesting: a
  ``while`` (a `lax.scan`: the K steps of a dispatch, a prefill, a decode
  window) spans its body's events, so containers are dropped and only leaves
  count as work. ``Async XLA Ops`` holds the start-to-done spans of
  asynchronous operations (copies, collectives), which overlap the leaves;
  ``XLA Modules`` one event per executed program (``jit_<fn>(<id>)``);
- the host plane ``/host:CPU`` has one line per thread;
  `jax.profiler.TraceAnnotation` spans land there under their own names. The
  device's clock runs within about a millisecond of the host's (the recorded
  trace under `tests/data/` shows a first program run 0.9 ms "before" the
  mark that preceded its dispatch), which is nothing against windows of
  seconds.

All times here are seconds from the trace's own origin.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
ASYNC_LINE = "Async XLA Ops"
MODULES_LINE = "XLA Modules"
#: operations whose interval is their children's: not work themselves
CONTAINERS = ("while", "conditional", "call")
_OPCODE = re.compile(r"(?<![\w.-])([a-z][a-z0-9-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str               # as the trace has it (an HLO instruction's text)
    start: float
    end: float

    @property
    def seconds(self) -> float:
        return self.end - self.start

    @property
    def label(self) -> str:
        """``%fusion.592 = ...`` -> ``fusion.592``."""
        return self.name.split(" = ", 1)[0].lstrip("%")

    @property
    def opcode(self) -> str:
        """The HLO opcode (``fusion``, ``copy``, ``custom-call``,
        ``all-reduce-start``, ``while``...); the label's stem where the
        name is not an HLO instruction."""
        head, sep, rest = self.name.partition(" = ")
        m = _OPCODE.search(rest) if sep else None
        return m.group(1) if m else re.sub(r"[.\d]+$", "", self.label)

    @property
    def short(self) -> str:
        """A name for a report: the HLO text without layouts, cut."""
        return _LAYOUT.sub("", self.name).lstrip("%")[:120]


@dataclasses.dataclass
class ChipTrace:
    chip: int
    ops: list[Event]        # leaf operations, by start
    modules: list[Event]    # program executions, by start
    async_ops: list[Event] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class Trace:
    chips: list[ChipTrace]
    marks: list[Event]      # the benchmark's own host annotations ("bench:")


def find_xplane(directory: str) -> str | None:
    paths = sorted(glob.glob(os.path.join(directory, "**", "*.xplane.pb"),
                             recursive=True))
    return paths[-1] if paths else None


def load(path: str) -> Trace:
    import jax.profiler

    data = jax.profiler.ProfileData.from_file(path)
    chips, marks = [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            lines = {line.name: [_event(e) for e in line.events]
                     for line in plane.lines
                     if line.name in (OPS_LINE, ASYNC_LINE, MODULES_LINE)}
            by_start = lambda evs: sorted(evs, key=lambda e: e.start)  # noqa: E731
            chips.append(ChipTrace(
                int(m.group(2)),
                by_start(e for e in lines.get(OPS_LINE, [])
                         if e.opcode not in CONTAINERS),
                by_start(lines.get(MODULES_LINE, [])),
                by_start(lines.get(ASYNC_LINE, []))))
        elif not plane.name.startswith("/device:"):
            for line in plane.lines:
                marks += [_event(e) for e in line.events
                          if e.name.startswith("bench:")]
    chips.sort(key=lambda c: c.chip)
    marks.sort(key=lambda e: e.start)
    return Trace(chips, marks)


def _event(e) -> Event:
    start = e.start_ns / 1e9
    return Event(e.name, start, start + (e.duration_ns or 0) / 1e9)


# ---- interval arithmetic ----------------------------------------------------


def union(intervals) -> list[tuple[float, float]]:
    """Disjoint, sorted cover of ``(start, end)`` pairs."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if min(e, hi) > max(s, lo)]


def total(intervals) -> float:
    return sum(e - s for s, e in intervals)


def subtract(a, b) -> list[tuple[float, float]]:
    """The part of the disjoint cover ``a`` that ``b`` (disjoint) leaves."""
    out, j = [], 0
    b = list(b)
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


# ---- reductions -------------------------------------------------------------


def busy_seconds(chip: ChipTrace, lo: float, hi: float) -> float:
    """Seconds of [lo, hi] in which some operation ran on this chip."""
    return total(clip(union((e.start, e.end) for e in chip.ops), lo, hi))


def op_seconds(chip: ChipTrace, lo: float, hi: float) -> dict[str, float]:
    """Seconds per operation (by its report name) inside [lo, hi], clipped
    at the edges."""
    out: dict[str, float] = {}
    for e in chip.ops:
        s, t = max(e.start, lo), min(e.end, hi)
        if t > s:
            out[e.short] = out.get(e.short, 0.0) + (t - s)
    return out


def seconds_by_opcode(chip: ChipTrace, lo: float, hi: float) -> dict[str, float]:
    """Seconds of [lo, hi] per HLO opcode, leaves and asynchronous spans
    apart (``async:all-reduce-start``), largest first."""
    out: dict[str, float] = {}
    for prefix, events in (("", chip.ops), ("async:", chip.async_ops)):
        for e in events:
            s, t = max(e.start, lo), min(e.end, hi)
            if t > s:
                key = prefix + e.opcode
                out[key] = out.get(key, 0.0) + (t - s)
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def opcode_seconds(chip: ChipTrace, lo: float, hi: float, opcode: str) -> float:
    """Seconds of [lo, hi] spent in leaf operations of this opcode."""
    return sum(min(e.end, hi) - max(e.start, lo) for e in chip.ops
               if e.opcode == opcode and min(e.end, hi) > max(e.start, lo))


def collective_spans(chip: ChipTrace, stem: str):
    """The intervals of one kind of collective (``all-reduce``): the
    synchronous operations, and the start-to-done spans of the asynchronous
    ones (``all-reduce-start`` on the async line)."""
    sync = [(e.start, e.end) for e in chip.ops if e.opcode == stem]
    spans = [(e.start, e.end) for e in chip.async_ops
             if e.opcode in (stem, stem + "-start")]
    return union(sync + spans)


def exposed_seconds(chip: ChipTrace, lo: float, hi: float, stem: str) -> float:
    """Seconds of [lo, hi] in which a collective of this kind was under way
    on this chip and no other operation ran."""
    mine = collective_spans(chip, stem)
    rest = union((e.start, e.end) for e in chip.ops
                 if not e.opcode.startswith(stem))
    return total(clip(subtract(mine, rest), lo, hi))


def idle_gaps(chip: ChipTrace, lo: float, hi: float):
    """The idle intervals of [lo, hi] on this chip, each with the program
    that ran before it: ``[(start, end, after_module)]``."""
    busy = clip(union((e.start, e.end) for e in chip.ops), lo, hi)
    gaps = subtract([(lo, hi)], busy)
    out, j, last = [], 0, "start"
    for s, e in gaps:
        while j < len(chip.modules) and chip.modules[j].start <= s:
            last = chip.modules[j].name
            j += 1
        out.append((s, e, last))
    return out


def module_name(name: str) -> str:
    """``jit_core(1234567)`` -> ``jit_core``."""
    return re.sub(r"\(\d+\)$", "", name)


def breakdown(trace: Trace, lo: float, hi: float, *, sync_mark: str | None,
              top: int = 10) -> dict:
    """The ten device operations that took most of [lo, hi] on chip 0, and
    the idle time by what the host was doing: a gap that holds (or ends
    within 2 ms before) one of the benchmark's ``sync_mark`` annotations is
    that annotation's; every other gap is ``host:unattributed`` after the
    program that ran before it (the program has no annotations of its own
    yet — PERF.md §7)."""
    chip = trace.chips[0]
    ops = sorted(op_seconds(chip, lo, hi).items(), key=lambda kv: -kv[1])
    marks = [m.start for m in trace.marks if sync_mark and m.name == sync_mark]
    by_label: dict[str, float] = {}
    for s, e, after in idle_gaps(chip, lo, hi):
        if any(s <= t <= e + 2e-3 for t in marks):
            label = sync_mark
        else:
            label = f"host:unattributed after {module_name(after)}"
        by_label[label] = by_label.get(label, 0.0) + (e - s)
    gaps = sorted(by_label.items(), key=lambda kv: -kv[1])
    return {"device_ops": [[k, v] for k, v in ops[:top]],
            "idle_gaps": [[k, v] for k, v in gaps[:top]]}


def traced_window(result: dict, cell):
    """What every trace reader starts from: ``(trace, lo, hi, chips)``, or
    None when the run left no device trace (the reader then returns None)."""
    trace, span = result.get("trace"), result.get("trace_window")
    if trace is None or span is None or not trace.chips:
        return None
    return trace, span[0], span[1], trace.chips[:cell.chips]


def idle_share(result: dict, cell):
    """Percent of the traced window in which no operation ran, mean over the
    chips used; None without a device trace."""
    w = traced_window(result, cell)
    if w is None:
        return None
    _, lo, hi, chips = w
    busy = sum(busy_seconds(c, lo, hi) for c in chips) / len(chips)
    return 100.0 * (1.0 - busy / (hi - lo))


def kernel_seconds_per_step(result: dict, cell):
    """Seconds per optimizer step chip 0 spent in Mosaic kernels (leaves
    whose opcode is ``custom-call``); None without a trace or steps."""
    w = traced_window(result, cell)
    if w is None or not result.get("steps"):
        return None
    _, lo, hi, chips = w
    return opcode_seconds(chips[0], lo, hi, "custom-call") / result["steps"]
