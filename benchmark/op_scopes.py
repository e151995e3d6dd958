"""Per-layer device time of the train program: the program's op-to-scope
map joined with the device trace.

A ``--profile-dir`` run of the program writes ``op_scopes.json`` beside its
trace (`lstm_tensorspark_tpu/utils/op_scopes.py`): for each train program
it ran, keyed by the name the trace's ``XLA Modules`` line gives it
(``jit_core``), the device scope of each instruction — ``embed``, ``lstm``,
``head``, ``optimizer`` or ``dp_sync`` — under ``"ops"``, and the fusions
that hold more than one scope under ``"mixed"``. `train_cell.py` points
``--profile-dir`` at ``<workdir>/profile``, where the map is read.

The trace's events carry no metadata, so an operation is joined to its
program by time: chip 0's leaf operations (and asynchronous spans) that
start inside a run of a mapped program, clipped to the traced window. Each
takes its instruction's scope, or where the map names none and the label
holds a kernel's stem, that kernel's layer (``lstm_rec_`` the LSTM stack,
``lm_head_`` the head). Times are unions of intervals, per optimizer step.
Without a trace or a map (a program that writes none) everything here
returns None.
"""

from __future__ import annotations

import json
import os

import trace_reduce

FILE = "op_scopes.json"
RECURRENCE = "lstm_rec_"
KERNEL_LAYERS = {RECURRENCE: "lstm", "lm_head_": "head"}


def load(cell) -> dict | None:
    """The map the traced run wrote, or None."""
    path = os.path.join(cell.workdir, "profile", FILE)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def _in_runs(events, runs):
    """``(event, module)`` for each event (sorted by start) that starts
    inside one of ``runs`` (sorted, disjoint ``(start, end, module)``)."""
    out, j = [], 0
    for e in events:
        while j < len(runs) and runs[j][1] <= e.start:
            j += 1
        if j < len(runs) and runs[j][0] <= e.start:
            out.append((e, runs[j][2]))
    return out


def scoped(result, cell):
    """``(lo, hi, ops, spans)``: chip 0's leaf operations and asynchronous
    spans inside the mapped programs' runs, each as ``(event, scope)`` with
    scope None where the operation is under none; None without a trace, a
    step count or a map."""
    w = trace_reduce.traced_window(result, cell)
    if w is None or not result.get("steps"):
        return None
    scopes = load(cell)
    if not scopes:
        return None
    _, lo, hi, chips = w
    chip = chips[0]
    runs = [(m.start, m.end, name) for m in chip.modules
            if (name := trace_reduce.module_name(m.name)) in scopes]
    if not runs:
        return None

    def scope_of(e, module):
        s = scopes[module]["ops"].get(e.label)
        if s is None:
            s = next((layer for stem, layer in KERNEL_LAYERS.items()
                      if stem in e.label), None)
        return s

    def pick(events):
        return [(e, scope_of(e, m)) for e, m in _in_runs(events, runs)]

    return lo, hi, pick(chip.ops), pick(chip.async_ops)


def ms_per_step(result, cell, keep, *, spans: bool = False):
    """Milliseconds per optimizer step of the traced window covered by the
    operations ``keep(event, scope)`` selects (and by the asynchronous
    spans it selects, with ``spans``); None where it selects nothing."""
    s = scoped(result, cell)
    if s is None:
        return None
    lo, hi, ops, async_spans = s
    chosen = [e for e, scope in ops + (async_spans if spans else [])
              if keep(e, scope)]
    if not chosen:
        return None
    t = trace_reduce.total(trace_reduce.clip(
        trace_reduce.union((e.start, e.end) for e in chosen), lo, hi))
    return 1e3 * t / result["steps"]


def in_scope(name: str):
    """``keep`` for the operations of one scope."""
    return lambda e, scope: scope == name


def breakdown(result, cell) -> dict | None:
    """Per step: ms by scope (``unscoped``: under none), the busy time of
    the mapped runs, the twelve longest mixed fusions' ms with their scopes
    and the eight longest unscoped operations' ms: the table of PERF.md
    section 5."""
    s = scoped(result, cell)
    if s is None:
        return None
    lo, hi, ops, _ = s
    steps = result["steps"]

    def ms(events):
        return 1e3 * trace_reduce.total(trace_reduce.clip(
            trace_reduce.union((e.start, e.end) for e in events), lo, hi)) / steps

    def grouped(key, keep=lambda e, scope: True):
        out: dict = {}
        for e, scope in ops:
            if keep(e, scope):
                out.setdefault(key(e, scope), []).append(e)
        return out

    mixed = {k: v for entry in load(cell).values()
             for k, v in entry.get("mixed", {}).items()}
    by_scope = grouped(lambda e, scope: scope or "unscoped")
    unscoped = grouped(lambda e, _: e.label, lambda e, scope: scope is None)
    in_mixed = grouped(lambda e, _: e.label, lambda e, _: e.label in mixed)

    def largest(groups, n):
        return sorted(((k, ms(v)) for k, v in groups.items()),
                      key=lambda kv: -kv[1])[:n]

    return {"ms_per_step": {k: ms(v) for k, v in sorted(by_scope.items())},
            "busy_ms_per_step": ms([e for e, _ in ops]),
            "mixed_fusions_ms_per_step": {
                k: [t, mixed[k]] for k, t in largest(in_mixed, 12)},
            "largest_unscoped_ms_per_step": dict(largest(unscoped, 8))}
