"""The op-to-scope map a ``--profile-dir`` run writes beside its trace.

A device trace names each operation by its HLO instruction (``fusion.539``,
``lstm_rec_fwd_tiled.24``) and carries no metadata, so the device scopes of
`utils/tracing.SCOPES` cannot be read from the trace alone. The compiled
executable has them: every instruction's
``metadata={op_name="jit(core)/while/body/transpose(jvp(head))/dot_general"}``.
After the profiler has stopped, the CLI lowers the train step again on the
arguments of its last dispatch (`LastCall`) and `write` compiles it — JAX
hands back the executable that ran from its in-memory cache, so nothing
compiles — and writes ``<profile-dir>/op_scopes.json``::

    {"jit_core": {"ops":   {"fusion.539": "head", "lstm_rec_bwd.24": "lstm", ...},
                  "mixed": {"fusion.539": ["head", "optimizer"], ...}}}

keyed by the program's name as the trace's ``XLA Modules`` line has it. An
instruction's scope is the innermost name of the set in its ``op_name``
(a collective inside the optimizer is ``dp_sync``); one under none is left
out. A fusion takes its own ``op_name``'s scope, or where that has none the
scope most of its fused operations carry; a fusion whose operations hold
more than one scope is also listed under ``mixed`` with all of them.
"""

from __future__ import annotations

import collections
import json
import os
import re

from .tracing import SCOPES

FILE = "op_scopes.json"

_MODULE = re.compile(r"^HloModule ([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%([\w.\-]+) = (.*)$")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%([\w.\-]+)")
# the opcode is the first lower-case word before "(" (layouts hold "T(8,128)")
_OPCODE = re.compile(r"(?<![\w.-])([a-z][a-z0-9-]*)\(")
# how JAX writes a scope under a transformation: transpose(jvp(head))
_TRANSFORMED = re.compile(r"^(?:jvp|transpose|vmap|remat|checkpoint)\((.*)\)$")


def scope_of(op_name: str) -> str | None:
    """The innermost name of `SCOPES` in an ``op_name`` path, or None."""
    found = None
    for part in op_name.split("/"):
        while (m := _TRANSFORMED.match(part)):
            part = m.group(1)
        if part in SCOPES:
            found = part
    return found


def parse(hlo: str) -> tuple[str | None, dict, dict]:
    """``(module, ops, mixed)`` of one compiled module's text: the module's
    name, ``{label: scope}`` for the instructions that can run as operations
    (those outside fused computations), and ``{label: [scopes]}`` for the
    fusions that hold more than one scope."""
    module, current = None, None
    computations: dict[str, list] = {}
    for line in hlo.splitlines():
        if (m := _INSTRUCTION.match(line)) and current is not None:
            label, rest = m.groups()
            opcode = _OPCODE.search(rest)
            name = _OP_NAME.search(rest)
            calls = _CALLS.search(rest)
            computations[current].append((
                label, opcode.group(1) if opcode else "",
                scope_of(name.group(1)) if name else None,
                calls.group(1) if calls else None))
        elif module is None and (m := _MODULE.match(line)):
            module = m.group(1)
        elif line.endswith("{") and (m := _COMPUTATION.match(line)):
            current = m.group(1)
            computations[current] = []
    fused = {callee for body in computations.values()
             for _, opcode, _, callee in body if opcode == "fusion" and callee}

    def inside(name: str, counts: collections.Counter) -> collections.Counter:
        for _, opcode, scope, callee in computations.get(name, ()):
            if scope:
                counts[scope] += 1
            if opcode == "fusion" and callee:
                inside(callee, counts)
        return counts

    ops, mixed = {}, {}
    for name, body in computations.items():
        if name in fused:
            continue
        for label, opcode, scope, callee in body:
            if opcode == "fusion" and callee:
                counts = inside(callee, collections.Counter())
                held = set(counts) | ({scope} if scope else set())
                if len(held) > 1:
                    mixed[label] = sorted(held)
                if not scope and counts:
                    scope = counts.most_common(1)[0][0]
            if scope:
                ops[label] = scope
    return module, ops, mixed


class LastCall:
    """``step(state, *rest)`` that keeps ``rest`` of its last dispatch, so
    that the program it ran can be lowered again on the state it handed
    back (the state it was handed is donated)."""

    def __init__(self, step):
        self.step, self.rest = step, None

    def __call__(self, state, *rest):
        self.rest = rest
        return self.step(state, *rest)

    def lower(self, state):
        """The step's ``Lowered`` program, or None where the step was never
        dispatched or cannot be lowered (a plain function)."""
        if self.rest is None or not hasattr(self.step, "lower"):
            return None
        return self.step.lower(state, *self.rest)


def bind(program, arguments):
    """A train step that dispatches ``program(*arguments(*a))`` and lowers
    the same way: `LastCall` can then lower a step whose staged arrays the
    caller closes over."""

    def step(*a):
        return program(*arguments(*a))

    step.lower = lambda *a: program.lower(*arguments(*a))
    return step


def write(directory: str, lowered) -> dict:
    """Compile a ``Lowered`` program (the executable already in memory),
    write ``<directory>/op_scopes.json`` and return what it holds."""
    module, ops, mixed = parse(lowered.compile().as_text())
    out = {module: {"ops": ops, "mixed": mixed}}
    os.makedirs(directory, exist_ok=True)
    with open(os.path.join(directory, FILE), "w") as f:
        json.dump(out, f)
    return out
