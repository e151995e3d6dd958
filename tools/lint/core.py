"""graftlint core: findings, the rule registry, inline suppressions, and
the baseline diff gate.

The gate is modeled on tools/tier1_diff.py: a checked-in baseline
(tools/lint_baseline.txt) records accepted findings WITH a written
justification each, and the exit code is ``REGRESSION_RC`` (3, imported
from resilience/exit_codes.py — the one table) only on NEW findings.
Fixing a finding makes the run report it as retired (tighten with
``--update-baseline``); introducing one fails ``tools/verify.sh`` before
the timed tier-1 suite ever starts.

Finding identity is ``path:rule:fingerprint`` — no line number, so an
unrelated edit shifting lines never churns the baseline. The fingerprint
is the stable part of the message (rules keep names/identifiers in it,
not positions).

Suppression: append ``# graftlint: disable=<rule-id>[,<rule-id>...]`` to
the offending line. Suppressions are for findings the code is RIGHT to
trigger on generically but wrong here for a stated reason — put the
reason in a comment next to the pragma (docs/LINT.md has the policy).
"""

from __future__ import annotations

import ast
import dataclasses
import json
import re
import sys

from .model import ModuleInfo, Project

# the shared regression exit code — resilience/exit_codes.py is the one
# authority (tools/tier1_diff.py routes on the same constant)
from lstm_tensorspark_tpu.resilience.exit_codes import (  # noqa: E402
    REGRESSION_RC,
    USAGE_RC,
)

__all__ = [
    "Finding", "Rule", "RULES", "register", "run_rules",
    "load_baseline", "write_baseline", "suppressed",
    "REGRESSION_RC", "USAGE_RC",
]


@dataclasses.dataclass(frozen=True)
class Finding:
    rule: str          # rule id (kebab-case)
    rel: str           # repo-relative path
    line: int          # 1-based, for the human report only
    message: str       # one line, stable identifiers only

    def key(self) -> str:
        """Baseline identity — line-number free (see module docstring)."""
        return f"{self.rel}:{self.rule}:{self.message}"

    def render(self) -> str:
        return f"{self.rel}:{self.line}: {self.rule} {self.message}"


class Rule:
    """One invariant. Subclasses set ``id``/``doc`` and implement
    :meth:`run` returning findings over the whole project (rules are
    project-scoped, not file-scoped: lock graphs and warmup reachability
    span modules)."""

    id: str = ""
    doc: str = ""

    def run(self, project: Project) -> list[Finding]:  # pragma: no cover
        raise NotImplementedError


RULES: dict[str, Rule] = {}


def register(rule_cls: type[Rule]) -> type[Rule]:
    rule = rule_cls()
    if not rule.id:
        raise ValueError(f"{rule_cls.__name__} has no id")
    if rule.id in RULES:
        raise ValueError(f"duplicate rule id {rule.id!r}")
    RULES[rule.id] = rule
    return rule_cls


_PRAGMA_RE = re.compile(r"#\s*graftlint:\s*disable=([a-z0-9_,\- ]+)")


def _pragma_spans(module: ModuleInfo) -> list[tuple[int, int]]:
    """Header spans a suppression must cover as a unit: a def/class's
    decorator-to-signature block and a (possibly multi-line) ``with``
    header. A pragma anywhere in the span — or on the line above it —
    suppresses findings attributed to any line of the span, so
    ``# graftlint: disable=`` above a decorated ``def`` (whose physical
    line-above is the last decorator) and inside a wrapped ``with``
    header both work. Cached on the module (one AST pass)."""
    spans = getattr(module, "_graftlint_pragma_spans", None)
    if spans is not None:
        return spans
    spans = []
    for node in ast.walk(module.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            start = min([d.lineno for d in node.decorator_list]
                        + [node.lineno])
            end = node.body[0].lineno - 1 if node.body else node.lineno
            spans.append((start, max(start, end)))
        elif isinstance(node, (ast.With, ast.AsyncWith)):
            end = node.body[0].lineno - 1 if node.body else node.lineno
            if end > node.lineno:  # multi-line header only
                spans.append((node.lineno, end))
    module._graftlint_pragma_spans = spans  # type: ignore[attr-defined]
    return spans


def _pragma_names(module: ModuleInfo, line: int) -> set[str]:
    m = _PRAGMA_RE.search(module.line(line))
    if not m:
        return set()
    return {r.strip() for r in m.group(1).split(",")}


def suppressed(module: ModuleInfo, line: int, rule_id: str) -> bool:
    """True when the finding's line, the line above it, or — for
    findings inside a decorated-def / multi-line-``with`` header span —
    any line of that span (or the line above the span) carries a
    disable pragma naming the rule."""
    candidates = {line, line - 1}
    for start, end in _pragma_spans(module):
        if start <= line <= end:
            candidates.update(range(start - 1, end + 1))
    return any(rule_id in _pragma_names(module, ln) for ln in candidates)


def run_rules(project: Project,
              only: set[str] | None = None) -> list[Finding]:
    findings: list[Finding] = []
    for rule_id in sorted(RULES):
        if only is not None and rule_id not in only:
            continue
        for f in RULES[rule_id].run(project):
            module = project.by_rel.get(f.rel)
            if module is not None and suppressed(module, f.line, f.rule):
                continue
            findings.append(f)
    findings.sort(key=lambda f: (f.rel, f.line, f.rule, f.message))
    return findings


# ---- baseline ----------------------------------------------------------

_BASELINE_HEADER = """\
# graftlint baseline (tools/lint/core.py) — accepted findings.
#
# Format: one `path:rule:fingerprint` per line; everything after ` # ` is
# the REQUIRED one-line justification for accepting instead of fixing.
# The gate (verify.sh) exits REGRESSION_RC only on findings NOT listed
# here. Tighten with `python -m tools.lint --update-baseline` after
# fixing entries; never add one without a justification.
"""


def load_baseline(path: str) -> dict[str, str]:
    """{finding key: justification}. Missing file = empty baseline."""
    out: dict[str, str] = {}
    try:
        with open(path, encoding="utf-8") as f:
            lines = f.read().splitlines()
    except OSError:
        return out
    for ln in lines:
        ln = ln.strip()
        if not ln or ln.startswith("#"):
            continue
        key, _, just = ln.partition(" # ")
        out[key.strip()] = just.strip()
    return out


def write_baseline(path: str, findings: list[Finding],
                   old: dict[str, str]) -> None:
    """Rewrite the baseline to the current finding set, keeping existing
    justifications and marking new entries for a human to justify."""
    with open(path, "w", encoding="utf-8") as f:
        f.write(_BASELINE_HEADER)
        for finding in findings:
            key = finding.key()
            just = old.get(key, "TODO: justify or fix")
            f.write(f"{key} # {just}\n")


# ---- report ------------------------------------------------------------

def report(findings: list[Finding], baseline: dict[str, str],
           *, json_path: str | None = None, scoped: bool = False,
           out=None) -> tuple[list[Finding], list[str]]:
    """Print the human report; return (new findings, retired keys)."""
    if out is None:
        out = sys.stdout  # resolved at call time (test capture works)
    new = [f for f in findings if f.key() not in baseline]
    current_keys = {f.key() for f in findings}
    retired = sorted(k for k in baseline if k not in current_keys)
    for f in findings:
        tag = "" if f.key() in baseline else " [NEW]"
        print(f.render() + tag, file=out)
    for k in retired:
        print(f"retired (fixed — tighten with --update-baseline): {k}",
              file=out)
    deltas = ""
    if json_path:
        # per-rule deltas vs the PREVIOUS report at this path, when one
        # exists (verify.sh writes the untracked LINT_report.json in place
        # each run, so the summary line trends finding movement).
        # Scoped (--changed) runs neither compute deltas nor count as a
        # trend point: partial counts vs full-tree counts would print
        # large spurious deltas either way — the scoped flag in the
        # payload tells the next full run to skip the comparison.
        prev = None
        try:
            with open(json_path, encoding="utf-8") as f:
                prev = json.load(f)
        except (OSError, ValueError):
            prev = None
        by_rule = _by_rule(findings)
        prev_by_rule = prev.get("by_rule") if isinstance(prev, dict) \
            else None
        if (not scoped and isinstance(prev_by_rule, dict)
                and not prev.get("scoped")):
            parts = []
            for rule in sorted(set(by_rule) | set(prev_by_rule)):
                d = by_rule.get(rule, 0) - int(prev_by_rule.get(rule, 0))
                if d:
                    parts.append(f"d({rule})={d:+d}")
            if parts:
                deltas = " " + " ".join(parts)
        payload = {
            "findings": [dataclasses.asdict(f) | {"key": f.key(),
                                                  "new": f.key() not in
                                                  baseline}
                         for f in findings],
            "new": len(new),
            "baseline": len(baseline),
            "retired": retired,
            "by_rule": by_rule,
            "scoped": scoped,
        }
        with open(json_path, "w", encoding="utf-8") as f:
            json.dump(payload, f, indent=2, sort_keys=True)
            f.write("\n")
    # the one summary line tools/verify.sh surfaces for its GRAFTLINT phase
    print(f"GRAFTLINT new={len(new)} baseline={len(baseline)}" + deltas,
          file=out)
    return new, retired


def _by_rule(findings: list[Finding]) -> dict[str, int]:
    out: dict[str, int] = {}
    for f in findings:
        out[f.rule] = out.get(f.rule, 0) + 1
    return out
