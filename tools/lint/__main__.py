"""graftlint CLI: ``python -m tools.lint [paths...]``.

Default paths are the production tree (``lstm_tensorspark_tpu/`` +
``tools/``); tests pass fixture directories instead. Exit codes come
from the one table (resilience/exit_codes.py):

- 0  — no findings outside the baseline;
- 3  — REGRESSION_RC: new findings (the verify.sh gate);
- 2  — USAGE_RC: bad flags/paths.

``--update-baseline`` rewrites tools/lint_baseline.txt to the current
finding set (keeping existing justifications; new entries get a TODO a
human must replace). ``--json PATH`` writes the machine-readable
report; when a previous report exists at the
same path the summary line grows per-rule ``d(rule)=±k`` deltas vs it.
``--changed GIT_REF`` is the sub-second pre-commit mode: only files
changed vs the ref plus their importers (from the project model) are
analyzed — verify.sh phase 0 keeps the full-tree run.
"""

from __future__ import annotations

import argparse
import os
import sys

_HERE = os.path.dirname(os.path.abspath(__file__))
_REPO = os.path.dirname(os.path.dirname(_HERE))
if _REPO not in sys.path:
    sys.path.insert(0, _REPO)

from tools.lint import RULES, core, model  # noqa: E402

DEFAULT_PATHS = ("lstm_tensorspark_tpu", "tools")
DEFAULT_BASELINE = os.path.join(_REPO, "tools", "lint_baseline.txt")


def _changed_files(ref: str, root: str) -> set[str] | None:
    """Repo-relative ``.py`` files changed vs ``ref``: the diff (incl.
    working-tree edits) PLUS untracked files — a brand-new module is
    exactly the one most likely to carry fresh violations, and a plain
    ``git diff`` would hide it until ``git add``. None (-> USAGE_RC)
    when git cannot answer."""
    import subprocess
    files: set[str] = set()
    for cmd in (["git", "-C", root, "diff", "--name-only", ref, "--",
                 "*.py"],
                ["git", "-C", root, "ls-files", "--others",
                 "--exclude-standard", "--", "*.py"]):
        try:
            out = subprocess.run(cmd, capture_output=True, text=True,
                                 timeout=30)
        except (OSError, subprocess.TimeoutExpired) as e:
            print(f"lint: --changed: {' '.join(cmd[3:5])} failed: {e}",
                  file=sys.stderr)
            return None
        if out.returncode != 0:
            print(f"lint: --changed: {' '.join(cmd[3:5])} vs {ref!r} "
                  f"failed: {out.stderr.strip()}", file=sys.stderr)
            return None
        files.update(ln.strip().replace(os.sep, "/")
                     for ln in out.stdout.splitlines() if ln.strip())
    return files


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m tools.lint",
        description="AST invariant analyzer (see docs/LINT.md)")
    ap.add_argument("paths", nargs="*",
                    help="files/dirs to analyze (default: "
                         "lstm_tensorspark_tpu/ tools/)")
    ap.add_argument("--baseline", default=DEFAULT_BASELINE,
                    help="accepted-findings file (default: "
                         "tools/lint_baseline.txt)")
    ap.add_argument("--no-baseline", action="store_true",
                    help="ignore the baseline: exit 3 on ANY finding "
                         "(fixture tests)")
    ap.add_argument("--update-baseline", action="store_true",
                    help="rewrite the baseline to the current finding set")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids to run (default: all)")
    ap.add_argument("--list-rules", action="store_true",
                    help="print the rule catalogue and exit")
    ap.add_argument("--json", default=None, metavar="PATH",
                    help="write a machine-readable findings report")
    ap.add_argument("--root", default=None,
                    help="repo root for relative finding paths (default: "
                         "inferred; fixture tests pass the fixture dir)")
    ap.add_argument("--changed", default=None, metavar="GIT_REF",
                    help="scoped pre-commit mode: lint only files "
                         "changed vs GIT_REF plus their importers from "
                         "the project model (verify.sh phase 0 keeps the "
                         "full-tree run)")
    args = ap.parse_args(argv)

    if args.list_rules:
        for rule_id in sorted(RULES):
            print(f"{rule_id}: {RULES[rule_id].doc}")
        return 0

    only = None
    if args.rules:
        only = {r.strip() for r in args.rules.split(",") if r.strip()}
        unknown = only - set(RULES)
        if unknown:
            print(f"lint: unknown rule(s): {', '.join(sorted(unknown))}",
                  file=sys.stderr)
            return core.USAGE_RC

    paths = args.paths or [os.path.join(_REPO, p) for p in DEFAULT_PATHS]
    for p in paths:
        if not os.path.exists(p):
            print(f"lint: no such path: {p}", file=sys.stderr)
            return core.USAGE_RC
    root = os.path.abspath(args.root) if args.root else _REPO

    project = model.load_project(paths, root)
    baseline = {} if args.no_baseline else core.load_baseline(args.baseline)
    if args.changed is not None:
        if args.update_baseline:
            # write_baseline rewrites the WHOLE file from the current
            # finding set — under a scoped run that would silently drop
            # every out-of-scope entry and its hand-written
            # justification, then fail the next full-tree gate
            print("lint: --changed cannot be combined with "
                  "--update-baseline (the rewrite needs the full-tree "
                  "finding set)", file=sys.stderr)
            return core.USAGE_RC
        changed = _changed_files(args.changed, root)
        if changed is None:
            return core.USAGE_RC
        scope = model.changed_closure(project, changed)
        project = model.Project(
            [m for m in project.modules if m.rel in scope])
        # rules that need the full project universe (the metrics rule's
        # docs-runbook check) consult this to stay silent in scoped mode
        project.scoped = True
        # baseline entries for files outside the scope are neither
        # judged nor reported retired — this run never analyzed them
        baseline = {k: v for k, v in baseline.items()
                    if k.split(":", 1)[0] in scope}
        print(f"lint: --changed {args.changed}: {len(changed)} changed "
              f"file(s), {len(scope)} analyzed with importers",
              file=sys.stderr)
    findings = core.run_rules(project, only)

    if args.update_baseline:
        # ALWAYS read the file here, even under --no-baseline: the rewrite
        # must preserve existing hand-written justifications
        core.write_baseline(args.baseline, findings,
                            core.load_baseline(args.baseline))
        print(f"lint: baseline updated ({len(findings)} entries) — fill in "
              "any TODO justifications")
        # an intentional rewrite is not a regression (tier1_diff contract)
        core.report(findings, {f.key(): "" for f in findings},
                    json_path=args.json)
        return 0

    new, _retired = core.report(findings, baseline, json_path=args.json,
                                scoped=args.changed is not None)
    return core.REGRESSION_RC if new else 0


if __name__ == "__main__":
    raise SystemExit(main())
