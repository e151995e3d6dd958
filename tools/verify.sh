#!/usr/bin/env bash
# One-command repo verify: graftlint gate + tier-1 + regression gate +
# serve smoke, in that order.
#
# Phase 0 — GRAFTLINT: `python -m tools.lint` (AST invariant analyzer,
# docs/LINT.md) over lstm_tensorspark_tpu/ + tools/, gated on
# tools/lint_baseline.txt. Prints its own `GRAFTLINT new=N baseline=M`
# summary line — with per-rule `d(rule)=±k` deltas vs the previous
# LINT_report.json when one exists (an untracked local file, listed in
# .gitignore, rewritten in place each run) — and exits REGRESSION_RC
# (3) on NEW findings — the run aborts HERE, before the ~30 min suite,
# because a lint regression is a deterministic fail and the feedback
# should be seconds, not minutes (phase-0 budget: 10 s; see
# docs/OPERATIONS.md). Pure CPU/AST, sequenced BEFORE the timed suite
# so it cannot perturb it.
#
# Phase 1 — tier-1: the ROADMAP.md "Tier-1 verify" line exactly (same
# timeout, same pytest flags, same DOTS_PASSED accounting), then gated
# on tools/tier1_diff.py — which diffs the failing-test SET against
# tools/tier1_baseline.txt and exits 3 (REGRESSION_RC) only on NEW
# failures. The raw pytest rc is reported but NOT the verdict: the seed
# tree carries ~75 known-environmental failures.
#
# Phase 2 — serve smoke: tools/serve_smoke.py boots the real
# `cli serve --http --replicas 2` subprocess and validates the /healthz
# replica fan-in, routed /v1/generate replies, /stats router+replica
# sections, and the replica-labelled /metrics Prometheus exposition;
# then the restart drill — kept session, disk-tier checkpoint awaited,
# SIGKILL, fresh boot on the same --session-dir, continuation served
# from the disk tier (runs AFTER the timed suite on purpose — never
# concurrently with it).
#
# Phase 3 — serve chaos drill: tools/chaos_serve.py machine-checks the
# robustness invariants under INJECTED faults (replica death loses zero
# kept sessions token-identically; disk errors lose durability but
# never correctness; corrupt session files quarantine + fail honestly;
# priority p99 TTFT holds its SLO under a 4x burst while best-effort
# sheds with honest Retry-After 429s; a blackholed remote host opens
# its circuit, is routed around losing nothing, and REJOINS on heal
# with replay-deduped exactly-once generates); its report is the JSON
# line it prints — sequenced after the smoke, never concurrent with the
# timed suite; ~60 s budget, 900 s hard cap.
#
# Nothing here writes a tracked file: after a run `git status` shows
# what it showed before.
#
# Usage: tools/verify.sh        (from anywhere; cd's to the repo root)
# Exit:  graftlint's code on lint regressions (3), else tier1_diff's on
#        gate failure (3 regression, 2 usage, 76 liveness), else the
#        serve smoke's, else the chaos drill's (0 ok, 1 fail).
#
# Run it with nothing else executing: CPU contention flakes the
# convergence-threshold tests (ROADMAP.md).
set -o pipefail
cd "$(dirname "$0")/.." || exit 2

python -m tools.lint --json LINT_report.json
lint_rc=$?
if [ "$lint_rc" -ne 0 ]; then
  echo "verify: graftlint gate failed (rc=$lint_rc) — fix or baseline" \
       "with a justification (docs/LINT.md) before running the suite"
  exit "$lint_rc"
fi

rm -f /tmp/_t1.log
timeout -k 10 1800 env JAX_PLATFORMS=cpu python -m pytest tests/ -q -m 'not slow' \
  --continue-on-collection-errors -p no:cacheprovider -p no:xdist -p no:randomly \
  2>&1 | tee /tmp/_t1.log
rc=${PIPESTATUS[0]}
echo "DOTS_PASSED=$(grep -aE '^[.FEsx]+( *\[ *[0-9]+%\])?$' /tmp/_t1.log | tr -cd . | wc -c)"
echo "pytest raw rc=$rc (informational; the baseline diff below is the gate)"

python tools/tier1_diff.py --log /tmp/_t1.log
gate=$?
if [ "$gate" -ne 0 ]; then
  exit "$gate"
fi

# 900 s > the smoke's own worst-case internal budget (4x 180 s boot
# waits — main + restart + pallas + mesh boots — + generates + GETs +
# 30 s checkpoint wait) so its failure diagnostics always print before
# the outer kill fires
JAX_PLATFORMS=cpu timeout -k 10 900 python tools/serve_smoke.py
smoke=$?
if [ "$smoke" -ne 0 ]; then
  exit "$smoke"
fi

# serve chaos drill (sequenced after the smoke — never concurrent with
# the timed suite): ~60 s measured. The 900 s cap covers the host_die
# AND partition phases' worst-case internal budgets on a loaded box
# (each boots a 180 s replica-host subprocess + 30 s checkpoint wait,
# plus host_die's 15 s retirement wait and partition's 25 s circuit-
# open + 20 s rejoin waits on top of the ~30 s fault phases) so the
# drill's failure diagnostics always print before the outer kill
# fires.
JAX_PLATFORMS=cpu timeout -k 10 900 python tools/chaos_serve.py
exit $?
