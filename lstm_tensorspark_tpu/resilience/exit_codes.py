"""The process exit-code table — ONE authority for every failure class.

These codes are a cross-process protocol: the training CLI, the supervisor
and the verification tooling (tools/tier1_diff.py, tools/lint) all route on
them, so they live in a module with NO third-party imports (the supervisor
and shell tooling must be able to read them without initialising a
backend). A table, not inline literals: two gates once shared one literal
and their callers had to scan stdout to tell them apart — dedicated,
documented codes make the routing structural.

| code | name            | meaning                                          | retry? |
|------|-----------------|--------------------------------------------------|--------|
| 2    | USAGE_RC        | argparse/flag-validation error (deterministic)   | no     |
| 3    | REGRESSION_RC   | a verification gate found NEW failures           | no     |
|      |                 | (tools/tier1_diff.py, tools/lint)                |        |
| 76   | LIVENESS_RC     | a bounded run did not finish inside its window   | yes    |
|      |                 | (tools/tier1_diff.py: the tier-1 suite timed     |        |
|      |                 | out) — there is no verdict, run it again         |        |
| 77   | ANOMALY_RC      | train loop aborted after K consecutive           | yes    |
|      |                 | non-finite (NaN/Inf) steps: restart from         |        |
|      |                 | checkpoint (updates were skipped, params clean)  |        |
| 78   | POISON_RC       | supervisor gave up: restarts are not advancing   | no     |
|      |                 | the restored checkpoint step (crash loop)        |        |
| 81   | FAULT_CRASH_RC  | injected process crash (resilience/faults.py     | yes    |
|      |                 | drill) — retryable by construction               |        |

``RETRYABLE_RCS`` is the set the supervisor must relaunch even when the
child died fast (its sub-second "deterministic failure" heuristic must not
eat them): these codes are emitted deliberately by code that EXPECTS a
restart-from-checkpoint to make progress.
"""

USAGE_RC = 2
REGRESSION_RC = 3
LIVENESS_RC = 76
ANOMALY_RC = 77
POISON_RC = 78
FAULT_CRASH_RC = 81

RETRYABLE_RCS = frozenset({LIVENESS_RC, ANOMALY_RC, FAULT_CRASH_RC})
