"""Crash supervisor: relaunch training until completion, resuming from the
latest checkpoint.

Reference parity: SURVEY.md §5 "Failure detection / elastic recovery" — the
reference inherits lineage-based task retry from Spark (a failed partition's
task is re-run automatically) but loses the whole run on a driver crash.
XLA has no partition-retry equivalent (the step is one program), so the
rebuild's fault story is checkpoint-restart; this module closes the loop by
supervising the process the way Spark's driver supervises tasks:

    python -m lstm_tensorspark_tpu.supervise --max-restarts 3 -- \
        --dataset ptb_char --num-steps 10000 \
        --checkpoint-dir ckpt --checkpoint-every 50

The child is the normal CLI (same flags). On a nonzero exit the supervisor
relaunches it with ``--resume`` injected, so the run continues from the
last checkpoint; ``--num-steps`` is resume-inclusive (cli.py), so the total
step budget holds across restarts. Exit code: the child's final exit code —
0 on success, or the LAST failing child's code when restarts are exhausted
(so callers can still distinguish failure classes, e.g. OOM kills).

Stall detection (``--stall-timeout N``): crashes are not the only failure
mode — a child can also hang forever without exiting (a dispatch or a
collective that never returns). With a stall timeout the supervisor watches the child's output: if no line
arrives for N seconds it terminates the child (SIGTERM, then SIGKILL) and
treats it like a signal death — retryable, relaunched with ``--resume``.
Size N well above the longest silent phase of the run (first XLA compile +
the --log-every cadence).

Serving children: ``supervise -- serve --http --session-dir d ...``
relaunches a crashed server WITHOUT injecting ``--resume`` (a training
flag serve's parser rejects); clients' kept sessions survive the restart
through serve's own disk tier (``--session-dir``), resuming
token-identically from their last completed request.

Self-healing (resilience plane): restart delays back off exponentially
with jitter (--restart-delay is the base, --max-delay the cap); known
retryable exit codes (resilience/exit_codes.py: anomaly aborts, injected
crash drills) always relaunch; and a forward-progress check declares the
run POISONED (dedicated exit code) when consecutive failures stop
advancing the latest checkpoint step — the crash-loop case a fixed retry
budget would grind through pointlessly. Drills: arm
``--faults``/``LSTM_TSP_FAULTS`` on the child (resilience/faults.py) or
run tools/chaos_smoke.py.
"""

from __future__ import annotations

import argparse
import os
import subprocess
import sys
import threading
import time

from . import obs
from .resilience import ckpt_layout
from .resilience.backoff import backoff_delay
from .resilience.exit_codes import POISON_RC, RETRYABLE_RCS, USAGE_RC

__all__ = ["backoff_delay", "supervise", "main"]  # backoff_delay is
# re-exported on purpose: it moved to resilience/backoff.py (the serve
# loadgen's 429 retry path shares the one implementation) and existing
# callers/tests keep importing it from here.


def build_parser() -> argparse.ArgumentParser:
    p = argparse.ArgumentParser(
        prog="lstm_tensorspark_tpu.supervise",
        description="relaunch-on-crash wrapper around the training CLI",
    )
    p.add_argument("--max-restarts", type=int, default=3,
                   help="restarts after the first attempt (default 3)")
    p.add_argument("--restart-delay", type=float, default=1.0,
                   help="BASE restart delay in seconds; attempts back off "
                        "exponentially (base * 2^(attempt-1), capped by "
                        "--max-delay) with up to +50%% jitter so a fleet of "
                        "supervisors never relaunches in lockstep")
    p.add_argument("--max-delay", type=float, default=30.0,
                   help="exponential-backoff cap in seconds (default 30)")
    p.add_argument("--no-progress-limit", type=int, default=2,
                   help="give up with the poison exit code "
                        f"({POISON_RC}) after this many CONSECUTIVE "
                        "failures during which the latest checkpoint step "
                        "did not advance — a crash loop that replays the "
                        "same step forever is unrecoverable by restarting. "
                        "Signal deaths (preemption/OOM-kill/stall-kill) "
                        "never count: two preemptions inside one long "
                        "checkpoint interval is bad luck, not poison. "
                        "0 disables (needs --checkpoint-dir to measure)")
    p.add_argument("--stall-timeout", type=float, default=None,
                   help="kill + relaunch the child if it prints NOTHING for "
                        "this many seconds (hang/wedge detection; size it "
                        "above first-compile time + the log cadence; must "
                        "be > 0; NOTE: the watchdog merges the child's "
                        "stderr into stdout so one stream carries the "
                        "liveness signal)")
    p.add_argument("--registry-dir", default=None,
                   help="model registry directory (serve/registry.py): "
                        "after every child exit, promote the run's best "
                        "checkpoint (best.msgpack, versioned by its step) "
                        "into the registry so a serving fleet can roll it "
                        "without a restart; requires --checkpoint-dir in "
                        "the child's flags")
    p.add_argument("--registry-model", default="default",
                   help="model id to publish under (default: 'default' — "
                        "the serve engine's boot model id, so rollouts "
                        "reach existing sessions)")
    p.add_argument("--rollout-url", default=None,
                   help="serve fleet base URL (e.g. http://host:8000): "
                        "POST /rollout after each NEW publication so the "
                        "fleet rolls the fresh best automatically; best "
                        "effort — an unreachable fleet only loses the "
                        "trigger, not the artifact")
    p.add_argument("cli_args", nargs=argparse.REMAINDER,
                   help="-- followed by the training CLI flags")
    return p


def latest_checkpoint_step(directory: str) -> int | None:
    """Newest restorable checkpoint step in ``directory`` (None when the
    directory is missing/empty) — the forward-progress signal: a restart
    that cannot advance this number is a crash loop. Filename patterns
    come from resilience/ckpt_layout.py, the jax-free naming authority
    shared with train/checkpoint.py."""
    try:
        names = os.listdir(directory)
    except OSError:
        return None
    steps = [int(m.group(1)) for n in names
             if (m := ckpt_layout.RESTORABLE_PAT.match(n))]
    return max(steps, default=None)


def _deterministic_failure(rc, lifetime: float, subprocess_runner: bool) -> bool:
    """Deterministic failures can never be fixed by a retry: argparse usage
    errors exit 2, and flag-validation SystemExits die within well under a
    second (before any training state exists). Retrying those burns the
    whole restart budget on a run that cannot succeed. The lifetime
    heuristic only applies to real child processes — injected test runners
    return instantly by construction — never to signal deaths (rc >= 128):
    an early OOM-kill or preemption is exactly the transient class the
    supervisor exists to retry; and never to the KNOWN-retryable codes
    (RETRYABLE_RCS: anomaly aborts, injected crash drills), which are
    emitted deliberately by code that expects a restart-from-checkpoint to
    help."""
    if rc == USAGE_RC:
        return True
    return (subprocess_runner and rc is not None and 0 < rc < 128
            and rc not in RETRYABLE_RCS and lifetime < 1.0)


def _checkpoint_dir_of(cli_args: list[str]) -> str | None:
    for i, a in enumerate(cli_args):
        if a == "--checkpoint-dir" and i + 1 < len(cli_args):
            return cli_args[i + 1]
        if a.startswith("--checkpoint-dir="):
            return a.split("=", 1)[1]
    return None


def _publish_best(ckpt_dir: str, registry_dir: str, model_id: str, *,
                  rollout_url: str | None = None) -> dict | None:
    """Promote the run's best checkpoint into a model registry
    (serve/registry.py) so the serving side can roll it out without a
    restart. The raw ``best.msgpack`` bytes are published VERBATIM as a
    ``best_state`` artifact versioned by its step — the supervisor never
    deserializes multi-MB weights, and re-publication of an already-
    promoted step is a no-op (registry versions are immutable). Returns
    the published metadata record, or None when there was nothing new
    (or nothing valid) to promote. Sharded bests (``best.complete``
    marker sets) are skipped: promotion needs the single-artifact form a
    1-process training run writes."""
    import json

    meta_path = os.path.join(ckpt_dir, "best.json")
    try:
        with open(meta_path) as f:
            best = json.load(f)
        step = int(best["step"])
    except (OSError, ValueError, KeyError, TypeError):
        return None  # no best yet — nothing to promote
    # heavy imports stay OUT of module scope: the supervisor is
    # import-light by contract (no jax/backend init) unless publication
    # is armed and a best checkpoint actually exists
    from .serve.registry import ModelRegistry
    from .train.checkpoint import CorruptCheckpointError, read_verified

    path = os.path.join(ckpt_dir, "best.msgpack")
    try:
        payload = read_verified(path)
    except (CorruptCheckpointError, OSError) as e:
        print(f"supervise: best checkpoint not publishable ({e})",
              file=sys.stderr)
        return None
    reg = ModelRegistry(registry_dir)
    try:
        meta = reg.publish(model_id, payload, kind="best_state",
                           version=step,
                           parent=f"best.msgpack @ step {step}")
    except ValueError:
        return None  # this step is already in the registry
    print(f"supervise: published {model_id} v{step} "
          f"({len(payload)} bytes) to {registry_dir}", file=sys.stderr)
    if rollout_url:
        _trigger_rollout(rollout_url, model_id, step)
    return meta


def _trigger_rollout(url: str, model_id: str, version: int) -> None:
    """Ask a serve fleet (``POST /rollout``) to roll the version that was
    just published. Best effort: an unreachable fleet only loses the
    TRIGGER — the artifact is in the registry, and an operator (or the
    next publication) can roll it later."""
    import json
    import urllib.request

    body = json.dumps({"model": model_id, "version": version}).encode()
    req = urllib.request.Request(
        url.rstrip("/") + "/rollout", data=body,
        headers={"Content-Type": "application/json"}, method="POST")
    try:
        with urllib.request.urlopen(req, timeout=10) as resp:
            print(f"supervise: rollout of {model_id} v{version} accepted "
                  f"({resp.status})", file=sys.stderr)
    except OSError as e:
        print(f"supervise: rollout trigger failed ({e}) — artifact is "
              "published; roll it manually via POST /rollout",
              file=sys.stderr)


def run_with_stall_watch(cmd: list[str], stall_timeout: float) -> int:
    """Run ``cmd``, relaying its output line-by-line; if NO line arrives for
    ``stall_timeout`` seconds, terminate (then kill) it. Returns the exit
    code — negative (signal death) when the watchdog fired, so the caller's
    retry logic treats a stall exactly like a crash-by-signal."""
    proc = subprocess.Popen(cmd, stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True)
    last = [time.monotonic()]

    def pump():
        for line in proc.stdout:
            last[0] = time.monotonic()
            print(line, end="", flush=True)
        proc.stdout.close()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    while True:
        rc = proc.poll()
        if rc is not None:
            t.join(timeout=5)
            return rc
        if time.monotonic() - last[0] > stall_timeout:
            print(f"supervise: child silent for >{stall_timeout:.0f}s — "
                  "stalled; terminating", file=sys.stderr)
            proc.terminate()
            try:
                proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait()
            t.join(timeout=5)
            return proc.returncode
        time.sleep(min(1.0, stall_timeout / 4))


def supervise(cli_args: list[str], *, max_restarts: int = 3,
              restart_delay: float = 1.0, max_delay: float = 30.0,
              no_progress_limit: int = 2,
              stall_timeout: float | None = None,
              registry_dir: str | None = None,
              registry_model: str = "default",
              rollout_url: str | None = None,
              runner=None, rand=None) -> int:
    """Run the CLI (as a subprocess by default); relaunch with --resume on
    failure. ``runner(argv) -> int`` is injectable for tests; ``rand``
    feeds the backoff jitter (tests pass ``lambda: 0.0``).

    Self-healing contract (resilience/exit_codes.py): restart delays back
    off exponentially with jitter; a known-retryable child exit
    (``RETRYABLE_RCS`` — injected crash drills, anomaly aborts) is always
    relaunched even when the child died fast; and when ``--checkpoint-dir``
    is visible in the child's flags, the latest checkpoint step must
    ADVANCE between failures — ``no_progress_limit`` consecutive
    no-progress failures end the run with ``POISON_RC`` instead of
    replaying the same doomed step until the restart budget burns out."""
    if stall_timeout is not None and stall_timeout <= 0:
        # 0 would silently mean "no watchdog" and a negative value would
        # kill every healthy child at launch — both are operator mistakes
        raise SystemExit(
            f"--stall-timeout must be > 0, got {stall_timeout}"
        )
    # a supervised SERVE child (``supervise -- serve --http ...``) is the
    # serve-session resilience drill: relaunches must NOT inject --resume
    # (the serve parser has no such flag — argparse would exit 2 and the
    # deterministic-failure check would give up on a perfectly retryable
    # server), and checkpoint-step forward progress is a training notion
    # (serve's --checkpoint-dir is read-only params restore). Session
    # continuity across the restart comes from serve's own disk tier
    # (--session-dir, serve/state_cache.py SessionTiers).
    serve_child = bool(cli_args) and cli_args[0] == "serve"
    ckpt_dir = None if serve_child else _checkpoint_dir_of(cli_args)
    if ckpt_dir is None and not serve_child:
        print("supervise: warning: no --checkpoint-dir — a crash will "
              "restart from step 0 (and forward-progress poison detection "
              "is off)", file=sys.stderr)
    subprocess_runner = runner is None
    if runner is None:
        def runner(argv):
            cmd = [sys.executable, "-m", "lstm_tensorspark_tpu.cli", *argv]
            if stall_timeout:
                return run_with_stall_watch(cmd, stall_timeout)
            return subprocess.run(cmd).returncode

    # telemetry (obs/): restart/backoff accounting in the process-wide
    # registry — a long-lived supervisor's churn becomes scrapeable (and a
    # MetricsLogger.log_registry snapshot in any co-resident run carries it)
    m_restarts = obs.REGISTRY.counter(
        "supervise_restarts_total", "child relaunches after failure")
    m_backoff = obs.REGISTRY.counter(
        "supervise_backoff_seconds_total", "total time slept backing off")
    m_verdicts = obs.REGISTRY.counter(
        "supervise_terminal_total",
        "terminal supervisor verdicts (poisoned/deterministic/exhausted)",
        labelnames=("verdict",))
    attempt = 0
    _UNSET = object()
    prev_ckpt_step = _UNSET  # latest checkpoint step at the PREVIOUS failure
    no_progress = 0
    while True:
        argv = list(cli_args)
        if attempt > 0 and not serve_child:
            # --resume-best is a ONE-TIME rewind (and mutually exclusive
            # with --resume in the CLI): after the first attempt performed
            # it, relaunches must continue the fine-tune's own lineage
            argv = [a for a in argv if a != "--resume-best"]
            if "--resume" not in argv:
                argv.append("--resume")
        start = time.monotonic()
        rc = runner(argv)
        lifetime = time.monotonic() - start
        if rc is not None and rc < 0:
            rc = 128 - rc  # signal death -> conventional 128+signum status
        if registry_dir is not None and ckpt_dir is not None:
            # promotion runs on EVERY exit, not just success: a crashed
            # attempt may still have improved the best checkpoint, and
            # serving the newest best should not wait out the restart
            # budget. Already-published steps no-op inside.
            try:
                _publish_best(ckpt_dir, registry_dir, registry_model,
                              rollout_url=rollout_url)
            except Exception as e:  # registry trouble must not eat the
                # supervisor's retry loop — the child's lifecycle wins
                print(f"supervise: registry publication failed: {e}",
                      file=sys.stderr)
        if rc == 0:
            if attempt > 0:
                print(f"supervise: succeeded after {attempt} restart(s)",
                      file=sys.stderr)
            return 0
        if _deterministic_failure(rc, lifetime, subprocess_runner):
            print(f"supervise: child failed deterministically (exit {rc} "
                  f"after {lifetime:.2f}s) — not retrying", file=sys.stderr)
            m_verdicts.labels(verdict="deterministic").inc()
            return rc
        # Forward-progress check: between consecutive FAILURES the latest
        # restorable checkpoint step must advance, or the restarts are a
        # crash loop replaying the same step (poisoned data window, broken
        # model, corrupt-beyond-fallback checkpoints). Declaring poison
        # needs `no_progress_limit` consecutive stalls — a single repeat is
        # legitimate (e.g. a crash landing just before the next save).
        # Signal deaths (rc >= 128: preemption, OOM-kill, the stall
        # watchdog) never count toward poison — two preemptions landing
        # inside one long checkpoint interval is bad luck, not a doomed
        # step, and the transient class gets the full restart budget.
        # Also requires an actual checkpoint to exist (cur is not None):
        # a run that has not saved yet — first checkpoint interval still
        # open, or --checkpoint-every 0 with the dir used only for
        # keep-best/fault markers — has nothing to measure progress BY,
        # and transient early crashes must get the full restart budget.
        if (ckpt_dir is not None and no_progress_limit > 0
                and rc is not None and rc < 128):
            cur = latest_checkpoint_step(ckpt_dir)
            if (prev_ckpt_step is not _UNSET and cur is not None
                    and cur == prev_ckpt_step):
                no_progress += 1
                if no_progress >= no_progress_limit:
                    print(f"supervise: POISONED — {no_progress} consecutive "
                          f"failures without checkpoint progress (stuck at "
                          f"step {cur}); giving up (exit {POISON_RC})",
                          file=sys.stderr)
                    m_verdicts.labels(verdict="poisoned").inc()
                    return POISON_RC
            else:
                no_progress = 0
            prev_ckpt_step = cur
        if attempt >= max_restarts:
            print(f"supervise: giving up after {attempt} restart(s) "
                  f"(last exit code {rc})", file=sys.stderr)
            m_verdicts.labels(verdict="exhausted").inc()
            return rc
        attempt += 1
        delay = backoff_delay(restart_delay, attempt, cap=max_delay,
                              rand=rand)
        m_restarts.inc()
        m_backoff.inc(delay)
        print(f"supervise: child exited {rc}; restart {attempt}/"
              f"{max_restarts} in {delay:.1f}s", file=sys.stderr)
        time.sleep(delay)


def main(argv=None) -> int:
    args = build_parser().parse_args(argv)
    cli_args = args.cli_args
    if cli_args and cli_args[0] == "--":
        cli_args = cli_args[1:]
    if not cli_args:
        raise SystemExit("usage: ... supervise [--max-restarts N] -- <cli flags>")
    return supervise(
        cli_args,
        max_restarts=args.max_restarts,
        restart_delay=args.restart_delay,
        max_delay=args.max_delay,
        no_progress_limit=args.no_progress_limit,
        stall_timeout=args.stall_timeout,
        registry_dir=args.registry_dir,
        registry_model=args.registry_model,
        rollout_url=args.rollout_url,
    )


if __name__ == "__main__":
    raise SystemExit(main())
