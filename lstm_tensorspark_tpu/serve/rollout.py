"""Rollout controller: zero-downtime weight swaps and slot resizes.

The missing terminal stage of the training loop (ROADMAP item 3, the
pjit/TPUv4 production-training framing in PAPERS.md): ``supervise``
publishes each new best checkpoint into the :class:`~.registry.
ModelRegistry`, and this controller rolls it across the live fleet —
one replica at a time, through the router's drain machinery, so serving
capacity never drops below N−1 and no admitted request is lost.

One replica's roll is four phases, each counted in
``serve_rollout_total{phase,outcome}``:

1. **drain** — ``Router.begin_drain`` takes the replica out of fresh
   routing (continuations for its kept sessions migrate just-in-time in
   the router's pick; see ``_drain_affinity_locked``); its queued,
   not-yet-admitted work is requeued onto the peers with deadlines
   intact (``Router.requeue``); then the controller waits for in-flight
   work to finish (``Batcher.load() == 0``). A replica that never
   quiesces inside ``drain_timeout_s`` is returned to rotation and the
   rollout aborts with ``outcome="stuck"`` (the runbook row). Only then
   is the scheduler thread stopped — deliberately, which is why the
   router's death sweep skips draining replicas — and the remaining
   idle kept sessions move to peers via the PR 7 detach/restore path
   (``Router.migrate_from``: an uninterrupted-run-identical migration,
   the token-identity half of the gate drill).
2. **swap** — params come OUT OF THE REGISTRY (sha256-verified at load;
   a corrupt artifact quarantines and aborts the rollout, it is never
   served), with a config-fingerprint check against the engine's
   resident architecture (the version-skew guard). Same model id ⇒
   ``ServeEngine.swap_model``: params are traced ARGUMENTS to every
   compiled program, so same-shaped new weights reuse every compiled
   program — zero compiles. A new model id ⇒ ``add_model`` under its
   own compile-key namespace.
3. **warmup** — the batcher replays the server's remembered warmup spec
   off-path, so a NEW model id's programs (or a resize's new cache
   shapes) compile before traffic returns: zero mid-traffic compiles
   across the whole swap (``tests/test_serve_rollout.py``).
4. **rejoin** — a fresh scheduler thread starts and
   ``Router.end_drain`` returns the replica to rotation.

A replica whose scheduler DIES mid-drain (chaos ``replica_die``) is
handed back to the router's normal death path (end_drain + sweep →
retire: requeue/fail/migrate) and the rollout continues on the
survivors — the fleet still converges to the new version.

**Canary** (``canary_every > 0``, fleets of ≥ 2 local replicas): the
LAST local replica is rolled first, then a router hook shadows every
Nth stateless request onto it — a cloned best-effort request with
``use_prefix=False`` so the probe neither perturbs nor is flattered by
the shared prefix cache. Completed (primary, shadow) pairs are
token-diffed into ``serve_canary_diff_total{verdict}`` and the
TTFT distributions of both sides are summarised into a comparison
report BEFORE the remaining replicas promote. The report is
informational by default — new weights legitimately decode different
tokens; ``require_canary_match=True`` turns a diff into an abort (the
canary-diff-regression runbook row).

**Resize** (the PR 14 autotuner residual): device-slot count was frozen
at boot shape because the state arrays' shapes are baked into every
compiled program. ``request_resize`` runs the same drain → reshape
(``ServeEngine.resize_slots`` + ``Batcher.set_max_active``) → warmup →
rejoin move per replica, so the autotuner can ask for capacity instead
of being capped at boot.

Thread lifecycle is the AutoTuner contract: ``_run`` reads
``self._stop``; ``stop()`` sets it and joins ``self._thread`` (the
graftlint ``thread-lifecycle`` fixture pair ``viol_rollout`` /
``clean_rollout`` pins this shape).
"""

from __future__ import annotations

import itertools
import threading
import time
from collections import deque

from .batcher import Request
from .engine import GREEDY
from .registry import ModelRegistry, config_fingerprint

#: phases of one replica's roll, in order (metric label values)
PHASES = ("drain", "swap", "warmup", "rejoin")


class RolloutError(RuntimeError):
    """A rollout step failed; the fleet was left serving (the failing
    replica rejoined on its old weights, or retired through the normal
    death path)."""


class _ReplicaDied(RuntimeError):
    """The drainee's scheduler died mid-drain (chaos ``replica_die``) —
    handled by handing the corpse to the router's death path."""


def _pctl(values: list[float], q: float) -> float | None:
    if not values:
        return None
    xs = sorted(values)
    i = min(len(xs) - 1, int(q * (len(xs) - 1) + 0.5))
    return xs[i]


class RolloutController:
    """Drives rolling swaps/resizes over a :class:`~.server.ServeServer`
    (module docstring). ``start()``/``stop()`` manage the controller
    thread (the server's lifecycle drives them); ``run_rollout`` /
    ``run_resize`` execute one move synchronously (tests and the smoke
    drill call them directly); ``request_*`` enqueue for the thread."""

    def __init__(self, server, registry, *,
                 canary_every: int = 0,
                 canary_min_pairs: int = 8,
                 canary_timeout_s: float = 10.0,
                 require_canary_match: bool = False,
                 drain_timeout_s: float = 30.0,
                 interval_s: float = 0.25,
                 history: int = 32):
        if canary_every < 0:
            raise ValueError(
                f"canary_every must be >= 0, got {canary_every}")
        if drain_timeout_s <= 0:
            raise ValueError(
                f"drain_timeout_s must be > 0, got {drain_timeout_s}")
        self.server = server
        self.registry = (registry if isinstance(registry, ModelRegistry)
                         else ModelRegistry(registry))
        self.canary_every = int(canary_every)
        self.canary_min_pairs = int(canary_min_pairs)
        self.canary_timeout_s = float(canary_timeout_s)
        self.require_canary_match = bool(require_canary_match)
        self.drain_timeout_s = float(drain_timeout_s)
        self.interval_s = float(interval_s)
        reg = server.engine.metrics
        fam = reg.counter(
            "serve_rollout_total",
            "rollout-controller phase outcomes (phase=drain/swap/warmup/"
            "rejoin; outcome=ok/error/stuck — 'stuck' on drain is the "
            "stuck-drain runbook row)",
            labelnames=("phase", "outcome"))
        self._m_rollout = fam
        fam = reg.counter(
            "serve_canary_diff_total",
            "canary shadow-pair verdicts (match/diff/error); diff is "
            "informational unless require_canary_match is set",
            labelnames=("verdict",))
        self._m_canary = {v: fam.labels(verdict=v)
                         for v in ("match", "diff", "error")}
        # move queue + bookkeeping (guarded by _lock; the controller
        # thread pops, request_* and HTTP handlers push/read)
        self._lock = threading.Lock()
        self._queue: deque = deque()
        self._active: dict | None = None
        self._history: deque = deque(maxlen=history)
        self.rollouts = 0
        self.resizes = 0
        self.errors = 0
        self._last_error: str | None = None
        self.last_canary: dict | None = None
        # canary shadow state (its own lock: the router hook runs on
        # client threads while the controller thread collects)
        self._canary_lock = threading.Lock()
        self._pairs: list = []
        self._canary_counts = {"match": 0, "diff": 0, "error": 0,
                               "shadowed": 0, "skipped": 0}
        self._stop = threading.Event()
        self._thread: threading.Thread | None = None

    # ---- lifecycle -----------------------------------------------------

    def start(self) -> "RolloutController":
        if self._thread is not None:
            raise RuntimeError("rollout controller already started")
        self._stop.clear()
        t = threading.Thread(target=self._run, name="serve-rollout",
                             daemon=True)
        self._thread = t
        t.start()
        return self

    def stop(self) -> None:
        self._stop.set()
        if self._thread is not None:
            self._thread.join(timeout=30.0)
            self._thread = None

    def _run(self) -> None:
        # the wait IS the cadence: stop() parks the loop within one
        # interval (and aborts any in-progress drain wait)
        while not self._stop.wait(self.interval_s):
            with self._lock:
                move = self._queue.popleft() if self._queue else None
            if move is None:
                continue
            try:
                if move["kind"] == "rollout":
                    self.run_rollout(move["model"],
                                     version=move.get("version"))
                else:
                    self.run_resize(move["num_slots"])
            except Exception as e:
                # a failed move must degrade to "fleet keeps serving the
                # old version", never to a dead controller — recorded,
                # surfaced in /stats, the queue keeps draining
                with self._lock:
                    self.errors += 1
                    self._last_error = f"{type(e).__name__}: {e}"

    # ---- requests (async; the controller thread executes) ---------------

    def request_rollout(self, model_id: str,
                        version: int | None = None) -> dict:
        move = {"kind": "rollout", "model": str(model_id),
                "version": version}
        with self._lock:
            self._queue.append(move)
            return {**move, "queued": len(self._queue)}

    def request_resize(self, num_slots: int) -> dict:
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        move = {"kind": "resize", "num_slots": int(num_slots)}
        with self._lock:
            # collapse pending resizes — only the latest target matters
            self._queue = deque(m for m in self._queue
                                if m["kind"] != "resize")
            self._queue.append(move)
            return {**move, "queued": len(self._queue)}

    # ---- the moves (synchronous; tests/smoke call these directly) -------

    def _local_replicas(self) -> list:
        """The replicas this controller can swap: local engines only (a
        RemoteReplica's weights belong to its own host's controller)."""
        return [r for r in self.server.replicas
                if hasattr(getattr(r, "engine", None), "swap_model")]

    def run_rollout(self, model_id: str, version: int | None = None,
                    canary_every: int | None = None) -> dict:
        """Roll ``model_id`` (latest version by default) across every
        local replica. Returns the rollout record (also kept in
        ``stats()['history']``)."""
        locals_ = self._local_replicas()
        if not locals_:
            raise RolloutError("no local replicas to roll")
        # rescan first: the artifact being rolled was usually published
        # by ANOTHER process (supervise --registry-dir) after this
        # server's registry built its manifest at boot
        self.registry.scan()
        # decode ONCE against replica 0's param structure; each swap
        # re-places the host arrays onto its own replica's device/mesh
        meta, params = self.registry.load_params(
            model_id, locals_[0].engine.params, version)
        want = meta.get("config_hash")
        if want is not None:
            have = config_fingerprint(locals_[0].engine.cfg)
            if want != have:
                self._m_rollout.labels(phase="swap",
                                       outcome="error").inc()
                raise RolloutError(
                    f"{model_id} v{meta['version']} was trained on config "
                    f"{want}, the fleet serves {have} — refusing the swap "
                    "(version skew)")
        every = self.canary_every if canary_every is None else canary_every
        record = {"kind": "rollout", "model": meta["model"],
                  "version": meta["version"], "replicas": [],
                  "canary": None, "outcome": "ok",
                  # operator-facing record timestamps: wall clock intended
                  "t_start": time.time()}  # graftlint: disable=wallclock-timing
        with self._lock:
            self._active = record
        try:
            order = list(locals_)
            if every > 0 and len(order) > 1:
                # canary replica first: roll the LAST local replica, then
                # shadow-compare before the rest promote
                order = [order[-1]] + order[:-1]
                self._roll_one(order[0], meta, params, record)
                report = self._run_canary(order[0], meta, every)
                record["canary"] = report
                if (self.require_canary_match
                        and report["counts"]["diff"] > 0):
                    record["outcome"] = "canary_regression"
                    raise RolloutError(
                        f"canary diffed on {report['counts']['diff']} of "
                        f"{report['counts']['compared']} shadow pairs — "
                        "aborting promotion (the canary replica keeps the "
                        "new version for diagnosis)")
                order = order[1:]
            for rep in order:
                self._roll_one(rep, meta, params, record)
            with self._lock:
                self.rollouts += 1
        except Exception as e:
            if record["outcome"] == "ok":
                record["outcome"] = f"error: {e}"
            raise
        finally:
            record["t_end"] = time.time()  # graftlint: disable=wallclock-timing
            with self._lock:
                self._active = None
                self._history.append(record)
        return record

    def run_resize(self, num_slots: int) -> dict:
        """Drain-and-rejoin each local replica with ``num_slots`` device
        slots (the PR 14 residual: slot count is no longer a frozen boot
        shape). New cache shapes mean new programs — the warmup phase
        recompiles the lattice off-path before rejoin."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        record = {"kind": "resize", "num_slots": int(num_slots),
                  "replicas": [], "outcome": "ok",
                  # operator-facing record timestamps: wall clock intended
                  "t_start": time.time()}  # graftlint: disable=wallclock-timing
        with self._lock:
            self._active = record
        try:
            for rep in self._local_replicas():
                if rep.engine.cache.num_slots == num_slots:
                    continue  # already at target (idempotent requests)
                self._roll_one(rep, None, None, record,
                               num_slots=num_slots)
            with self._lock:
                self.resizes += 1
        except Exception as e:
            if record["outcome"] == "ok":
                record["outcome"] = f"error: {e}"
            raise
        finally:
            record["t_end"] = time.time()  # graftlint: disable=wallclock-timing
            with self._lock:
                self._active = None
                self._history.append(record)
        return record

    # ---- one replica's drain → swap/resize → warmup → rejoin ------------

    def _roll_one(self, rep, meta, params, record,
                  num_slots: int | None = None) -> None:
        entry = {"replica": rep.index, "phases": []}
        record["replicas"].append(entry)
        router = self.server.router
        try:
            self._phase(entry, "drain", self._drain, rep)
        except _ReplicaDied:
            # chaos mid-drain: hand the corpse to the normal death path
            # (requeue/fail/migrate) and keep rolling the survivors —
            # the fleet still converges to the new version
            router.end_drain(rep.index)
            router.sweep()
            return
        try:
            if num_slots is not None:
                self._phase(entry, "swap", self._resize_one, rep,
                            num_slots)
            else:
                self._phase(entry, "swap", rep.engine.swap_model, params,
                            model_id=meta["model"],
                            version=meta["version"])
            self._phase(entry, "warmup", self._warmup_one, rep)
        finally:
            # ALWAYS rejoin: even a failed swap leaves the engine on its
            # previous (or half-new, for a failed warmup) weights —
            # serving capacity comes back either way, and the phase
            # counters say which step to diagnose
            self._phase(entry, "rejoin", self._rejoin, rep)

    def _phase(self, entry: dict, phase: str, fn, *a, **kw):
        try:
            out = fn(*a, **kw)
        except _ReplicaDied:
            self._m_rollout.labels(phase=phase, outcome="error").inc()
            entry["phases"].append({"phase": phase, "outcome": "died"})
            raise
        except RolloutError as e:
            outcome = "stuck" if "quiesce" in str(e) else "error"
            self._m_rollout.labels(phase=phase, outcome=outcome).inc()
            entry["phases"].append({"phase": phase, "outcome": outcome,
                                    "error": str(e)})
            raise
        except Exception as e:
            self._m_rollout.labels(phase=phase, outcome="error").inc()
            entry["phases"].append({"phase": phase, "outcome": "error",
                                    "error": f"{type(e).__name__}: {e}"})
            raise
        self._m_rollout.labels(phase=phase, outcome="ok").inc()
        entry["phases"].append({"phase": phase, "outcome": "ok"})
        return out

    def _drain(self, rep) -> None:
        router = self.server.router
        router.begin_drain(rep.index)
        # requeue the not-yet-admitted backlog FIRST (deadlines ride
        # along), then wait for in-flight work to finish
        router.requeue(rep.batcher.drain_queue(), rep)
        deadline = time.monotonic() + self.drain_timeout_s
        while rep.batcher.load() > 0:
            if rep.thread is not None and not rep.thread.is_alive():
                raise _ReplicaDied(
                    f"replica {rep.index} died mid-drain")
            if time.monotonic() > deadline:
                router.end_drain(rep.index)
                raise RolloutError(
                    f"replica {rep.index} did not quiesce within "
                    f"{self.drain_timeout_s:g}s (load "
                    f"{rep.batcher.load()}) — returned to rotation")
            if self._stop.wait(0.005):
                router.end_drain(rep.index)
                raise RolloutError("controller stopped mid-drain")
            # late arrivals (continuations routed to the drainee while
            # it still owned their sessions) land in the queue — keep
            # requeueing them behind the migrating sessions
            router.requeue(rep.batcher.drain_queue(), rep)
        # quiesced: stop the scheduler (deliberate — the sweep skips
        # draining replicas) and move the idle kept sessions to peers
        self.server._stop_replica(rep)
        router.migrate_from(rep)

    def _resize_one(self, rep, num_slots: int) -> None:
        rep.engine.resize_slots(num_slots)
        rep.batcher.set_max_active(num_slots)

    def _warmup_one(self, rep) -> int:
        sampling, lens = getattr(self.server, "_warmup_spec",
                                 None) or (GREEDY, (1,))
        return rep.batcher.warmup(sampling, prompt_lens=lens)

    def _rejoin(self, rep) -> None:
        self.server._start_replica(rep)
        self.server.router.end_drain(rep.index)

    # ---- canary shadowing ------------------------------------------------

    def _run_canary(self, canary_rep, meta: dict, every: int) -> dict:
        """Shadow every ``every``-th stateless request onto the already-
        rolled canary replica until ``canary_min_pairs`` pairs compared
        (or ``canary_timeout_s``), then report. The hook clones the
        primary request — same prompt/sampling/model, ``use_prefix=False``
        (a probe must not perturb the shared prefix cache), best-effort
        class so shadows shed first under load — and submits it straight
        to the canary's batcher, off the router's books."""
        with self._canary_lock:
            self._pairs = []
            for k in self._canary_counts:
                self._canary_counts[k] = 0
        ttft = {"primary": [], "canary": []}
        router = self.server.router
        router.set_canary(self._make_hook(canary_rep, every))
        try:
            deadline = time.monotonic() + self.canary_timeout_s
            while time.monotonic() < deadline:
                if self._collect(ttft) >= self.canary_min_pairs:
                    break
                if self._stop.wait(0.02):
                    break
        finally:
            router.clear_canary()
        # grace: settle pairs whose shadow is still decoding
        deadline = time.monotonic() + 2.0
        while time.monotonic() < deadline:
            with self._canary_lock:
                outstanding = len(self._pairs)
            if not outstanding or self._stop.wait(0.02):
                break
            self._collect(ttft)
        self._collect(ttft)
        with self._canary_lock:
            counts = dict(self._canary_counts)
            self._pairs = []
        counts["compared"] = (counts["match"] + counts["diff"]
                              + counts["error"])
        report = {
            "model": meta["model"], "version": meta["version"],
            "replica": canary_rep.index, "every": every,
            "counts": counts,
            "verdict": ("diff" if counts["diff"] else
                        "match" if counts["match"] else "no_traffic"),
            # the SLO half of the comparison: TTFT of primaries vs their
            # shadows over the SAME prompts — a slower canary here is a
            # perf regression even when the tokens match
            "slo": {side: {
                "count": len(vals),
                "ttft_p50_ms": None if not vals
                else round(_pctl(vals, 0.50) * 1e3, 3),
                "ttft_p99_ms": None if not vals
                else round(_pctl(vals, 0.99) * 1e3, 3),
            } for side, vals in ttft.items()},
        }
        self.last_canary = report
        return report

    def _make_hook(self, canary_rep, every: int):
        counter = itertools.count(1)

        def hook(req: Request) -> None:
            if req.session_id is not None or req.keep_session:
                return  # stateful requests have affinity — never forked
            if req.replica == canary_rep.index:
                return  # already landed on the canary (or IS a shadow)
            if next(counter) % every:
                return
            shadow = Request(
                list(req.prompt), req.max_new_tokens,
                sampling=req.sampling, eos_id=req.eos_id,
                use_prefix=False, klass="best_effort", model=req.model)
            try:
                canary_rep.batcher.submit(shadow)
            except Exception:
                with self._canary_lock:
                    self._canary_counts["skipped"] += 1
                return
            with self._canary_lock:
                self._canary_counts["shadowed"] += 1
                self._pairs.append((req, shadow))

        return hook

    def _collect(self, ttft: dict) -> int:
        """Settle completed (primary, shadow) pairs into verdict counts
        + TTFT samples. Returns pairs compared so far."""
        with self._canary_lock:
            remaining = []
            for prim, shad in self._pairs:
                if not (prim.done.is_set() and shad.done.is_set()):
                    remaining.append((prim, shad))
                    continue
                if (prim.error is not None or shad.error is not None
                        or prim.timed_out or shad.timed_out):
                    verdict = "error"
                elif list(prim.tokens) == list(shad.tokens):
                    verdict = "match"
                else:
                    verdict = "diff"
                self._canary_counts[verdict] += 1
                self._m_canary[verdict].inc()
                for side, r in (("primary", prim), ("canary", shad)):
                    if r.t_first_token and r.t_submit:
                        ttft[side].append(r.t_first_token - r.t_submit)
            self._pairs = remaining
            return (self._canary_counts["match"]
                    + self._canary_counts["diff"]
                    + self._canary_counts["error"])

    # ---- views ----------------------------------------------------------

    def stats(self) -> dict:
        """The ``/stats`` ``rollout`` section."""
        with self._lock:
            return {
                "running": self._thread is not None,
                "registry": self.registry.stats(),
                "active": None if self._active is None
                else {k: v for k, v in self._active.items()
                      if k != "t_start"},
                "queued": [dict(m) for m in self._queue],
                "rollouts": self.rollouts,
                "resizes": self.resizes,
                "errors": self.errors,
                "last_error": self._last_error,
                "canary_every": self.canary_every,
                "last_canary": self.last_canary,
                "history": list(self._history),
            }
