"""Admission router over N per-replica schedulers (data-parallel serving).

The serve stack's scaling unit is a **replica**: one :class:`ServeEngine`
(its own state cache, prefix cache and compiled programs — on TPU also
its own device, via ``ServeEngine(device=...)``) plus one
:class:`Batcher` driven by its own scheduler thread. Replicas share
NOTHING on the hot path: recurrent-state slots and ``PrefixCache``
entries are replica-local, so there is no cross-replica cache coherence
to get wrong — the source paper's driver/worker split applied to
inference (the router is the driver, replicas are the map workers; cf.
the DrJAX map/reduce framing in PAPERS.md). Aggregate decode tokens/s
then scales with replicas instead of hard-capping at one scheduler.

Routing (:meth:`Router.submit`):

- **session → replica affinity**: a request naming a ``session_id`` goes
  to the replica whose state cache holds that session — probed directly
  (``sid in engine.cache``), so the cache IS the affinity table and
  there is no side mapping to go stale. A kept session's continuations
  therefore always land where its carries (and any prefix entries its
  prompts seeded) live.
- **fresh requests** go to the least-loaded live replica
  (queued + active + prefilling), round-robin on ties — so an idle
  fleet splits a burst instead of piling onto replica 0.
- **admission** enforces ONE global bound: total queued across live
  replicas ``>= queue_size`` raises :class:`QueueFullError` (HTTP 429).
  Per-replica queues are sized at the same bound, so the global check
  is the only one that ever fires.

Replica death — a scheduler thread that EXITS outside ``stop()``
(uncaught exception) — is detected by :meth:`Router.sweep` (piggybacked
on every submit and health probe; no monitor thread) and the replica is
retired exactly once:

1. its queued, not-yet-admitted requests are **requeued** onto live
   replicas (bypassing the global bound — they already held queue slots
   before the death);
2. its in-flight (admitted) requests **fail honestly**: under
   dispatch-ahead windowed decode the host cannot know how many tokens
   an un-fetched window already consumed, so resuming mid-decode on
   another replica could silently double-decode — "state lost" is the
   truthful verdict;
3. its idle kept sessions **migrate** to live replicas via the exact
   ``detach``/``restore`` path (state_cache), BEFORE the requeue — so a
   queued continuation follows its migrated state and completes
   token-identically to an uninterrupted run. Sessions that cannot be
   restored are dropped; their next continuation fails loudly as
   "unknown session" (never silently decodes from zero state).

A WEDGED replica (thread alive, heartbeat stale) is only excluded from
fresh routing and health — its thread may still wake and touch its
structures, so retirement (which mutates them from the router's thread)
would race; see docs/OPERATIONS.md "Router runbook". Retirement runs
inline on the detecting probe/submit thread; its cost is bounded by
``num_slots`` × one O(1) LSTM state per kept session (KBs each —
detach/restore of idle state, no pending compute to await), so a sweep
stays well under orchestrator probe timeouts. A continuation submitted
concurrently with its session's in-flight migration can land between
detach and restore and fail "unknown session" once — transient by
construction; an immediate retry follows the restored state.

Lock order: ``Router._lock`` is acquired ABOVE replica locks (the
router reads ``Batcher.queued()``/``load()`` and probes caches while
holding it); nothing in a replica ever calls back up into the router,
so the acquisition graph stays acyclic (graftlint ``lock-order``).
"""

from __future__ import annotations

import itertools
import threading
import time

from .batcher import (
    CLASSES,
    Batcher,
    QueueFullError,
    Request,
    register_shed_instruments,
    retry_after_from_p99,
)
from .engine import ServeEngine, UnknownModelError
from .state_cache import PREFIX_SID_NAMESPACE


class Replica:
    """One engine + scheduler pair. The thread handle lives here so the
    router and server agree on liveness; ``retired`` marks a dead
    replica whose cleanup (requeue/fail/migrate) already ran."""

    __slots__ = ("index", "engine", "batcher", "thread", "retired",
                 "draining")

    def __init__(self, index: int, engine: ServeEngine, batcher: Batcher):
        self.index = index
        self.engine = engine
        self.batcher = batcher
        self.thread: threading.Thread | None = None
        self.retired = False  # claimed under the router lock, exactly once
        # held out of rotation by the rollout controller: fresh routing,
        # the admission bound and the death sweep all skip it (its
        # scheduler thread is about to be stopped DELIBERATELY)
        self.draining = False

    def alive(self) -> bool:
        """Live: never started (requests queue until ``start()``) or the
        thread is running. Started-and-exited is dead — except during a
        drain, when the controller stops the thread on purpose."""
        return not self.retired and (
            self.thread is None or self.thread.is_alive())

    def routable(self) -> bool:
        """Eligible for routing: live AND not mid-drain."""
        return self.alive() and not self.draining

    def stale(self, stale_after: float) -> bool:
        """Running but heartbeat-silent past ``stale_after`` — the wedge
        case (thread stuck inside a dispatch that never returns). An
        unstarted replica has no heartbeat and is NOT stale."""
        hb = self.batcher.last_heartbeat
        return (self.thread is not None and hb is not None
                and time.monotonic() - hb > stale_after)

    def circuit_open(self) -> bool:
        """True while the replica's transport circuit is open or suspect
        (remote replicas only — a partitioned peer must be routed
        around instantly, like a wedge, while its heartbeat prober
        works toward rejoin). Local replicas have no circuit."""
        return False


class Router:
    """Admission front for a set of replicas (module docstring)."""

    #: tenant token-bucket table cap: beyond this, fully-refilled buckets
    #: (indistinguishable from absent ones) are pruned — an adversarial
    #: stream of fresh tenant names cannot grow router memory unboundedly
    MAX_TENANT_BUCKETS = 4096

    def __init__(self, replicas: list[Replica], *, queue_size: int = 64,
                 stale_after: float = 60.0,
                 best_effort_frac: float = 0.5, registry=None,
                 tenant_rate: float | None = None,
                 tenant_burst: float = 5.0):
        if not replicas:
            raise ValueError("router needs at least one replica")
        if queue_size < 1:
            raise ValueError(f"queue_size must be >= 1, got {queue_size}")
        if not 0.0 < best_effort_frac <= 1.0:
            raise ValueError(
                f"best_effort_frac must be in (0, 1], got {best_effort_frac}")
        if tenant_rate is not None and tenant_rate <= 0:
            raise ValueError(
                f"tenant_rate must be > 0 req/s or None, got {tenant_rate}")
        if tenant_burst < 1:
            raise ValueError(
                f"tenant_burst must be >= 1, got {tenant_burst}")
        self.replicas = list(replicas)
        self.queue_size = queue_size
        # per-tenant token buckets (requests/s with a burst allowance) on
        # TOP of the class policy: one tenant flooding the fleet is
        # rate-limited before it can consume the shared queue bound the
        # other tenants' traffic lives under. None = no per-tenant
        # limiting (requests without a tenant field are never limited).
        self.tenant_rate = tenant_rate
        self.tenant_burst = float(tenant_burst)
        self._tenant_buckets: dict[str, list] = {}  # tenant -> [tokens, t]
        # SLO-aware shedding: best-effort requests are 429'd once the
        # live queue reaches this smaller bound, so a best-effort burst
        # sheds while the priority class keeps the remaining headroom —
        # the honest degradation the old single fixed bound couldn't
        # express (it shed both classes indiscriminately, FIFO)
        self.best_effort_frac = float(best_effort_frac)
        self._best_effort_bound = max(
            1, int(round(queue_size * best_effort_frac)))
        # heartbeat-staleness bound for ROUTING (mirrors the server's
        # health_stale_after): a wedged replica must stop receiving fresh
        # sessions — they would hang to client timeout while holding
        # global queue capacity, even with healthy replicas idle
        self.stale_after = stale_after
        self._lock = threading.Lock()
        self._rr = itertools.count()  # round-robin tie-break cursor
        # rollout canary hook: called with every successfully admitted
        # request OUTSIDE the lock (the hook submits shadow work back
        # through replica batchers — calling it under ``_lock`` would
        # deadlock on re-entry through submit's own acquisition)
        self._canary = None
        # the death sweep starts DISARMED: ServeServer.start() arms it
        # (set_stopping(False)) only once every scheduler thread is
        # running — otherwise a submit/probe racing the first start()
        # could see an assigned-but-not-yet-started thread and retire a
        # replica that is about to serve
        self._stopping = True
        self.rejected = 0            # global-bound 429s
        self.shed = {c: 0 for c in CLASSES}  # 429s by admission class
        self.tenant_limited = {c: 0 for c in CLASSES}  # token-bucket 429s
        self.requeued = 0            # dead-replica queue → live replica
        self.rerouted = 0            # undelivered RPCs re-picked elsewhere
        self.failed_on_death = 0     # in-flight requests failed honestly
        self.migrated_sessions = 0   # idle kept sessions detach/restored
        self.lost_sessions = 0       # could not be restored anywhere
        self.routed: dict[int, int] = {r.index: 0 for r in self.replicas}
        reg = registry if registry is not None else replicas[0].engine.metrics
        self._m_rejected = reg.counter(
            "serve_router_rejected_total",
            "requests 429'd at the router's global admission bound")
        # ALSO recorded under the shared outcome family (replica="router"):
        # the global bound fires before any per-replica bound can, and the
        # runbook's queue-saturation signature is
        # serve_requests_total{outcome="rejected"} — it must keep climbing
        # on real 429s, not flatline because rejection moved up a layer
        self._m_rejected_outcome = reg.counter(
            "serve_requests_total",
            labelnames=("outcome", "replica")).labels(
            outcome="rejected", replica="router")
        fam = reg.counter("serve_router_routed_total",
                          "requests routed, by target replica",
                          labelnames=("replica",))
        self._m_routed = {r.index: fam.labels(replica=str(r.index))
                          for r in self.replicas}
        self._m_requeued = reg.counter(
            "serve_router_requeued_total",
            "dead-replica queued requests requeued onto live replicas")
        self._m_failed_death = reg.counter(
            "serve_router_death_failures_total",
            "in-flight requests failed honestly on replica death")
        self._m_migrated = reg.counter(
            "serve_router_migrated_sessions_total",
            "idle kept sessions moved off dead replicas via detach/restore")
        self._m_rerouted = reg.counter(
            "serve_router_rerouted_total",
            "provably-undelivered remote RPCs re-routed to another replica")
        # shared with the batcher's own queue bound: one registration
        # site + one policy function, so the two layers can never hint
        # different Retry-After curves for the same queue state; the
        # tenant_limited="yes" children count this router's per-tenant
        # token-bucket 429s
        (self._m_shed, self._m_tenant_shed,
         self._m_retry_after) = register_shed_instruments(reg)
        # the live queue-wait histogram family (registered by the
        # batchers, same name/labels/buckets — idempotent): its p99 IS
        # the drain-time evidence Retry-After is computed from
        self._qwait = reg.histogram(
            "serve_queue_wait_seconds", "submit → admission wait",
            labelnames=("replica",))

    def _suspect(self, r: Replica) -> bool:
        """Unfit for fresh work: heartbeat-stale (the wedge) OR
        transport circuit open/suspect (the partition). Both are
        route-around states, not deaths — the replica stays in the
        fleet and rejoins when its heartbeat/probes recover."""
        return r.stale(self.stale_after) or r.circuit_open()

    # ---- client side ---------------------------------------------------

    def submit(self, req: Request) -> None:
        """Admit + route one request, or raise :class:`QueueFullError`
        (SLO-aware shed; HTTP 429 with ``retry_after_s``) /
        ``RuntimeError`` when no replica is live. Called from client/HTTP
        threads.

        Shedding is class-aware: ``best_effort`` requests are rejected
        once the live queue reaches ``best_effort_frac * queue_size``,
        ``priority`` only at the full bound — so a burst degrades by
        shedding the cheap class first. Every shed carries a
        ``Retry-After`` computed from the live queue-wait p99 histogram
        (the measured drain time), not a made-up constant."""
        self.sweep()
        with self._lock:
            live = [r for r in self.replicas if r.routable()]
            if not live:
                raise RuntimeError(
                    "no routable replica schedulers (replicas dead or "
                    "draining)")
            # per-tenant token bucket FIRST: a rate-limited tenant is
            # rejected before it can consume the shared queue bound the
            # other tenants' traffic lives under
            if self.tenant_rate is not None and req.tenant is not None:
                retry = self._tenant_take_locked(req.tenant)
                if retry is not None:
                    self.tenant_limited[req.klass] += 1
                    self._m_tenant_shed[req.klass].inc()
                    self._m_retry_after.observe(retry)
                    raise QueueFullError(
                        f"tenant {req.tenant!r} exceeded its "
                        f"{self.tenant_rate:g} req/s rate limit; retry "
                        f"after {retry:.2f}s", retry_after_s=retry)
            # the bound covers NON-SUSPECT queues only: a wedged replica
            # never drains (its admission loop is stuck) and a
            # partitioned one drains only after it heals, so counting
            # their stranded entries would shrink the fleet's effective
            # admission capacity until recovery. If the wedge/partition
            # recovers, a transient overshoot of the bound drains
            # normally.
            queued = sum(r.batcher.queued() for r in live
                         if not self._suspect(r))
            bound = (self._best_effort_bound
                     if req.klass == "best_effort" else self.queue_size)
            if queued >= bound:
                retry = self._retry_after_locked(queued)
                self.rejected += 1
                self.shed[req.klass] += 1
                self._m_rejected.inc()
                self._m_rejected_outcome.inc()
                self._m_shed[req.klass].inc()
                self._m_retry_after.observe(retry)
                raise QueueFullError(
                    f"submit queue full for class {req.klass!r} "
                    f"({queued} pending >= bound {bound}); retry after "
                    f"{retry:.2f}s", retry_after_s=retry)
            self._dispatch_locked(req, live)
        canary = self._canary
        if canary is not None:
            try:
                canary(req)
            except Exception:
                pass  # a shadow must never fail the admitted primary

    def _tenant_take_locked(self, tenant: str) -> float | None:
        """Take one token from ``tenant``'s bucket. Returns None when a
        token was available (request admitted to the normal policy), or
        the honest Retry-After: the time until the bucket accrues a
        token, floored by the shared queue-drain policy
        (:func:`~.batcher.retry_after_from_p99`) so a rate-limited
        client never retries into a congested queue faster than a shed
        one would."""
        now = time.monotonic()
        bucket = self._tenant_buckets.get(tenant)
        if bucket is None:
            if len(self._tenant_buckets) >= self.MAX_TENANT_BUCKETS:
                # prune fully-refilled buckets — indistinguishable from
                # absent ones, so dropping them changes no verdict
                full = [t for t, (tok, ts) in self._tenant_buckets.items()
                        if tok + (now - ts) * self.tenant_rate
                        >= self.tenant_burst]
                for t in full:
                    del self._tenant_buckets[t]
                while len(self._tenant_buckets) >= self.MAX_TENANT_BUCKETS:
                    # nothing prunable (a flood of FRESH tenant names
                    # faster than the refill rate): evict the fullest
                    # bucket — the closest to indistinguishable-from-
                    # absent, so dropping it perturbs verdicts least.
                    # The cap is a hard bound, not a hint: without this
                    # the table grows with attacker send rate.
                    victim = max(
                        self._tenant_buckets,
                        key=lambda t: self._tenant_buckets[t][0]
                        + (now - self._tenant_buckets[t][1])
                        * self.tenant_rate)
                    del self._tenant_buckets[victim]
            bucket = self._tenant_buckets[tenant] = [self.tenant_burst, now]
        tokens = min(self.tenant_burst,
                     bucket[0] + (now - bucket[1]) * self.tenant_rate)
        bucket[1] = now
        if tokens >= 1.0:
            bucket[0] = tokens - 1.0
            return None
        bucket[0] = tokens
        deficit = (1.0 - tokens) / self.tenant_rate
        agg = self._qwait.aggregate_over("replica")
        s = agg.get("") or {}
        return max(deficit, retry_after_from_p99(s.get("p99"), 0.0))

    def set_best_effort_frac(self, frac: float) -> None:
        """Move the best-effort shed bound at runtime — the autotuner's
        admission knob (tightened when the state plane thrashes at its
        capacity ceiling, relaxed back toward the configured policy when
        the pressure clears). Same validation as construction."""
        if not 0.0 < frac <= 1.0:
            raise ValueError(
                f"best_effort_frac must be in (0, 1], got {frac}")
        with self._lock:
            self.best_effort_frac = float(frac)
            self._best_effort_bound = max(
                1, int(round(self.queue_size * frac)))

    def _retry_after_locked(self, queued: int) -> float:
        """Honest Retry-After (seconds) for a shed: the fleet's queue-wait
        p99 — the measured time a queued request recently waited for
        admission — through the shared policy
        (:func:`~.batcher.retry_after_from_p99`) at the current queue
        fullness."""
        agg = self._qwait.aggregate_over("replica")
        s = agg.get("") or {}
        return retry_after_from_p99(
            s.get("p99"), queued / max(self.queue_size, 1))

    def _dispatch_locked(self, req: Request, live: list[Replica]) -> None:
        self._submit_to_locked(req, self._pick_locked(req, live))

    def _submit_to_locked(self, req: Request, target: Replica) -> None:
        req.replica = target.index
        # per-replica queues are sized at the global bound, so this never
        # raises QueueFullError here; a bad prompt still raises ValueError
        # before any accounting (nothing to undo)
        target.batcher.submit(req)
        self.routed[target.index] += 1
        self._m_routed[target.index].inc()

    def _pick_locked(self, req: Request, live: list[Replica]) -> Replica:
        if req.model is not None:
            # multi-model routing: only replicas with the model resident
            # are candidates — a miss everywhere is the client's error
            # (HTTP 404), not a capacity condition
            hosts = [r for r in live if r.engine.has_model(req.model)]
            if not hosts:
                raise UnknownModelError(
                    f"model {req.model!r} is not resident on any "
                    "routable replica")
            live = hosts
        sid = req.session_id
        if sid is not None:
            # affinity: the replica holding the session's carries owns the
            # session — even when heartbeat-stale (a transient stall must
            # not hard-fail a valid session; routing elsewhere would
            # GUARANTEE an "unknown session" error). No match → route by
            # load; the target batcher then fails an expired continuation
            # loudly (never decodes from zero state), exactly as in the
            # single-replica stack.
            for r in live:
                if sid in r.engine.cache:
                    return r
            # drain affinity: a DRAINING replica's kept sessions migrate
            # off as they go idle — a continuation racing that migration
            # is migrated HERE, just in time, so it never fails "unknown
            # session" mid-rollout
            target = self._drain_affinity_locked(sid, live)
            if target is not None:
                return target
            # tier residency (SessionTiers): the session was spilled off
            # its device slot. MEMORY tiers first — a replica holding the
            # session in its pending/host/evacuating tiers is the OWNER
            # with the freshest request boundary, and with a SHARED
            # --session-dir every replica's disk probe matches, possibly
            # against an older not-yet-overwritten file (filling that
            # elsewhere would silently decode stale tokens). Fill-ahead
            # promotes the memory copy so the state is already
            # device-resident when the continuation reaches admission;
            # skipped on a wedged replica (its locks may be held across a
            # dispatch that never returns — admission fills once it
            # wakes).
            for r in live:
                tiers = r.engine.tiers
                if tiers is not None and tiers.has_memory(sid):
                    if not self._suspect(r):
                        tiers.fill_ahead(sid)
                    return r
            # disk tier only: no live replica holds a fresher memory
            # copy, so the (shared) file IS the last flushed boundary —
            # any tiered replica can restore it; pick healthy ones by
            # load (stale replicas only as a last resort). The residency
            # stat is deduped per DISTINCT session directory: this runs
            # under the router's global lock, and an unknown-sid burst
            # must cost at most one stat per directory, not per replica.
            cands = []
            by_dir: dict[str, bool] = {}
            for r in live:
                tiers = r.engine.tiers
                if tiers is None:
                    continue
                d = tiers.disk_dir
                if d is None:
                    continue  # memory tiers already probed above
                hit = by_dir.get(d)
                if hit is None:
                    hit = by_dir[d] = tiers.has(sid)
                if hit:
                    cands.append(r)
            healthy = [r for r in cands if not self._suspect(r)]
            if cands:
                return min(healthy or cands,
                           key=lambda r: r.batcher.load())
        # fresh sessions avoid wedged (stale) and circuit-open
        # (partitioned) replicas while any healthy one exists — work
        # routed there hangs to client timeout (or fails fast against
        # an open circuit) while holding queue capacity
        fresh = [r for r in live if not self._suspect(r)]
        pool = fresh or live
        loads = [(r.batcher.load(), r) for r in pool]
        lo = min(load for load, _ in loads)
        cands = [r for load, r in loads if load == lo]
        return cands[next(self._rr) % len(cands)]

    def _drain_affinity_locked(self, sid: str,
                               live: list[Replica]) -> Replica | None:
        """Resolve a continuation whose session still lives on a
        DRAINING replica. Idle sessions are detach/restored onto a live
        peer right now — O(1) LSTM state, KBs, same cost bound as the
        fill-ahead this lock already tolerates — and the continuation
        follows, token-identical. A pinned (in-flight) session routes to
        the drainee itself while its scheduler still runs: it finishes
        the active work, and the session migrates once idle. Overlapping
        same-session submits can still race the detach window and fail
        once — the documented transient, unchanged by rollouts."""
        for d in self.replicas:
            if not d.draining or d.retired:
                continue
            cache = d.engine.cache
            if sid in cache:
                if cache.is_pinned(sid):
                    if d.alive():
                        return d
                    continue  # stopped mid-flight: unreachable in a
                    # controller-sequenced drain (load hits 0 first)
                try:
                    state = d.engine.detach_session(sid)
                except KeyError:
                    continue  # went idle and migrated under our probe
                healthy = [r for r in live if not self._suspect(r)]
                for target in sorted(healthy or live,
                                     key=lambda r: r.batcher.load()):
                    try:
                        target.engine.restore_session(sid, state)
                    except Exception:
                        continue  # every slot pinned: try the next
                    self.migrated_sessions += 1
                    self._m_migrated.inc()
                    return target
                # nowhere to put it: undo — serve where the state is
                d.engine.restore_session(sid, state)
                return d if d.alive() else None
            tiers = d.engine.tiers
            if (tiers is not None and tiers.has_memory(sid)
                    and d.alive()):
                # the drainee owns the freshest boundary and its
                # scheduler still runs — admission fills from the tier.
                # Once the controller stops the thread it evacuates the
                # tiers immediately, so the post-stop window falls
                # through to the shared-disk probe instead of hanging.
                return d
        return None

    # ---- rollout drain (controller-driven) ------------------------------

    def begin_drain(self, index: int) -> Replica:
        """Take replica ``index`` out of rotation for a rolling swap or
        resize. One replica at a time, and never the last routable one,
        so serving capacity stays >= N-1 for the whole rollout. The
        death sweep skips a draining replica — its scheduler thread is
        stopped deliberately, not dead."""
        with self._lock:
            rep = self._replica_locked(index)
            if rep.retired:
                raise ValueError(f"replica {index} is retired")
            for r in self.replicas:
                if r.draining and r is not rep:
                    raise RuntimeError(
                        f"replica {r.index} is already draining; "
                        "rollouts move one replica at a time")
            if not any(r.routable() and r is not rep
                       for r in self.replicas):
                raise RuntimeError(
                    "cannot drain the last routable replica")
            rep.draining = True
            return rep

    def end_drain(self, index: int) -> None:
        """Return a drained replica to rotation (rollout rejoin)."""
        with self._lock:
            self._replica_locked(index).draining = False

    def _replica_locked(self, index: int) -> Replica:
        for r in self.replicas:
            if r.index == index:
                return r
        raise ValueError(f"no replica with index {index}")

    # ---- canary shadowing ----------------------------------------------

    def set_canary(self, hook) -> None:
        """Install the rollout controller's shadow hook: called with
        every successfully admitted request, OUTSIDE the router lock.
        Exceptions are swallowed at the call site — a shadow must never
        fail the primary it mirrors."""
        self._canary = hook

    def clear_canary(self) -> None:
        self._canary = None

    # ---- replica-death handling ----------------------------------------

    def set_stopping(self, stopping: bool) -> None:
        """A deliberate ``stop()`` joins every scheduler thread — the
        sweep must not mistake that for death and start requeueing."""
        with self._lock:
            self._stopping = bool(stopping)

    def sweep(self) -> None:
        """Detect replicas whose scheduler thread DIED (started, then
        exited outside ``stop()``) and retire each exactly once.
        Piggybacked on submit() and the health probe — O(replicas) when
        nothing died, so no monitor thread is needed."""
        claimed: list[Replica] = []
        with self._lock:
            if self._stopping:
                return
            for r in self.replicas:
                # a draining replica's thread is stopped DELIBERATELY by
                # the rollout controller — not a death
                if (not r.retired and not r.draining
                        and r.thread is not None
                        and not r.thread.is_alive()):
                    r.retired = True  # claim under the lock, clean outside
                    claimed.append(r)
        for r in claimed:
            self._retire(r)

    def _retire(self, dead: Replica) -> None:
        """Runs OUTSIDE the router lock: reaches into the dead replica's
        batcher and cache (their own locks) and resubmits through the
        normal routing path."""
        drained = dead.batcher.drain_queue()
        failed = dead.batcher.fail_inflight(
            f"replica {dead.index} scheduler died mid-request; its decode "
            "position is indeterminate under dispatch-ahead windows "
            "(state lost — resend the request)")
        # migrate idle kept sessions FIRST so a drained continuation is
        # requeued to wherever its state now lives
        self.migrate_from(dead)
        self.requeue(drained, dead)
        with self._lock:
            self.failed_on_death += failed
        if failed:
            self._m_failed_death.inc(failed)

    def migrate_from(self, rep: Replica) -> tuple[int, int]:
        """Move every kept session off ``rep``: device-resident idle
        sessions via detach/restore onto a live healthy peer, tier-held
        sessions via :meth:`SessionTiers.evacuate` (shared disk when one
        exists, else adopted into a peer's host tier). Shared by
        replica-death retirement and the rollout controller's drain —
        which is why targets exclude ``rep`` explicitly and skip
        draining peers rather than relying on ``alive()`` alone.
        Returns ``(migrated, lost)`` and folds both into the router's
        aggregate counters. Runs OUTSIDE the router lock (takes it
        briefly per session)."""
        migrated = lost = 0
        for sid in rep.engine.cache.session_ids():
            if sid.startswith(PREFIX_SID_NAMESPACE):
                continue  # prefix entries are an optimisation — they die
                # with their replica and re-seed from live traffic
            try:
                state = rep.engine.detach_session(sid)
            except KeyError:
                continue  # raced an eviction; nothing to move
            placed = False
            with self._lock:
                targets = [r for r in self.replicas
                           if r.routable() and r is not rep]
            # healthy targets ONLY — no wedged fallback: a wedged
            # replica's engine lock may be held across a dispatch that
            # never returns, so restore_session could block this thread
            # (a health probe!) forever, and even a successful restore
            # parks the session where continuations hang to client
            # timeout. No healthy target → the session is lost, honestly.
            healthy = [r for r in targets if not self._suspect(r)]
            for target in sorted(healthy,
                                 key=lambda r: r.batcher.load()):
                try:
                    target.engine.restore_session(sid, state)
                except Exception:
                    continue  # cache full of pinned slots: try the next
                if not target.alive():
                    # the target died while the restore was in flight
                    # (double death): a session landed in a corpse's cache
                    # is unreachable — pull it back out and keep looking
                    # rather than reporting a migration that never helps
                    try:
                        state = target.engine.detach_session(sid)
                    except Exception:
                        break  # its own retirement already took the sid
                    continue
                placed = True
                break
            if placed:
                migrated += 1
                self._m_migrated.inc()
            else:
                lost += 1
        # tier-held sessions (spilled to host RAM / pending spills) are
        # still reachable — the replica's THREAD died (or was stopped),
        # not the process. Persist them to the shared disk tier when one
        # exists (any live replica then fills from it on demand), else
        # adopt them into a live healthy replica's host tier.
        if rep.engine.tiers is not None:
            persisted, homeless = rep.engine.tiers.evacuate()
            migrated += persisted
            if persisted:
                self._m_migrated.inc(persisted)
            for sid, state in homeless:
                with self._lock:
                    targets = [r for r in self.replicas
                               if r.routable() and r is not rep
                               and r.engine.tiers is not None
                               and not self._suspect(r)]
                target = min(targets, key=lambda r: r.batcher.load(),
                             default=None)
                if target is not None:
                    target.engine.tiers.adopt(sid, state)
                    migrated += 1
                    self._m_migrated.inc()
                else:
                    lost += 1
        with self._lock:
            self.migrated_sessions += migrated
            self.lost_sessions += lost
        return migrated, lost

    def requeue(self, reqs: list[Request], source: Replica) -> int:
        """Resubmit drained, not-yet-admitted requests through the
        normal routing path. Deadlines survive: ``Batcher.submit`` only
        stamps ``t_submit``/``deadline`` when unset, so a requeued
        request keeps its original clock. No global-bound recheck —
        these requests already held queue slots before the drain.
        Concurrent submits can still steal that headroom (the drain
        released it before this loop re-enqueues), so capacity is
        checked under the router lock (every client submit serialises
        through it) and a full affinity pick falls back to any live
        replica with room — no exception-driven retry, so the
        per-replica rejected counters never see these internal probes.
        Returns the number requeued; the rest fail honestly on
        ``source``'s batcher. Shared by replica-death retirement and
        the rollout controller's drain."""
        requeued = 0
        for req in reqs:
            try:
                with self._lock:
                    live = [r for r in self.replicas
                            if r.routable() and r is not source]
                    if not live:
                        raise RuntimeError("no live replica schedulers")
                    target = self._pick_locked(req, live)
                    if target.batcher.queued() >= self.queue_size:
                        if req.session_id is not None:
                            # never override affinity: rerouting a
                            # continuation to a replica without its state
                            # would fail it "unknown session" while the
                            # session is intact — queue-full is the
                            # honest verdict here
                            raise QueueFullError(
                                "the session's replica queue is full")
                        target = next(
                            (r for r in sorted(
                                live, key=lambda x: x.batcher.queued())
                             if r.batcher.queued() < self.queue_size),
                            None)
                    if target is None:
                        raise QueueFullError(
                            "every live replica's queue is full")
                    self._submit_to_locked(req, target)
                requeued += 1
                self._m_requeued.inc()
            except Exception as e:
                source.batcher.fail_request(
                    req, f"replica {source.index} went out of rotation "
                         f"and the request could not be requeued: {e}")
        with self._lock:
            self.requeued += requeued
        return requeued

    def reroute(self, req: Request, source: Replica) -> bool:
        """Re-pick a replica for a request whose remote RPC provably
        NEVER reached ``source`` (``TransportError.executed is False``:
        connect refused/timed out, or circuit fail-fast). Because
        nothing executed, resending — even a kept continuation, which
        the shared disk tier fills on the survivor — cannot double-
        decode. No global-bound recheck (the request already holds its
        admission slot); bounded by the fleet size so a total outage
        settles honestly instead of ping-ponging. Returns True when a
        new replica accepted the request."""
        req.reroutes += 1
        if req.reroutes > max(len(self.replicas) - 1, 1):
            return False
        try:
            with self._lock:
                live = [r for r in self.replicas
                        if r.routable() and r is not source]
                if not live:
                    return False
                self._submit_to_locked(req, self._pick_locked(req, live))
                self.rerouted += 1
        except Exception:
            return False
        self._m_rerouted.inc()
        return True

    # ---- views ---------------------------------------------------------

    def stats(self) -> dict:
        with self._lock:
            return {
                "replicas": len(self.replicas),
                "live": sum(1 for r in self.replicas if r.alive()),
                "retired": [r.index for r in self.replicas if r.retired],
                "draining": [r.index for r in self.replicas
                             if r.draining],
                "queue_size": self.queue_size,
                "routed": {str(k): v
                           for k, v in sorted(self.routed.items())},
                "rejected": self.rejected,
                "shed_by_class": dict(self.shed),
                "tenant_limited": dict(self.tenant_limited),
                "tenant_rate": self.tenant_rate,
                "best_effort_bound": self._best_effort_bound,
                "best_effort_frac": self.best_effort_frac,
                "requeued": self.requeued,
                "rerouted": self.rerouted,
                "failed_on_death": self.failed_on_death,
                "migrated_sessions": self.migrated_sessions,
                "lost_sessions": self.lost_sessions,
            }
