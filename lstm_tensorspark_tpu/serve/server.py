"""Serving front-ends: in-process synchronous client + stdlib HTTP server.

:class:`ServeServer` owns N replicas (one engine + batcher + scheduler
thread each) behind an admission :class:`~.router.Router` — a single
engine is the classic one-replica stack, a list of engines is the
data-parallel ``--replicas N`` stack (session→replica affinity, global
bounded admission, honest replica-death handling; serve/router.py).
:meth:`ServeServer.generate` is the synchronous request path used by
both front-ends:

- :class:`InprocessClient` — the test/loadgen client: same admission,
  batching and backpressure semantics as HTTP, no sockets;
- :func:`make_http_server` — a stdlib ``ThreadingHTTPServer`` JSON
  endpoint (no new dependencies):

  - ``POST /v1/generate``  body ``{"prompt": [ids], "max_new_tokens": N,
    "greedy": true, "temperature": t, "top_k": k, "top_p": p,
    "session_id": "...", "keep_session": false, "eos_id": null,
    "use_prefix": true}`` →
    ``{"tokens": [...], "session_id": "...", "latency_ms": ...,
    "ttft_ms": ..., "max_itl_ms": ...}`` (time-to-first-token and the
    request's worst inter-token gap — windowed decode delivers K tokens
    per burst, and a client deciding whether to pin ``--decode-window 1``
    needs to SEE that, not guess it);
  - ``GET /healthz`` → honest liveness fanned in across replicas:
    ``status`` is ``ok`` / ``degraded`` (some replicas dead or wedged —
    still 200, survivors are serving) / ``down`` (503), with per-replica
    alive/stale/heartbeat-age detail (a wedged server must fail probes,
    not smile at them);
    ``GET /stats`` (alias ``/v1/stats``) → batcher/engine/cache counters:
    per-key compile counts, prefix-cache hit/miss/evict/invalidate,
    state-cache swap generation, prefill-chunk/window dispatch counts,
    plus ``metrics`` — histogram summaries (p50/p99) and counter/gauge
    values from the telemetry registry (obs/);
  - ``GET /metrics`` → Prometheus text exposition of the same registry
    (histograms as cumulative buckets): server-side TTFT,
    inter-token-latency and queue-wait distributions, scheduler
    iteration time, readback latency, compile/cache/prefix counters —
    the live-server view of what loadgen could only measure offline.

  Each generate reply also carries ``phases_ms`` — the request's own
  queue/prefill/decode/readback host-time breakdown (the per-request
  trace timeline, summarised; the full timeline goes to ``--trace``).

  Backpressure maps to HTTP: full queue → 429, bad request → 400,
  scheduler failure → 500, timeout → 504.
"""

from __future__ import annotations

import json
import threading
import time
import urllib.parse
from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

from .batcher import (
    CLASSES,
    Batcher,
    DeadlineExceededError,
    QueueFullError,
    Request,
)
from .engine import GREEDY, SamplingParams, ServeEngine, UnknownModelError
from .router import Replica, Router
from .state_cache import PREFIX_SID_NAMESPACE, PREFIX_STATS_CONFIG_KEYS


class _ReplicaStop:
    """Per-replica stop signal layered over the server-wide one: the
    rollout controller stops ONE scheduler (drain → swap → rejoin)
    without touching its peers. ``Batcher.run`` only polls
    ``is_set()``; ``wait()`` completes the Event-shaped surface for
    code that parks on the stop signal (the wedged-scheduler test
    stub) — without it such a thread dies with AttributeError and the
    liveness sweep retires a replica that was merely stuck."""

    __slots__ = ("server_stop", "local")

    def __init__(self, server_stop: threading.Event):
        self.server_stop = server_stop
        self.local = threading.Event()

    def is_set(self) -> bool:
        return self.server_stop.is_set() or self.local.is_set()

    def wait(self, timeout: float | None = None) -> bool:
        # OR over two Events with no shared condition to block on:
        # park on the server-wide one in short slices, re-checking the
        # local flag each wake (≤50 ms extra latency on a local stop)
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        while not self.is_set():
            step = 0.05
            if deadline is not None:
                step = min(step, deadline - time.monotonic())
                if step <= 0:
                    return False
            self.server_stop.wait(step)
        return True

#: aggregated batcher counters summed across replicas in stats(); config
#: fields (window ladder etc.) are taken from replica 0 instead
_SUMMED_BATCHER_KEYS = (
    "submitted", "completed", "rejected", "failed", "timed_out",
    "queued", "active", "prefilling", "windows_pipelined",
    "tokens_generated",
    "prefill_chunks_dispatched", "prefix_resumed", "prefix_tokens_saved",
    "prefill_tokens_computed",
)


class ServeServer:
    """N replicas (engine + batcher + scheduler thread each) behind an
    admission router, with a synchronous submit path.

    ``engine`` may be a single :class:`ServeEngine` (the classic
    one-replica stack — every existing call site) or a list of engines
    (``cli serve --replicas N``): one :class:`Batcher` is built per
    engine and the :class:`Router` spreads fresh sessions by load while
    keeping session continuations replica-affine. ``queue_size`` is the
    GLOBAL admission bound, enforced at the router.

    ``health_stale_after``: seconds of scheduler-heartbeat silence before
    a replica counts unhealthy even though its thread is alive — the
    wedged-dispatch case (thread stuck inside a device call that never
    returns) where ``is_alive()`` stays true forever. An idle scheduler
    beats the heartbeat every ``idle_wait`` (~0.05 s), so any healthy
    server sits far below the default."""

    def __init__(self, engine, batcher: Batcher | None = None,
                 health_stale_after: float = 60.0,
                 best_effort_queue_frac: float = 0.5,
                 deadline_defaults: dict | None = None,
                 sweep_interval: float | None = None,
                 remote_replicas: tuple[str, ...] = (),
                 remote_timeout_s: float | None = 120.0,
                 remote_rpc_timeout_s: float = 5.0,
                 remote_poll_interval_s: float = 0.5,
                 autotune=None,
                 tenant_rate: float | None = None,
                 tenant_burst: float = 5.0,
                 model_registry=None,
                 rollout_kw: dict | None = None, **batcher_kw):
        engines = (list(engine) if isinstance(engine, (list, tuple))
                   else [engine])
        if not engines:
            # remote-only fleets are deliberately unsupported: replica 0
            # anchors the registry, the back-compat engine/batcher views,
            # and the shared-session-dir failover target host death
            # depends on — a front with zero local capacity would also
            # lose every kept session with its last remote host
            raise ValueError(
                "ServeServer needs at least one LOCAL engine (remote "
                "replicas ride behind it via remote_replicas=)")
        if sweep_interval is not None and sweep_interval <= 0:
            raise ValueError(
                f"sweep_interval must be > 0 or None, got {sweep_interval}")
        # per-class default deadlines (seconds): applied in generate()
        # when the request names none — the serve plane's promise that
        # NO admitted request can wait/decode forever. None per class =
        # no default (the shipped default, back-compat).
        self.deadline_defaults = {c: None for c in CLASSES}
        if deadline_defaults:
            for c, v in deadline_defaults.items():
                if c not in CLASSES:
                    raise ValueError(f"unknown admission class {c!r}")
                if v is not None and v < 0:
                    raise ValueError(
                        f"deadline_defaults[{c!r}] must be >= 0 or None, "
                        f"got {v}")
                # 0 normalizes to None (the CLI's 0-means-none
                # convention) HERE, at construction — otherwise every
                # request of the class would fail Request validation at
                # runtime with a client-blaming 400
                self.deadline_defaults[c] = v if v else None
        if batcher is not None and len(engines) > 1:
            raise ValueError(
                "an explicit batcher only makes sense for a single-replica "
                "server; pass batcher_kw for replicated stacks")
        self.replicas: list[Replica] = []
        for i, eng in enumerate(engines):
            b = batcher if (batcher is not None and i == 0) else Batcher(
                eng, replica=i, **batcher_kw)
            if eng.tiers is not None:
                # tier metrics carry the replica label like every other
                # serve family — rebinding here covers engines built
                # without an explicit replica index
                eng.tiers.set_replica(i)
            self.replicas.append(Replica(i, eng, b))
        # remote replicas (serve/remote.py): peer serve PROCESSES behind
        # this router — the RPC shim satisfies the same replica surface,
        # its heartbeat poller is the scheduler thread start() drives,
        # and host death retires through the exact replica-death path.
        # Indexed after the locals, so replica 0 (the engine/batcher
        # back-compat views, the registry anchor) stays in-process.
        remotes = []
        for url in remote_replicas:
            from .remote import RemoteReplica

            rep = RemoteReplica(
                len(self.replicas), url, registry=engines[0].metrics,
                queue_size=self.replicas[0].batcher.queue_size,
                poll_interval=remote_poll_interval_s,
                rpc_timeout=remote_rpc_timeout_s,
                generate_timeout_s=remote_timeout_s)
            self.replicas.append(rep)
            remotes.append(rep)
        # the global admission bound == the per-replica queue bound, so
        # the router's check is the only one that ever fires
        self.router = Router(
            self.replicas, queue_size=self.replicas[0].batcher.queue_size,
            stale_after=health_stale_after,
            best_effort_frac=best_effort_queue_frac,
            registry=engines[0].metrics,
            tenant_rate=tenant_rate, tenant_burst=tenant_burst)
        # wire the provably-undelivered reroute path: a remote RPC that
        # failed before delivery (connect refused/timed out, circuit
        # fail-fast) re-enters routing instead of settling "state lost"
        for rep in remotes:
            rep.batcher.set_reroute(
                lambda req, _r=rep: self.router.reroute(req, _r))
        # prefix-state fabric propagation (serve/prefix_trie.py): every
        # LOCAL trie pushes its hot inserts to every remote peer through
        # that peer's OWN transport/circuit (RemoteBatcher.transport), so
        # one replica's cold prefill warms the fleet. Exact-match
        # PrefixCache stores have no adopt path and are left alone.
        self._propagators = []
        if remotes:
            from .prefix_trie import PrefixPropagator

            peer_shims = [rep.batcher for rep in remotes]
            for r in self.replicas:
                trie = getattr(r.engine, "prefix", None)
                if trie is not None and hasattr(trie, "attach_propagator"):
                    prop = PrefixPropagator(
                        trie, peer_shims, rpc_timeout=remote_rpc_timeout_s)
                    trie.attach_propagator(prop)
                    self._propagators.append(prop)
        # peer-side replay dedup for the generate POST: remote fronts
        # mint a request_id per request; a retried delivery whose first
        # attempt executed replays the settled reply instead of
        # double-decoding (exactly-once effect; serve/transport.py)
        from .transport import SettledCache

        self.settled = SettledCache(registry=engines[0].metrics)
        self.health_stale_after = health_stale_after
        # online autotuner (serve/autotune.py): built over the finished
        # stack so it sees every replica/tier/router surface; its
        # controller thread is started by start() and JOINED by stop()
        # (the thread-lifecycle contract lives inside AutoTuner itself).
        # None (the default) is byte-identical pre-autotuner behavior —
        # no thread, no knob ever moves.
        self.autotuner = None
        if autotune is not None:
            from .autotune import AutoTuner

            self.autotuner = AutoTuner(self, autotune)
        # rollout controller (serve/rollout.py): registry-backed rolling
        # weight swaps and slot resizes over this stack. None (the
        # default) = no registry, no controller thread, no new behavior.
        # ``model_registry`` is a ModelRegistry or a directory path.
        self.rollout = None
        if model_registry is not None:
            from .rollout import RolloutController

            self.rollout = RolloutController(
                self, model_registry, **(rollout_kw or {}))
        # the last warmup spec, remembered so the rollout controller can
        # replay the full compile-key lattice off-path before a swapped/
        # resized replica rejoins (None until warmup() runs)
        self._warmup_spec: tuple | None = None
        self._replica_stops: dict[int, _ReplicaStop] = {}
        self._model_info_seen: set[tuple[str, str]] = set()
        # optional periodic death sweep: the sweep normally piggybacks on
        # submits and health probes, so a dead replica on a QUIET server
        # is only retired when the next probe lands — an interval makes
        # retirement (requeue/migrate) happen within sweep_interval even
        # with no traffic and no prober
        self.sweep_interval = sweep_interval
        self._sweep_thread: threading.Thread | None = None
        self._stop = threading.Event()

    # ---- single-replica views (back-compat + convenience) --------------

    @property
    def engine(self) -> ServeEngine:
        """Replica 0's engine (THE engine of a single-replica server)."""
        return self.replicas[0].engine

    @property
    def batcher(self) -> Batcher:
        """Replica 0's batcher (THE batcher of a single-replica server)."""
        return self.replicas[0].batcher

    @property
    def _thread(self) -> threading.Thread | None:
        return self.replicas[0].thread

    # ---- lifecycle -----------------------------------------------------

    def start(self) -> "ServeServer":
        if any(r.thread is not None for r in self.replicas):
            raise RuntimeError("server already started")
        self._stop.clear()
        for r in self.replicas:
            # a stop()/start() restart revives retired replicas: their
            # death cleanup (requeue/fail/migrate) already ran, and the
            # fresh scheduler thread below serves again — leaving the
            # flag set would make the router refuse them forever while
            # health reports the new thread alive
            r.retired = False
            self._start_replica(r)
        # re-arm the death sweep only once every thread is RUNNING: a
        # concurrent probe/submit sweeping between `r.thread = t` and
        # `t.start()` would see a not-yet-alive thread and retire a
        # replica that is about to serve
        self.router.set_stopping(False)
        if self.sweep_interval is not None:
            t = threading.Thread(target=self._sweep_loop,
                                 name="serve-death-sweeper", daemon=True)
            self._sweep_thread = t
            t.start()
        if self.autotuner is not None:
            self.autotuner.start()
        if self.rollout is not None:
            self.rollout.start()
        return self

    def _start_replica(self, r: Replica) -> None:
        """Start (or restart, after a rollout drain) one replica's
        scheduler thread under a fresh per-replica stop signal. Target
        resolved at start time so tests can monkeypatch replica
        batchers' run/step before (or between) starts."""
        stop = _ReplicaStop(self._stop)
        self._replica_stops[r.index] = stop
        t = threading.Thread(
            target=r.batcher.run, args=(stop,),
            name=f"serve-scheduler-{r.index}", daemon=True,
        )
        r.thread = t
        t.start()

    def _stop_replica(self, r: Replica, timeout: float = 10.0) -> None:
        """Stop ONE replica's scheduler (the rollout controller's drain
        step — the replica must already be out of rotation and idle;
        the run loop's exit path would fail anything still pending)."""
        stop = self._replica_stops.get(r.index)
        if stop is not None:
            stop.local.set()
        if r.thread is not None:
            r.thread.join(timeout=timeout)

    def _sweep_loop(self) -> None:
        # stop() sets self._stop, which this loop's wait reads — the
        # thread parks within one interval of a shutdown
        while not self._stop.wait(self.sweep_interval):
            self.router.sweep()

    def stop(self) -> None:
        # the controllers park FIRST: knobs must not move and no drain
        # may start while the schedulers are being joined (both threads
        # are joined here — the thread-lifecycle contract)
        if self.rollout is not None:
            self.rollout.stop()
        if self.autotuner is not None:
            self.autotuner.stop()
        # mark the stop BEFORE joining: the router's death sweep must not
        # mistake deliberately-joined scheduler threads for crashes and
        # start requeueing a shutting-down server's work
        self.router.set_stopping(True)
        self._stop.set()
        if self._sweep_thread is not None:
            self._sweep_thread.join(timeout=10.0)
            self._sweep_thread = None
        for r in self.replicas:
            if r.thread is not None:
                r.thread.join(timeout=10.0)
                r.thread = None
        for r in self.replicas:
            if r.engine.tiers is not None:
                # durability barrier: a clean stop lands every kept
                # session's write-behind checkpoint on the disk tier, so
                # stop → start resumes them all (tests/test_serve_tiers);
                # close() then parks the spill worker (a later start's
                # first enqueue revives it) so stopped stacks don't leak
                # polling threads
                r.engine.tiers.flush(timeout=10.0)
                r.engine.tiers.close()
        for prop in self._propagators:
            # park the fabric's propagation workers: undelivered queue
            # entries are best-effort warmth, not durable state
            prop.close()

    def warmup(self, sampling: SamplingParams = GREEDY,
               prompt_lens: tuple[int, ...] = (1,)) -> int:
        """Pre-compile everything the schedulers can dispatch for these
        prompt lengths, on EVERY replica (each engine owns its compiled
        programs). Delegates to each batcher, which derives the chunk /
        prefix-insert split and window-ladder programs from its own
        policy — the one warmup entry point front-ends should use.
        Returns the total number of cached programs across replicas.

        The spec is remembered: the rollout controller replays it on a
        swapped/resized replica before that replica rejoins rotation, so
        a rollout never reintroduces mid-traffic compiles."""
        self._warmup_spec = (sampling, tuple(prompt_lens))
        return sum(r.batcher.warmup(sampling, prompt_lens=prompt_lens)
                   for r in self.replicas)

    def __enter__(self) -> "ServeServer":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # ---- request path --------------------------------------------------

    def generate(
        self,
        prompt,
        *,
        max_new_tokens: int,
        sampling: SamplingParams = GREEDY,
        session_id: str | None = None,
        keep_session: bool = False,
        eos_id: int | None = None,
        use_prefix: bool = True,
        timeout: float = 120.0,
        klass: str = "priority",
        deadline_s: float | None = None,
        tenant: str | None = None,
        model: str | None = None,
    ) -> Request:
        """Submit and block until the request completes; returns the filled
        :class:`Request` (``.tokens``, ``.session_id``, ``.replica``,
        timestamps). Raises :class:`QueueFullError` (backpressure/shed —
        carries ``retry_after_s``), :class:`DeadlineExceededError` (the
        server-side deadline lapsed; ``.request`` holds the partial
        output), ``TimeoutError`` (client-side wait bound), or
        ``RuntimeError`` on a scheduler-side failure.

        ``deadline_s`` defaults to the server's per-class policy
        (``deadline_defaults``); an EXPLICIT ``deadline_s <= 0`` opts out
        of that default (the CLI's documented 0-means-none semantics —
        without it a client on a defaulted server could never request an
        unbounded run). The absolute deadline is stamped at submission
        and enforced at admission, in the queue, and at every
        decode-window boundary."""
        if deadline_s is None:
            deadline_s = self.deadline_defaults.get(klass)
        elif deadline_s <= 0:
            deadline_s = None  # explicit opt-out of the per-class default
        req = Request(
            prompt, max_new_tokens, sampling=sampling,
            session_id=session_id, keep_session=keep_session, eos_id=eos_id,
            use_prefix=use_prefix, klass=klass, deadline_s=deadline_s,
            tenant=tenant, model=model,
        )
        self.router.submit(req)
        if not req.done.wait(timeout):
            # tell the scheduler to stop working for a client that left —
            # otherwise abandoned requests hold queue/slot capacity and
            # decode tokens nobody reads (504 + retry = load amplification)
            req.cancelled = True
            raise TimeoutError(
                f"request {req.id} not completed within {timeout:.0f}s"
            )
        if req.timed_out:
            # honest server-side expiry: the partial output rides on the
            # exception — the HTTP layer returns it, never a wedged client
            raise DeadlineExceededError(req)
        if req.error is not None:
            retry = getattr(req, "remote_shed_retry_after", None)
            if retry is not None:
                # a REMOTE replica shed this request after routing
                # (serve/remote.py): re-raise as the same retryable 429
                # a local shed produces, with the peer's measured
                # Retry-After — not a hard RuntimeError/500
                raise QueueFullError(req.error, retry_after_s=retry)
            raise RuntimeError(req.error)
        return req

    def has_session(self, session_id: str) -> bool:
        """Fleet-wide session residency (device slots OR tiers on any
        replica) — the ``/replica/has_session`` affinity probe a FRONT
        router's RPC shim asks before routing a continuation here."""
        return any(r.engine.has_session(session_id)
                   for r in self.replicas
                   if hasattr(r.engine, "has_session"))

    @staticmethod
    def _aggregate_batcher(snapshots: list[dict]) -> dict:
        """THE cross-replica batcher aggregation — one implementation
        for ``stats()`` and ``replica_heartbeat()``, so a counter added
        to ``_SUMMED_BATCHER_KEYS`` (or a new merged dict) can never
        diverge between the two views. Seeds from the first snapshot
        (config fields ride along; merged dicts deep-copied so summing
        never mutates replica 0's reported view), sums the counter
        keys, and merges the per-K / per-class dicts."""
        agg: dict = {}
        for b in snapshots:
            if not agg:
                agg = dict(b)
                agg["windows_dispatched"] = dict(
                    b.get("windows_dispatched") or {})
                agg["queued_by_class"] = dict(
                    b.get("queued_by_class") or {})
                continue
            for k in _SUMMED_BATCHER_KEYS:
                agg[k] += b.get(k, 0)
            for k, v in (b.get("windows_dispatched") or {}).items():
                agg["windows_dispatched"][k] = (
                    agg["windows_dispatched"].get(k, 0) + v)
            for k, v in (b.get("queued_by_class") or {}).items():
                agg["queued_by_class"][k] = (
                    agg["queued_by_class"].get(k, 0) + v)
        agg.pop("replica", None)  # the aggregate is not one replica's view
        return agg

    def replica_heartbeat(self) -> dict:
        """Lightweight liveness + load payload for a front-of-fleet
        router's RPC shim (``GET /replica/heartbeat``): the health
        verdict plus the summed batcher counters — deliberately WITHOUT
        the metrics summaries /stats carries, because the shim polls
        this every ~0.5 s."""
        health = self.health()
        agg = self._aggregate_batcher(
            [r.batcher.stats() for r in self.replicas])
        return {
            "ok": health["ok"],
            "status": health["status"],
            "queued": health["queued"],
            "active": health["active"],
            "replicas_healthy": health["replicas_healthy"],
            "replicas_total": health["replicas_total"],
            "sessions": sum(len(r.engine.cache)
                            for r in self.replicas
                            if hasattr(r.engine.cache, "__len__")),
            # resident session ids (device slots AND tiers): the front's
            # RPC shim answers affinity probes from this snapshot so the
            # admission plane never blocks on a per-continuation GET.
            # None = truncated (a fleet past the cap falls back to the
            # shared-disk probe front-side — correct, just less warm).
            "session_ids": self._resident_session_ids(),
            "batcher": agg,
            # the prefix-store section a polling front mirrors into its
            # _RemoteEngine.stats() (None when no local replica runs a
            # prefix store) — keeps /stats honest fleet-wide
            "prefix_cache": self._aggregate_prefix(),
        }

    def _aggregate_prefix(self) -> dict | None:
        """Sum prefix-store counters across local replicas; config keys
        (:data:`PREFIX_STATS_CONFIG_KEYS`) keep the first store's value
        — stride/max/mode are fleet-uniform by construction (one CLI
        builds every replica). Works for both store modes: the stats
        contract is a FLAT dict of ints plus config scalars."""
        stats_list = [r.engine.prefix.stats() for r in self.replicas
                      if getattr(r.engine, "prefix", None) is not None]
        if not stats_list:
            return None
        agg = dict(stats_list[0])
        for s in stats_list[1:]:
            for k, v in s.items():
                if k in PREFIX_STATS_CONFIG_KEYS:
                    continue
                agg[k] = agg.get(k, 0) + v
        return agg

    #: heartbeat residency-list cap: past this the payload reports None
    #: (truncated) instead of shipping an unbounded id list every poll
    MAX_HEARTBEAT_SESSIONS = 4096

    def _resident_session_ids(self) -> list[str] | None:
        ids: set[str] = set()
        for r in self.replicas:
            cache = r.engine.cache
            if hasattr(cache, "session_ids"):
                ids.update(s for s in cache.session_ids()
                           if not s.startswith(PREFIX_SID_NAMESPACE))
            tiers = getattr(r.engine, "tiers", None)
            if tiers is not None and hasattr(tiers, "session_ids"):
                ids.update(tiers.session_ids())
            if len(ids) > self.MAX_HEARTBEAT_SESSIONS:
                return None
        return sorted(ids)

    def stats(self) -> dict:
        """Aggregate view + per-replica detail. Top-level ``batcher`` sums
        counters across replicas (identical to replica 0's stats on a
        single-replica server); top-level engine fields stay replica 0's
        for back-compat; ``replicas`` carries each replica's full
        batcher/engine stats and ``router`` the routing/requeue/migration
        counters."""
        per = []
        for r in self.replicas:
            # ONE stats() call per replica: the aggregate and this
            # replica's detail in one reply describe the same instant
            per.append({"replica": r.index, "batcher": r.batcher.stats(),
                        **r.engine.stats()})
        agg = self._aggregate_batcher([p["batcher"] for p in per])
        rt = self.router.stats()
        # router-level 429s are THE backpressure count of the replicated
        # stack (per-replica bounds never fire; see Router docstring)
        agg["rejected"] += rt["rejected"]
        return {"batcher": agg, **self.engine.stats(), "router": rt,
                "replicas": per, "metrics": self.metrics_summary(),
                # controller decisions + the last windowed (recent-
                # biased) signal deltas; None = autotuning off
                "autotune": (None if self.autotuner is None
                             else self.autotuner.stats()),
                # registry/rollout state; None = no registry attached
                "rollout": (None if self.rollout is None
                            else self.rollout.stats()),
                # fleet-wide model residency {model: {version: replica
                # count}} — two versions of one model nonzero at once
                # OUTSIDE an active rollout is the version-skew runbook
                # signature
                "models": self.resident_models()}

    def resident_models(self) -> dict:
        """{model: {version: replica_count}} across local replicas."""
        models: dict = {}
        for r in self.replicas:
            resident = getattr(r.engine, "resident_models", None)
            if resident is None:
                continue
            for mid, ver in resident().items():
                by_ver = models.setdefault(mid, {})
                by_ver[str(ver)] = by_ver.get(str(ver), 0) + 1
        return models

    def _collect_gauges(self) -> None:
        """Refresh poll-style gauges at scrape time — an idle server's
        schedulers may not have run since the last change, and cache
        occupancy is cheapest read on demand. One child per replica."""
        reg = self.engine.metrics
        live = dead = 0
        for r in self.replicas:
            rl = str(r.index)
            b = r.batcher.stats()
            reg.gauge("serve_queue_depth", labelnames=("replica",)).labels(
                replica=rl).set(b["queued"])
            reg.gauge("serve_active_sessions",
                      labelnames=("replica",)).labels(
                replica=rl).set(b["active"])
            reg.gauge("serve_prefilling_sessions",
                      labelnames=("replica",)).labels(
                replica=rl).set(b["prefilling"])
            c = r.engine.cache.stats()
            fam = reg.gauge("serve_state_cache_slots",
                            "state-cache slot occupancy",
                            labelnames=("replica", "state"))
            fam.labels(replica=rl, state="live").set(c["live_sessions"])
            fam.labels(replica=rl, state="pinned").set(c["pinned"])
            fam.labels(replica=rl, state="free").set(c["free"])
            if r.engine.prefix is not None:
                ps = r.engine.prefix.stats()
                reg.gauge("serve_prefix_cache_entries",
                          "live prefix-cache entries",
                          labelnames=("replica",)).labels(replica=rl).set(
                    ps["entries"])
                if "nodes_device" in ps:
                    # fabric mode: node population by residency kind —
                    # device (slot-backed), spilled (host tier, within
                    # the byte bound), structural (stateless radix
                    # splits)
                    fam = reg.gauge(
                        "serve_prefix_trie_nodes",
                        "prefix-trie nodes by residency kind",
                        labelnames=("replica", "kind"))
                    fam.labels(replica=rl, kind="device").set(
                        ps["nodes_device"])
                    fam.labels(replica=rl, kind="spilled").set(
                        ps["nodes_spilled"])
                    fam.labels(replica=rl, kind="structural").set(
                        ps["nodes_structural"])
            if r.engine.tiers is not None:
                ts = r.engine.tiers.stats()
                fam = reg.gauge("serve_tier_entries",
                                "spilled session states held per tier "
                                "(pending = spill captured, fetch not "
                                "done)",
                                labelnames=("tier", "replica"))
                for tier in ("pending", "host", "disk"):
                    fam.labels(tier=tier, replica=rl).set(
                        ts["entries"][tier])
            if r.alive():
                live += 1
            else:
                dead += 1
        fam = reg.gauge("serve_replicas",
                        "replica schedulers by liveness state",
                        labelnames=("state",))
        fam.labels(state="live").set(live)
        fam.labels(state="dead").set(dead)
        # model residency: replicas hosting each (model, version). Pairs
        # that vanish (a completed rollout's old version) are pinned to
        # 0, not dropped — a flatlined-to-zero child is how the scrape
        # side SEES the cutover complete
        fam = reg.gauge(
            "serve_model_info",
            "replicas hosting each resident model version (two versions "
            "of one model nonzero at once outside a rollout = version "
            "skew; see docs/OPERATIONS.md)",
            labelnames=("model", "version"))
        current = {}
        for mid, by_ver in self.resident_models().items():
            for ver, count in by_ver.items():
                current[(mid, ver)] = count
        for key in self._model_info_seen - set(current):
            fam.labels(model=key[0], version=key[1]).set(0)
        for (mid, ver), count in current.items():
            fam.labels(model=mid, version=ver).set(count)
        self._model_info_seen |= set(current)

    def metrics_text(self) -> str:
        """Prometheus text exposition of the serve stack's registry
        (``GET /metrics``)."""
        self._collect_gauges()
        return self.engine.metrics.render_prometheus()

    def metrics_summary(self) -> dict:
        """JSON-ready registry view (histograms as {count,sum,p50,p99})
        — embedded in ``/stats`` and the loadgen reports so
        server-side and loadgen-side percentiles sit next to each other.
        ``replica``-labelled families export per-child entries plus one
        cross-replica aggregate under the bare name."""
        self._collect_gauges()
        return self.engine.metrics.summaries()

    def health(self) -> dict:
        """Honest liveness, fanned in across replicas. A replica is
        healthy when its scheduler THREAD is alive AND its heartbeat is
        fresher than ``health_stale_after`` (a wedged thread — stuck
        inside a dispatch that never returns — stays is_alive() forever,
        so the heartbeat age is the real signal). The aggregate
        ``status`` is ``ok`` (all healthy), ``degraded`` (some dead or
        wedged, survivors still serving — HTTP 200, because an
        orchestrator kill-looping a half-healthy server would destroy
        the surviving capacity too) or ``down`` (nothing serving —
        HTTP 503). The probe also triggers the router's death sweep, so
        a dead replica's queued work is requeued by the next probe even
        on an otherwise idle server."""
        self.router.sweep()
        now = time.monotonic()
        reps = []
        healthy = 0
        for r in self.replicas:
            alive = r.thread is not None and r.thread.is_alive()
            hb = r.batcher.last_heartbeat
            age = None if hb is None else max(now - hb, 0.0)
            stale = age is not None and age > self.health_stale_after
            ok = bool(alive and not stale)
            healthy += ok
            st = r.batcher.stats()
            reps.append({
                "replica": r.index,
                "ok": ok,
                "alive": bool(alive),
                "stale": bool(stale),
                "retired": bool(r.retired),
                # mid-rollout: out of rotation on purpose — a "degraded"
                # verdict while this is set is the planned N-1 window
                "draining": bool(getattr(r, "draining", False)),
                "seconds_since_last_iteration":
                    None if age is None else round(age, 3),
                "queued": st["queued"],
                "active": st["active"],
            })
        status = ("ok" if healthy == len(reps)
                  else "degraded" if healthy else "down")
        ages = [x["seconds_since_last_iteration"] for x in reps
                if x["seconds_since_last_iteration"] is not None]
        return {
            "ok": status == "ok",
            "status": status,
            "replicas_healthy": healthy,
            "replicas_total": len(reps),
            "replicas": reps,
            # legacy flat fields: the single-replica view generalised —
            # alive only when EVERY scheduler thread lives, stale when any
            # heartbeat is, worst-case heartbeat age, summed depths
            "batcher_alive": all(x["alive"] for x in reps),
            "batcher_stale": any(x["stale"] for x in reps),
            "seconds_since_last_iteration": max(ages) if ages else None,
            "queued": sum(x["queued"] for x in reps),
            "active": sum(x["active"] for x in reps),
        }


class InprocessClient:
    """Synchronous in-process client: the HTTP semantics without sockets."""

    def __init__(self, server: ServeServer):
        self._server = server

    def generate(self, prompt, *, max_new_tokens: int,
                 sampling: SamplingParams = GREEDY, **kw) -> list[int]:
        req = self._server.generate(
            prompt, max_new_tokens=max_new_tokens, sampling=sampling, **kw
        )
        return list(req.tokens)

    def stats(self) -> dict:
        return self._server.stats()


def _sampling_from_body(body: dict) -> SamplingParams:
    # sampling params are COMPILE KEYS (engine.py): quantize the floats so
    # clients sending temperature=0.70000001 vs 0.7 share one compiled
    # program; the engine's max_sampling_configs bounds the rest
    top_k = body.get("top_k")
    top_p = body.get("top_p")
    return SamplingParams(
        temperature=round(float(body.get("temperature", 1.0)), 2),
        top_k=None if top_k is None else int(top_k),
        top_p=None if top_p is None else round(float(top_p), 2),
        greedy=bool(body.get("greedy", False)),
    )


class _Handler(BaseHTTPRequestHandler):
    server_version = "lstm-tsp-serve/1.0"
    protocol_version = "HTTP/1.1"

    def log_message(self, fmt, *args):  # keep serving logs structured
        pass

    @property
    def _serve(self) -> ServeServer:
        return self.server.serve  # type: ignore[attr-defined]

    def _reply(self, code: int, payload: dict,
               headers: dict | None = None) -> None:
        data = json.dumps(payload).encode()
        self.send_response(code)
        self.send_header("Content-Type", "application/json")
        self.send_header("Content-Length", str(len(data)))
        for k, v in (headers or {}).items():
            self.send_header(k, v)
        self.end_headers()
        self.wfile.write(data)

    @staticmethod
    def _error_parts(code: str, message: str, *, retryable: bool,
                     retry_after_s: float | None = None,
                     **extra) -> tuple[dict, dict | None]:
        """ONE error shape for every non-200 reply, so clients can branch
        on a stable contract instead of parsing prose: ``error`` (the
        human message — the key every pre-existing client reads),
        ``code`` (stable machine token), ``retryable``, and
        ``retry_after_s`` where the server has an honest estimate (also
        sent as the standard ``Retry-After`` header on 429s). Returns
        ``(body, headers)`` so the generate path can settle the payload
        into the replay cache before writing it to the wire."""
        body = {"error": message, "code": code, "retryable": bool(retryable),
                "retry_after_s": retry_after_s, **extra}
        headers = None
        if retry_after_s is not None:
            # delta-seconds per RFC 9110 (integer, rounded up — the body
            # keeps the precise float)
            headers = {"Retry-After": str(max(1, int(-(-retry_after_s // 1))))}
        return body, headers

    def _error(self, http_status: int, code: str, message: str, *,
               retryable: bool, retry_after_s: float | None = None,
               **extra) -> None:
        body, headers = self._error_parts(
            code, message, retryable=retryable,
            retry_after_s=retry_after_s, **extra)
        self._reply(http_status, body, headers)

    def do_GET(self) -> None:
        if self.path == "/healthz":
            # per-replica fan-in: 200 while ANY replica serves ("ok" or
            # "degraded" — kill-looping a half-healthy server would take
            # out the surviving capacity too), 503 only when "down"
            health = self._serve.health()
            self._reply(200 if health["status"] != "down" else 503, health)
        elif self.path in ("/stats", "/v1/stats"):
            # one payload, two routes: per-key compile counts, prefix-cache
            # hit/miss/evict/invalidate counters, state-cache swap
            # generation, batcher chunk/window counters + registry
            # histogram summaries (p50/p99)
            self._reply(200, self._serve.stats())
        elif self.path == "/metrics":
            # Prometheus text exposition (server-side TTFT/ITL/queue-wait
            # histograms as cumulative buckets; see docs/OPERATIONS.md for
            # the scrape config and runbook)
            data = self._serve.metrics_text().encode()
            self.send_response(200)
            self.send_header("Content-Type",
                             "text/plain; version=0.0.4; charset=utf-8")
            self.send_header("Content-Length", str(len(data)))
            self.end_headers()
            self.wfile.write(data)
        elif self.path == "/replica/heartbeat":
            # the remote-replica transport's liveness+load poll
            # (serve/remote.py RemoteBatcher.run): health verdict +
            # summed batcher counters, no metrics summaries — cheap
            # enough for a sub-second poll cadence
            self._reply(200, self._serve.replica_heartbeat())
        elif self.path.startswith("/replica/has_session"):
            # affinity probe from a front-of-fleet router: is this
            # session device- or tier-resident on ANY local replica?
            q = urllib.parse.parse_qs(
                urllib.parse.urlparse(self.path).query)
            sid = (q.get("sid") or [None])[0]
            if not sid:
                self._error(400, "bad_request",
                            "has_session needs ?sid=", retryable=False)
            else:
                self._reply(200, {"has": self._serve.has_session(sid)})
        elif self.path == "/rollout":
            # rollout-controller state: active move, queue, history,
            # last canary report, registry manifest
            if self._serve.rollout is None:
                self._error(404, "not_found",
                            "no model registry attached (start the "
                            "server with --registry-dir)",
                            retryable=False)
            else:
                self._reply(200, self._serve.rollout.stats())
        else:
            self._error(404, "not_found", f"no route {self.path}",
                        retryable=False)

    def do_POST(self) -> None:
        if self.path == "/replica/warmup":
            # front-of-fleet warmup pass-through: compile the lattice
            # for the front's prompt lengths/sampling before traffic
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                lens = tuple(int(t) for t in body.get("prompt_lens", (1,)))
                sampling = _sampling_from_body(body)
            except (ValueError, TypeError, json.JSONDecodeError) as e:
                self._error(400, "bad_request", f"bad request: {e}",
                            retryable=False)
                return
            try:
                n = self._serve.warmup(sampling, prompt_lens=lens)
            except (ValueError, RuntimeError) as e:
                self._error(500, "internal",
                            f"{type(e).__name__}: {e}", retryable=False)
                return
            self._reply(200, {"programs": n})
            return
        if self.path == "/replica/prefix":
            # fabric propagation receiver: a peer pushes one trie node
            # (token path + carry snapshot). Idempotent by token-hash —
            # the retrying transport may deliver twice (replay_safe) and
            # a replay answers dedup, not a double insert. Applied to
            # every LOCAL replica running a fabric trie so a
            # multi-replica host warms uniformly.
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
            except (ValueError, json.JSONDecodeError) as e:
                self._error(400, "bad_request", f"bad request: {e}",
                            retryable=False)
                return
            from .prefix_trie import decode_propagated_state

            applied = dedup = rejected = 0
            tries = [r.engine.prefix for r in self._serve.replicas
                     if hasattr(getattr(r.engine, "prefix", None),
                                "adopt_remote")]
            if not tries:
                self._error(404, "not_found",
                            "no prefix fabric on this host (boot with "
                            "--prefix-fabric on)", retryable=False)
                return
            for trie in tries:
                state = decode_propagated_state(
                    body, num_layers=trie.cache.num_layers,
                    hidden_size=trie.cache.hidden_size)
                if state is None:
                    rejected += 1
                    continue
                outcome = trie.adopt_remote(body.get("tokens", ()), state,
                                            body.get("hash"))
                if outcome == "applied":
                    applied += 1
                elif outcome == "dedup":
                    dedup += 1
                else:
                    rejected += 1
            if applied == dedup == 0 and rejected:
                self._error(400, "bad_request",
                            "malformed or rejected fabric node "
                            "(hash/shape/stride mismatch, or store "
                            "full of pinned nodes)", retryable=False)
                return
            self._reply(200, {"applied": applied, "dedup": dedup,
                              "rejected": rejected})
            return
        if self.path == "/rollout":
            # enqueue a rolling swap ({"model": ..., "version": N?}) or
            # a slot resize ({"slots": N}) for the controller thread;
            # 202 — the roll happens replica-by-replica off this request
            if self._serve.rollout is None:
                self._error(404, "not_found",
                            "no model registry attached (start the "
                            "server with --registry-dir)",
                            retryable=False)
                return
            try:
                length = int(self.headers.get("Content-Length", 0))
                body = json.loads(self.rfile.read(length) or b"{}")
                slots = body.get("slots")
                if slots is not None:
                    move = self._serve.rollout.request_resize(int(slots))
                else:
                    version = body.get("version")
                    move = self._serve.rollout.request_rollout(
                        str(body["model"]),
                        None if version is None else int(version))
            except (KeyError, ValueError, TypeError,
                    json.JSONDecodeError) as e:
                self._error(400, "bad_request", f"bad request: {e}",
                            retryable=False)
                return
            self._reply(202, {"accepted": True, **move})
            return
        if self.path != "/v1/generate":
            self._error(404, "not_found", f"no route {self.path}",
                        retryable=False)
            return
        try:
            length = int(self.headers.get("Content-Length", 0))
            body = json.loads(self.rfile.read(length) or b"{}")
            prompt = body["prompt"]
            max_new = int(body.get("max_new_tokens", 16))
            sampling = _sampling_from_body(body)
            timeout = float(body.get("timeout", 120.0))
            # deadline: body field wins, the X-Deadline-S header is the
            # proxy-friendly alternative; absent both, the server's
            # per-class default applies (ServeServer.deadline_defaults)
            deadline_s = body.get("deadline_s")
            if deadline_s is None:
                hdr = self.headers.get("X-Deadline-S")
                deadline_s = None if hdr is None else float(hdr)
            deadline_s = None if deadline_s is None else float(deadline_s)
            klass = str(body.get("class", "priority"))
            # per-tenant rate limiting (serve/router.py): the token-
            # bucket identity; absent = untenanted, never rate-limited
            tenant = body.get("tenant")
            tenant = None if tenant is None else str(tenant)
            # multi-model multiplexing: absent = the default model —
            # the single-model fleet's behavior, unchanged
            model = body.get("model")
            model = None if model is None else str(model)
        except (KeyError, ValueError, TypeError, json.JSONDecodeError) as e:
            # TypeError included: {"max_new_tokens": null} etc. must be a
            # 400, not a handler crash that resets the connection
            self._error(400, "bad_request", f"bad request: {e}",
                        retryable=False)
            return
        rid = body.get("request_id")
        rid = None if rid is None else str(rid)
        if rid is not None:
            # idempotent replay (serve/transport.py SettledCache): a
            # remote front retries delivery under this client-minted id
            # — a replay of an attempt that already executed returns
            # the settled reply verbatim instead of double-decoding
            state, cached = self._serve.settled.begin(
                rid, wait_timeout=timeout)
            if state == "hit":
                status, payload = cached
                self._reply(status, dict(payload, replayed=True))
                return
            if state == "timeout":
                self._error(504, "client_timeout",
                            f"request_id {rid!r} is still executing its "
                            "first delivery", retryable=True)
                return
            # "mine": first delivery — every outcome below settles or
            # abandons the id before the reply hits the wire
        status, payload, headers = self._generate_outcome(
            body, prompt, max_new, sampling, timeout, klass, deadline_s,
            tenant, model)
        if rid is not None:
            if status == 200 or payload.get("code") == "deadline_exceeded":
                # only outcomes that decoded tokens are worth replaying;
                # transient errors (shed, bad request, internal) abandon
                # so a retried delivery re-executes
                self._serve.settled.settle(rid, status, payload)
            else:
                self._serve.settled.abandon(rid)
        self._reply(status, payload, headers)

    def _generate_outcome(self, body, prompt, max_new, sampling, timeout,
                          klass, deadline_s, tenant, model):
        """Execute one generate call and return ``(status, payload,
        headers)`` instead of writing the wire directly — the replay
        cache records the settled outcome before the reply is sent."""
        t0 = time.perf_counter()
        err = self._error_parts
        try:
            req = self._serve.generate(
                prompt, max_new_tokens=max_new, sampling=sampling,
                session_id=body.get("session_id"),
                keep_session=bool(body.get("keep_session", False)),
                eos_id=body.get("eos_id"),
                use_prefix=bool(body.get("use_prefix", True)),
                timeout=timeout, klass=klass, deadline_s=deadline_s,
                tenant=tenant, model=model,
            )
        except UnknownModelError as e:
            # the model is not resident anywhere in the fleet: the
            # client named a thing that does not exist — 404, like an
            # unknown route, not a capacity condition
            return (404, *err("unknown_model", str(e), retryable=False))
        except QueueFullError as e:
            # the shed path: retryable by definition, with the router's
            # live drain estimate as the honest Retry-After
            return (429, *err("queue_full", str(e), retryable=True,
                              retry_after_s=getattr(e, "retry_after_s",
                                                    None)))
        except DeadlineExceededError as e:
            # server-side deadline expiry: an honest timeout WITH the
            # partial output — the client keeps every token that was
            # ready, and can branch on code="deadline_exceeded"
            r = e.request
            return (504, *err("deadline_exceeded", str(e), retryable=True,
                              tokens=list(r.tokens),
                              deadline_s=r.deadline_s,
                              phases_ms=r.phase_summary_ms()))
        except (ValueError, TypeError, RuntimeError) as e:
            # TypeError: a null/wrong-typed prompt surfaces from
            # np.asarray inside Request — still the client's fault
            if isinstance(e, RuntimeError):
                return (500, *err("internal", f"{type(e).__name__}: {e}",
                                  retryable=False))
            return (400, *err("bad_request", f"{type(e).__name__}: {e}",
                              retryable=False))
        except TimeoutError as e:
            # the client-side wait bound (distinct from the server-side
            # deadline): the request was CANCELLED, nothing useful to
            # return, but retrying re-sends the work — mark retryable
            return (504, *err("client_timeout", str(e), retryable=True))
        gaps = req.itl_gaps()
        return (200, {
            "tokens": list(req.tokens),
            "session_id": req.session_id,
            "replica": req.replica,
            "class": req.klass,
            "latency_ms": round((time.perf_counter() - t0) * 1e3, 3),
            "ttft_ms": round((req.t_first_token - req.t_submit) * 1e3, 3)
            if req.t_first_token and req.t_submit else None,
            "max_itl_ms": round(max(gaps) * 1e3, 3) if gaps else None,
            # per-request phase breakdown (queue/prefill/decode/readback
            # host time) — the trace timeline, summarised into the reply
            "phases_ms": req.phase_summary_ms(),
        }, None)


def make_http_server(serve: ServeServer, host: str = "127.0.0.1",
                     port: int = 0) -> ThreadingHTTPServer:
    """Bind the JSON endpoint (port 0 → ephemeral; see
    ``httpd.server_address``). Caller drives ``serve_forever`` (typically
    on a thread) and pairs it with ``serve.start()``/``serve.stop()``."""
    httpd = ThreadingHTTPServer((host, port), _Handler)
    httpd.serve = serve  # type: ignore[attr-defined]
    return httpd
