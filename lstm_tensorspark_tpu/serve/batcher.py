"""Continuous-batching scheduler over the serve engine.

One scheduler iteration (:meth:`Batcher.step`) does three things, in order:

1. **admission** — pop queued requests FIFO (same sampling config; capped
   by ``max_active`` and the engine's batch bucket), allocate/pin their
   cache slots, look up the **prefix cache** (``engine.prefix``, when
   enabled): a fresh prompt sharing a cached prefix resumes prefill at
   the matched offset from the prefix entry's slot instead of re-running
   the shared tokens — O(1) reuse of e.g. a system prompt thousands of
   sessions share;
2. **prefill** — dispatch prefill work for admitted sessions. Without
   ``prefill_chunk`` the whole remaining prompt runs now (one program,
   plus one head-less chunk when a prefix-insert split is due). With
   ``prefill_chunk=C`` at most ONE bounded program (<= C tokens per row)
   is dispatched per iteration, so a bucket-128 prompt's prefill
   interleaves with decode instead of stalling every running session
   behind one monolithic program (head-of-line ITL);
3. **decode** — advance EVERY active session, packed into bucketed decode
   batches grouped by sampling config. In steady state (empty queue, no
   prefill in flight, one sampling group that fits one batch bucket) the
   advance is a **decode window**: K tokens in one XLA program
   (``window_ladder``, K chosen adaptively), dispatched ahead of the
   previous window's readback.

Prefix-cache discipline: lookups ref-hold the matched entry (its backing
slot is pinned) until the resumed prefill is DISPATCHED — device data
ordering through the cache arrays covers the rest. Insertion is canonical:
a fresh prompt passing its stride boundary ``k`` snapshots the state after
``prompt[:k]`` into a new entry (one O(1) slot copy) exactly once; session
continuations (``session_id`` reuse) neither match nor insert, since their
prompt fragments are not absolute prefixes. Greedy output is
token-identical with the cache on (cold or hot), off, or chunked
(tests/test_serve_prefix.py).

**Adaptive windowing + async readback** (the per-token host-round-trip
killer): K falls back to 1 whenever the submit queue is non-empty or any
session is within K tokens of its budget — so a late request is still
admitted within one scheduler iteration and nobody decodes padding —
and grows to the ladder's largest rung in steady-state decode. A
dispatched window is held as ``_pending`` device handles; the NEXT
iteration dispatches window i+1 straight from those handles (the engine's
``decode_window_next``) *before* calling ``fetch_window`` on window i, so
host readback and Python token distribution overlap device compute. Rows
that hit EOS or their budget latch dead ON DEVICE (frozen carries, PAD
output), which is what makes running ahead safe. Greedy windowed output
is token-identical to the K=1 path (tests/test_serve_window.py).

Because step 2 covers all active sessions each iteration, fairness is
structural (no session can starve another; within a steady-state burst
every session advances by the same window), and because step 1 runs every
iteration, a short request submitted late finishes while longer earlier
sessions are still decoding — the continuous-batching property
(tests/test_serve_batcher.py).

Backpressure: the submit queue is bounded; a full queue raises
:class:`QueueFullError` immediately (the HTTP layer maps it to 429). The
active set is bounded by ``max_active`` (≤ cache slots, so admission can
always pin a slot without evicting another active session).

**Admission classes + deadlines** (the serve robustness plane): every
request carries an admission class (``priority`` default /
``best_effort``) and an optional deadline. The class queues are served
by weighted round-robin (``class_weights``, default 4:1 — FIFO within a
class, and exactly the old FIFO when only one class waits), so a
best-effort flood cannot starve priority traffic; the router above
additionally sheds best-effort at a smaller queue bound with an honest
``Retry-After``. Deadlines are enforced where they can still save work:
expired queued requests are REAPED before consuming a slot or a prefill
dispatch, mid-prefill expiry stops burning chunks, and decode honors
the deadline at window boundaries — settling the request with the
partial output under its own ``timeout`` outcome
(``serve_requests_total{outcome="timeout"}`` +
``serve_deadline_expired_total{stage=}``), never a wedged client
(tests/test_serve_deadline.py).

The scheduler is single-threaded by design — `step()` is driven either by
the server's background thread (`run`) or directly by tests (`drain`);
`submit` may be called from any thread.

Telemetry (obs/, via ``engine.metrics``): queue depth/wait, scheduler
iteration time, server-side TTFT and inter-token-latency histograms
(same timestamp definitions as loadgen's — the two views must agree),
window-K / prefill-chunk / readback-latency counters, and per-request
phase timelines (``Request.phases`` → the Chrome tracer under
``--trace`` + ``phases_ms`` in the HTTP reply). Instruments are resolved
once at construction; each record site costs a lock + an add.

Spans (``utils.tracing.span``; on under a profiler session or ``--trace``):
``serve:iteration`` around one ``step()``, ``serve:wait_for_work`` around
the idle wait of ``run``, and inside an iteration ``serve:admit``,
``serve:prefill_dispatch``, ``serve:decode_dispatch`` (``rows`` = sessions
owed tokens, ``k``, ``pipelined``) and ``serve:deliver``; the engine's
pack/launch/fetch spans nest inside the dispatches. Where a histogram or a
request phase covers a span's lines it reads the span's own stamps (the
iteration histogram, the per-token ``decode`` phase); the ``prefill``
phases start at the engine call and end with the dispatch span.
"""

from __future__ import annotations

import itertools
import os
import threading
import time
from collections import deque

import numpy as np

from ..resilience import faults as _faults
from ..utils import tracing
from .engine import (GREEDY, PAD_TOKEN, DecodeWindow, SamplingParams,
                     ServeEngine, UnknownModelError)
from .state_cache import PREFIX_SID_NAMESPACE

#: admission classes, in dequeue-priority order. "priority" is the
#: default (a class-less client gets the old FIFO behavior and the
#: stricter SLO); "best_effort" is shed first under overload and served
#: at the smaller weighted-dequeue share.
CLASSES = ("priority", "best_effort")


def retry_after_from_p99(p99, fullness: float) -> float:
    """The ONE Retry-After policy, shared by the router's shed path and
    the batcher's own queue bound: the measured queue-wait p99 (the
    drain-time evidence) scaled by how full the queue is (0.5 + fullness
    — 1.5x at a full queue), clamped to [0.05 s, 30 s], with a
    conservative 0.25 s floor when no samples exist yet (cold server) or
    the estimate is NaN."""
    base = (float(p99) if isinstance(p99, (int, float)) and p99 == p99
            else 0.0)
    if base <= 0:
        base = 0.25
    return float(min(max(base * (0.5 + fullness), 0.05), 30.0))


def register_shed_instruments(reg):
    """Resolve the shed instruments both admission layers record into —
    one registration site, so the name/labels/help can never drift
    between the router and the batcher (metrics-consistency). Returns
    ``(shed_by_class, tenant_shed_by_class, retry_after_histogram)`` —
    ``tenant_limited="yes"`` children count the router's per-tenant
    token-bucket 429s, ``"no"`` the capacity sheds."""
    fam = reg.counter(
        "serve_shed_total",
        "429 sheds by admission class (best_effort sheds at its "
        "smaller queue bound while priority keeps the headroom); "
        "tenant_limited=yes marks per-tenant token-bucket rejections",
        labelnames=("class", "tenant_limited"))
    # "class" is a Python keyword, so the kwarg must go through ** —
    # which the analyzer cannot resolve against the registration
    # graftlint: disable=metrics-consistency
    shed = {c: fam.labels(**{"class": c, "tenant_limited": "no"})
            for c in CLASSES}
    # graftlint: disable=metrics-consistency
    tenant_shed = {c: fam.labels(**{"class": c, "tenant_limited": "yes"})
                   for c in CLASSES}
    retry_hist = reg.histogram(
        "serve_retry_after_seconds",
        "Retry-After hints attached to 429 sheds, computed from the "
        "live queue-wait p99 (drain estimate, not a fixed constant)")
    return shed, tenant_shed, retry_hist


class QueueFullError(RuntimeError):
    """Admission control: the bounded submit queue is full, or the
    shedding policy rejected this class (HTTP 429). ``retry_after_s``
    (when set by the router) is the server's live drain estimate from
    the queue-wait p99 histogram — the client's honest retry hint."""

    def __init__(self, msg: str, retry_after_s: float | None = None):
        super().__init__(msg)
        self.retry_after_s = retry_after_s


class DeadlineExceededError(RuntimeError):
    """The request's deadline lapsed server-side. ``request`` carries
    whatever partial output was generated before expiry — the HTTP layer
    returns it with an honest ``deadline_exceeded`` body instead of
    wedging the client until its own timeout."""

    def __init__(self, request: "Request"):
        super().__init__(
            f"request {request.id} deadline exceeded after "
            f"{len(request.tokens)} token(s)")
        self.request = request


class Request:
    """One generation request; the result fields are filled by the
    scheduler and published by setting ``done``."""

    _ids = itertools.count()

    def __init__(
        self,
        prompt,
        max_new_tokens: int,
        *,
        sampling: SamplingParams = GREEDY,
        session_id: str | None = None,
        keep_session: bool = False,
        eos_id: int | None = None,
        use_prefix: bool = True,
        klass: str = "priority",
        deadline_s: float | None = None,
        tenant: str | None = None,
        model: str | None = None,
    ):
        self.prompt = np.asarray(prompt, np.int32).reshape(-1)
        if self.prompt.size < 1:
            raise ValueError("prompt must contain at least one token")
        if max_new_tokens < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new_tokens}")
        self.max_new_tokens = int(max_new_tokens)
        self.sampling = sampling
        if session_id is not None and session_id.startswith(PREFIX_SID_NAMESPACE):
            # the prefix cache's backing slots live in this namespace — a
            # client naming one would inherit (and corrupt) shared state
            raise ValueError(
                f"session_id namespace {PREFIX_SID_NAMESPACE!r} is reserved")
        self.session_id = session_id
        self.keep_session = keep_session
        self.eos_id = eos_id
        # opt-out of prefix-cache lookup AND insert for this request —
        # measurement probes must not perturb (or be flattered by) the
        # shared cache
        self.use_prefix = use_prefix
        if klass not in CLASSES:
            raise ValueError(
                f"unknown admission class {klass!r} (classes: "
                f"{', '.join(CLASSES)})")
        self.klass = klass
        if deadline_s is not None and deadline_s <= 0:
            raise ValueError(f"deadline_s must be > 0, got {deadline_s}")
        self.deadline_s = deadline_s
        # per-tenant rate limiting (serve/router.py): the token-bucket
        # identity. None = untenanted traffic, never rate-limited.
        if tenant is not None:
            tenant = str(tenant)
            if not tenant or len(tenant) > 256:
                raise ValueError(
                    "tenant must be a non-empty string of <= 256 chars")
        self.tenant = tenant
        # multi-model multiplexing (serve/engine.py residents): which
        # resident model serves this request. None = the replica's
        # default model — the single-model fleet's behavior, unchanged.
        # One dispatched batch is one model (like sampling configs), so
        # the scheduler groups by it everywhere it groups by sampling.
        if model is not None:
            model = str(model)
            if not model or len(model) > 256:
                raise ValueError(
                    "model must be a non-empty string of <= 256 chars")
        self.model = model
        # absolute perf_counter deadline, stamped at FIRST submission so
        # the budget covers queue wait; a requeued request (replica
        # death) keeps its original deadline — the client's budget does
        # not reset because a replica died
        self.deadline: float | None = None
        # honest server-side expiry: the request settled with whatever
        # tokens were already generated (partial output), counted under
        # serve_requests_total{outcome="timeout"}
        self.timed_out = False
        self.id = next(Request._ids)
        # replica index this request was routed to (serve/router.py) —
        # None until routed (or forever, for a direct Batcher.submit).
        # Surfaced in the HTTP reply and loadgen's per-replica counts.
        self.replica: int | None = None
        # network-resilience bookkeeping (serve/remote.py): the client-
        # minted idempotency key the remote transport replays under
        # (minted once, at first remote submit), and how many times a
        # provably-undelivered RPC re-entered routing (Router.reroute
        # bounds this by fleet size)
        self.rpc_request_id: str | None = None
        self.reroutes = 0
        self.tokens: list[int] = []
        self.error: str | None = None
        self.cancelled = False  # set by an abandoning client (timeout)
        # decoder family: per generated token, (its logit, the step's
        # largest) as the program computed them — what a judge compares
        # with a reference's logits (stays empty for the LSTM family)
        self.token_logits: list[tuple[float, float]] = []
        self.done = threading.Event()
        self.t_submit: float | None = None
        self.t_admit: float | None = None
        self.t_first_token: float | None = None
        self.t_done: float | None = None
        # phase timeline: (name, start, end) perf_counter intervals the
        # scheduler appends as the request moves admit → queue → prefill
        # chunk(s) → decode window(s) → readback. Cheap (tuple appends);
        # at completion the batcher emits them into the installed Chrome
        # tracer (one synthetic row per request) and the HTTP reply
        # carries phase_summary_ms().
        self.phases: list[tuple[str, float, float]] = []
        # host-side arrival time of each token (one entry per token):
        # consecutive deltas are the request's inter-token latencies. A
        # decode window delivers its K tokens in one burst, so these make
        # the latency cost of windowing measurable (loadgen p50/p99 ITL)
        # instead of guessed.
        self.t_tokens: list[float] = []

    def expired(self, now: float | None = None) -> bool:
        """True once the (submit-stamped) deadline has lapsed."""
        if self.deadline is None:
            return False
        return (time.perf_counter() if now is None else now) > self.deadline

    def itl_gaps(self) -> list[float]:
        """Inter-token latencies (seconds): gaps between consecutive
        token arrivals — the ONE definition shared by the HTTP reply's
        ``max_itl_ms`` and loadgen's pooled percentiles. TTFT is not a
        gap (reported separately); a window's burst contributes 0.0s
        gaps between its tokens."""
        return [b - a for a, b in zip(self.t_tokens, self.t_tokens[1:])]

    def phase_summary_ms(self) -> dict[str, float]:
        """Total host-side time per phase (ms) — the per-request breakdown
        the HTTP reply returns. Decode windows fold into ``decode_ms``
        (the sync per-token path records ``decode`` directly);
        ``readback_ms`` is fetch-blocked time. Per phase the spans are
        UNION-merged, not summed: pipelined decode windows overlap in time
        (window i+1 is dispatched before window i's fetch), and a plain
        sum would report decode_ms larger than the request's own
        latency. Each value is therefore <= the request latency, but
        DIFFERENT phases still overlap each other under pipelining
        (window i's readback runs inside window i+1's decode span — the
        overlap IS the pipeline), so the values don't add up to the
        latency either."""
        spans: dict[str, list[tuple[float, float]]] = {}
        for name, a, b in self.phases:
            key = "decode" if name == "decode_window" else name
            spans.setdefault(key, []).append((a, b))
        out = {}
        for key, ivs in spans.items():
            ivs.sort()
            total, cur_a, cur_b = 0.0, ivs[0][0], ivs[0][1]
            for a, b in ivs[1:]:
                if a > cur_b:
                    total += cur_b - cur_a
                    cur_a, cur_b = a, b
                else:
                    cur_b = max(cur_b, b)
            total += cur_b - cur_a
            out[f"{key}_ms"] = round(total * 1e3, 3)
        return out


class _Session:
    __slots__ = ("req", "sid", "slot", "remaining", "last_token")

    def __init__(self, req: Request, sid: str, slot: int):
        self.req = req
        self.sid = sid
        self.slot = slot
        self.remaining = req.max_new_tokens
        self.last_token = 0


class _Prefilling:
    """An admitted session whose prompt is not fully consumed yet.

    ``pos`` counts consumed prompt tokens; ``entry`` is the ref-held
    prefix-cache entry the FIRST dispatch gathers from (released, and set
    to None, once that dispatch is in flight); ``was_fresh`` records
    whether the session started stateless — only such sessions' prompts
    are absolute prefixes eligible for prefix-cache insertion."""

    __slots__ = ("sess", "pos", "entry", "was_fresh", "draft_started")

    def __init__(self, sess: _Session, pos: int, entry, was_fresh: bool):
        self.sess = sess
        self.pos = pos
        self.entry = entry
        self.was_fresh = was_fresh
        # speculative serving: True once the DRAFT model consumed this
        # session's first fragment — the first draft dispatch always
        # starts from zero (the draft has no prefix entries and no tier
        # copies to resume from; starting cold is lossless, it only
        # lowers acceptance until the draft catches context)
        self.draft_started = False

    def src(self) -> tuple[int, bool]:
        """(src_slot, fresh) for the next prefill dispatch."""
        if self.entry is not None:
            return self.entry.slot, False
        return self.sess.slot, self.was_fresh and self.pos == 0


class Batcher:
    #: default decode-window ladder: every K is a compile key, so the
    #: lattice stays tiny; (1,) disables windowing (pure K=1 path).
    DEFAULT_WINDOW_LADDER = (1, 4, 8)

    #: default weighted-dequeue shares (priority, best_effort): out of
    #: every 5 admissions with both classes waiting, 4 are priority.
    DEFAULT_CLASS_WEIGHTS = (4, 1)

    #: default speculative K_draft ladder: each K > 0 is a compile key
    #: (("spec_window", bucket, K)); rung 0 is ALWAYS present — it is
    #: the plain-decode fallback the autotuner retreats to when the
    #: draft stops paying for itself.
    DEFAULT_SPEC_LADDER = (0, 2, 4)

    def __init__(
        self,
        engine: ServeEngine,
        *,
        replica: int = 0,
        max_active: int = 16,
        queue_size: int = 64,
        window_ladder: tuple[int, ...] = DEFAULT_WINDOW_LADDER,
        prefill_chunk: int | None = None,
        prefill_chunk_choices: tuple[int, ...] | None = None,
        class_weights: tuple[int, int] = DEFAULT_CLASS_WEIGHTS,
        speculative: bool = False,
        spec_ladder: tuple[int, ...] = DEFAULT_SPEC_LADDER,
        spec_k: int | None = None,
    ):
        if max_active < 1:
            raise ValueError(f"max_active must be >= 1, got {max_active}")
        if max_active > engine.cache.num_slots:
            raise ValueError(
                f"max_active {max_active} exceeds the cache's "
                f"{engine.cache.num_slots} slots — active sessions must "
                "always be able to hold a pinned slot"
            )
        if queue_size < 1:
            raise ValueError(f"queue_size must be >= 1, got {queue_size}")
        if not window_ladder or any(k < 1 for k in window_ladder):
            raise ValueError(
                f"window_ladder needs positive window sizes, got "
                f"{window_ladder!r}")
        self._validate_chunk(prefill_chunk, engine)
        if prefill_chunk_choices:
            if prefill_chunk is None:
                # the choice set is the autotuner's movement range for an
                # ALREADY-chunked scheduler; flipping None↔int at runtime
                # would also flip submit()'s prompt-length admission rule
                # under a client's feet
                raise ValueError(
                    "prefill_chunk_choices needs prefill_chunk set (the "
                    "knob moves among chunk sizes, it cannot turn "
                    "chunking on or off)")
            for c in prefill_chunk_choices:
                self._validate_chunk(int(c), engine)
        if (len(class_weights) != len(CLASSES)
                or any(int(w) < 1 for w in class_weights)):
            raise ValueError(
                f"class_weights needs one positive weight per class "
                f"{CLASSES}, got {class_weights!r}")
        if any(int(k) < 0 for k in spec_ladder):
            raise ValueError(
                f"spec_ladder needs K_draft >= 0, got {spec_ladder!r}")
        if speculative and not engine.has_draft:
            raise ValueError(
                "speculative=True needs a draft model attached to the "
                "engine (attach_draft) — there is nothing to propose "
                "tokens with")
        # rung 1 is always present: _pick_window falls back to it (near
        # budget end, pipelined tails), and warmup(windows=ladder) must
        # precompile every size the scheduler can dispatch
        ladder = tuple(sorted({1} | set(window_ladder)))
        # rung 0 is always present in the spec ladder: the autotuner's
        # K_draft=0 fallback must be selectable even when the operator
        # configured only positive rungs
        self.spec_ladder = tuple(sorted({0} | {int(k) for k in spec_ladder}))
        self.speculative = bool(speculative)
        if not self.speculative:
            self.spec_k = 0
        elif spec_k is None:
            self.spec_k = self.spec_ladder[-1]
        else:
            if spec_k not in self.spec_ladder:
                raise ValueError(
                    f"spec_k {spec_k} is not a spec_ladder rung "
                    f"{self.spec_ladder}")
            self.spec_k = int(spec_k)
        self.engine = engine
        # identity within a replicated server (serve/router.py): labels
        # this scheduler's metric children and names it in /healthz —
        # a standalone batcher is replica 0 of a one-replica stack
        self.replica = int(replica)
        self.max_active = max_active
        self.queue_size = queue_size
        self.window_ladder = ladder
        # live ceiling on the adaptive window pick — the serve
        # autotuner's K knob. Always a ladder rung (set_window_cap
        # validates), so every reachable window size is warmup-covered;
        # the default (the top rung) is exactly the pre-knob behavior.
        self.window_cap = ladder[-1]
        self.prefill_chunk = prefill_chunk
        # warmed chunk sizes the autotuner may move prefill_chunk among
        # (set_prefill_chunk refuses anything else; warmup() replays the
        # stop sequence for EVERY choice so no pick compiles mid-traffic)
        self.prefill_chunk_choices = (
            tuple(sorted({int(c) for c in prefill_chunk_choices}
                         | {prefill_chunk}))
            if prefill_chunk_choices else ())
        # admitted sessions still consuming their prompt (FIFO; owned by
        # the scheduler thread — the lock only covers reads from stats())
        self._prefilling: list[_Prefilling] = []
        # the in-flight decode window: (DecodeWindow handles, its rows'
        # sessions in packed order). Owned by the scheduler thread only.
        self._pending: tuple[DecodeWindow, list[_Session]] | None = None
        # one bounded queue PER admission class; dequeue is weighted
        # round-robin over the non-empty ones (the wrr sequence below),
        # so a best-effort flood can no longer starve priority traffic
        # the way the old single FIFO did. The queue_size bound covers
        # the SUM — the router's class-aware shed policy sits above.
        self.class_weights = tuple(int(w) for w in class_weights)
        self._queues: dict[str, deque[Request]] = {
            c: deque() for c in CLASSES}
        self._wrr_seq: tuple[str, ...] = tuple(
            c for c, w in zip(CLASSES, self.class_weights)
            for _ in range(w))
        self._wrr_idx = 0
        # True while any queued request MAY carry a deadline — gates the
        # per-iteration queue reap so deadline-less workloads never pay
        # the scan (set by submit, cleared when a scan finds none left)
        self._deadlines_queued = False
        self._active: list[_Session] = []
        self._lock = threading.Lock()
        self._work = threading.Condition(self._lock)
        self._sid_counter = itertools.count()
        # auto-minted session ids must be unique across the FLEET, not
        # just this scheduler: with remote replicas (serve/remote.py)
        # every serve process has a replica 0, and two processes minting
        # "s0-0" for different clients would cross their affinity probes
        # AND alias each other's session files on a shared --session-dir
        # (hash(sid) names the file — a collision silently decodes the
        # other conversation's state). A per-process random component
        # makes the namespace collision-free without any coordination.
        self._sid_prefix = f"s{self.replica}.{os.urandom(3).hex()}"
        self.submitted = 0
        self.completed = 0
        self.rejected = 0
        self.failed = 0
        self.timed_out = 0  # deadline expiries (queue/prefill/decode)
        self.tokens_generated = 0
        self.windows_dispatched: dict[int, int] = {}  # K -> dispatch count
        self.windows_pipelined = 0  # dispatched ahead of a pending fetch
        self.prefill_chunks_dispatched = 0  # head-less chunk programs
        self.prefix_resumed = 0  # sessions that resumed from a prefix hit
        self.prefix_tokens_saved = 0  # prompt tokens skipped via the cache
        self.prefill_tokens_computed = 0  # prompt tokens actually run
        # speculative accounting: spec windows dispatched per K_draft,
        # and the accepted-proposal total (emitted = accepted + 1 per
        # live row per window — the correction token always rides along)
        self.spec_windows_dispatched: dict[int, int] = {}
        self.spec_accepted_tokens = 0
        self.draft_prefills_dispatched = 0
        self.draft_prefill_failures = 0
        # liveness heartbeat for /healthz: monotonic timestamp of the last
        # scheduler pass (run-loop cycle or direct step()); None until the
        # scheduler first runs. A dead/stuck scheduler thread stops
        # advancing it — the honest signal a wedged server must emit.
        self.last_heartbeat: float | None = None
        # telemetry (obs/): instruments resolved ONCE here — the per-event
        # cost at the record sites is a lock + an add. The registry comes
        # from the engine so one constructor argument scopes the whole
        # serve stack (and NULL_REGISTRY turns all of this into no-ops).
        # Every family carries a `replica` label: a replicated server's
        # schedulers share the registry, and their children must stay
        # separable (summaries() exports the cross-replica aggregate
        # under the bare family name).
        reg = engine.metrics
        rl = str(self.replica)
        self._m_queue_depth = reg.gauge(
            "serve_queue_depth", "requests waiting in the submit queue",
            labelnames=("replica",)).labels(replica=rl)
        self._m_active = reg.gauge(
            "serve_active_sessions", "sessions in active decode",
            labelnames=("replica",)).labels(replica=rl)
        self._m_prefilling = reg.gauge(
            "serve_prefilling_sessions", "admitted sessions mid-prefill",
            labelnames=("replica",)).labels(replica=rl)
        self._m_queue_wait = reg.histogram(
            "serve_queue_wait_seconds", "submit → admission wait",
            labelnames=("replica",)).labels(replica=rl)
        self._m_ttft = reg.histogram(
            "serve_ttft_seconds", "submit → first token (server-side)",
            labelnames=("replica",)).labels(replica=rl)
        self._m_itl = reg.histogram(
            "serve_itl_seconds",
            "inter-token gaps, host arrival times (0 within a window burst)",
            labelnames=("replica",)).labels(replica=rl)
        self._m_iteration = reg.histogram(
            "serve_scheduler_iteration_seconds",
            "duration of scheduler iterations that did work",
            labelnames=("replica",)).labels(replica=rl)
        self._m_readback = reg.histogram(
            "serve_readback_seconds",
            "decode-window dispatch → tokens on host (fetch latency)",
            labelnames=("replica",)).labels(replica=rl)
        self._m_chunks = reg.counter(
            "serve_prefill_chunks_total",
            "head-less bounded prefill chunk programs dispatched",
            labelnames=("replica",)).labels(replica=rl)
        fam = reg.counter("serve_decode_windows_total",
                          "decode windows dispatched by window size K",
                          labelnames=("k", "replica"))
        self._m_window_k = {k: fam.labels(k=str(k), replica=rl)
                            for k in self.window_ladder}
        # speculative telemetry: per-row accepted length per verify
        # window (what the autotuner's spec_k knob watches), and verify
        # outcomes — "full" = every proposal accepted, "partial" = some,
        # "reject" = none (the row still emitted its correction token)
        self._m_spec_accept = reg.histogram(
            "serve_spec_accept_len",
            "draft proposals accepted per speculative verify window, "
            "per live row",
            labelnames=("replica",),
            buckets=(0.0, 1.0, 2.0, 3.0, 4.0, 6.0, 8.0, 12.0, 16.0),
        ).labels(replica=rl)
        fam = reg.counter(
            "serve_spec_verify_total",
            "speculative verify windows by per-row outcome",
            labelnames=("outcome", "replica"))
        self._m_spec_outcome = {o: fam.labels(outcome=o, replica=rl)
                                for o in ("full", "partial", "reject")}
        fam = reg.counter("serve_requests_total",
                          "requests by final outcome",
                          labelnames=("outcome", "replica"))
        self._m_req_completed = fam.labels(outcome="completed", replica=rl)
        self._m_req_failed = fam.labels(outcome="failed", replica=rl)
        self._m_req_rejected = fam.labels(outcome="rejected", replica=rl)
        # honest deadline expiry is its OWN outcome (partial output,
        # never "failed" — the client got every token that was ready)
        self._m_req_timeout = fam.labels(outcome="timeout", replica=rl)
        fam = reg.counter(
            "serve_deadline_expired_total",
            "request deadlines that lapsed, by the pipeline stage that "
            "reaped them (queue = before any slot/prefill was spent)",
            labelnames=("stage", "replica"))
        self._m_deadline = {s: fam.labels(stage=s, replica=rl)
                            for s in ("queue", "prefill", "decode")}
        # the batcher-level bound can fire too (direct submits; a wedged
        # replica's own queue filling on the affinity path while the
        # router's non-stale sum stays low) — those 429s must carry the
        # same Retry-After + shed accounting as the router's (one shared
        # registration + one shared policy, so the layers cannot drift).
        # The tenant-limited children are the router's (rate limiting
        # lives above routing); the batcher only sheds on capacity.
        self._m_shed, _, self._m_retry_after = register_shed_instruments(reg)

    # ---- client side ---------------------------------------------------

    def submit(self, req: Request) -> None:
        """Enqueue a request, or raise :class:`QueueFullError` (bounded
        queue — the backpressure boundary)."""
        with self._lock:
            # under the lock: prefill_chunk is a live knob
            # (set_prefill_chunk) — though only its None-ness matters
            # here, and the autotuner can never flip that
            if (self.prefill_chunk is None
                    and req.prompt.size > self.engine.max_prompt_len):
                # chunked prefill lifts this cap: any prompt length is
                # consumed prefill_chunk tokens per dispatch, so no
                # single program ever exceeds the bucket lattice
                raise ValueError(
                    f"prompt length {req.prompt.size} exceeds the "
                    f"engine's largest prefill bucket "
                    f"{self.engine.max_prompt_len} "
                    "(enable prefill_chunk to serve longer prompts)"
                )
            if not self.engine.has_model(req.model):
                # reject at the admission boundary, not at dispatch time:
                # a request naming a non-resident model would otherwise
                # consume a slot, reach _dispatch_prefill, and fail a
                # whole co-batched dispatch with it
                raise UnknownModelError(
                    f"model {req.model!r} is not resident on replica "
                    f"{self.replica}")
            if self._qlen_locked() >= self.queue_size:
                # same honest-429 contract as the router's shed path:
                # Retry-After from the measured queue wait, counted under
                # serve_shed_total — a 429 from THIS layer (direct
                # submits; a wedged replica's own queue filling while the
                # router's non-stale sum stays low) must not be a
                # second-class reply clients cannot back off from
                retry = self._retry_after_locked()
                self.rejected += 1
                self._m_req_rejected.inc()
                self._m_shed[req.klass].inc()
                self._m_retry_after.observe(retry)
                raise QueueFullError(
                    f"submit queue full ({self.queue_size} pending); "
                    f"retry after {retry:.2f}s", retry_after_s=retry
                )
            if req.t_submit is None:
                # first submission; a REQUEUED request (router: replica
                # death) arrives with t_submit already stamped and is
                # neither re-stamped nor re-counted — queue-wait/TTFT
                # must cover the time spent on the dead replica's queue,
                # and the dead replica already counted the submission
                # (the cross-replica `submitted` sum stays one per
                # client request; the serving replica's per-replica
                # count undercounts by the requeues, which the router's
                # `requeued` counter makes explicit)
                req.t_submit = time.perf_counter()
                self.submitted += 1
                if req.deadline_s is not None:
                    # the absolute deadline starts at FIRST submission
                    # (covers queue wait); requeues keep the original
                    req.deadline = req.t_submit + req.deadline_s
            if req.deadline is not None:
                self._deadlines_queued = True  # arms the _admit reap
            self._queues[req.klass].append(req)
            self._work.notify()

    def _qlen_locked(self) -> int:
        return sum(len(q) for q in self._queues.values())

    def _retry_after_locked(self) -> float:
        """Honest Retry-After for a full-queue 429 at THIS layer: this
        scheduler's queue-wait p99 through the shared policy
        (:func:`retry_after_from_p99`) at fullness 1.0 — the bound only
        fires when the queue IS full."""
        s = self._m_queue_wait.summary() or {}
        return retry_after_from_p99(s.get("p99"), 1.0)

    def queued(self) -> int:
        """Requests waiting for admission, summed over the class queues
        (the router sums this across replicas for the GLOBAL bound)."""
        with self._lock:
            return self._qlen_locked()

    def load(self) -> int:
        """Routing weight: queued + admitted work on this scheduler, read
        under one lock hold (the router's least-loaded pick)."""
        with self._lock:
            return (self._qlen_locked() + len(self._active)
                    + len(self._prefilling))

    # ---- live knobs (serve/autotune.py; bounded by the warmed lattice) -

    @staticmethod
    def _validate_chunk(chunk: int | None, engine: ServeEngine) -> None:
        if chunk is None:
            return
        if chunk < 1:
            raise ValueError(
                f"prefill_chunk must be >= 1 or None, got {chunk}")
        if chunk > engine.max_prompt_len:
            raise ValueError(
                f"prefill_chunk {chunk} exceeds the largest prefill "
                f"bucket {engine.max_prompt_len} — each chunk is one "
                "bucketed program")
        if (engine.prefix is not None
                and chunk % engine.prefix.stride != 0
                and engine.prefix.stride % chunk != 0):
            # _stop_from stride-aligns every pre-boundary stop, so an
            # incompatible chunk is silently truncated each dispatch —
            # the operator gets a smaller effective chunk than configured
            raise ValueError(
                f"prefill_chunk {chunk} is not a multiple or divisor "
                f"of prefix stride {engine.prefix.stride} — chunks would "
                "be truncated to stride alignment; pick a compatible "
                "chunk or disable the prefix cache")

    def set_window_cap(self, k: int) -> None:
        """Move the decode-window ceiling to ladder rung ``k`` (the
        autotuner's K knob). Only warmed rungs are accepted — the
        controller can NEVER select a window size that would compile
        mid-traffic. Takes effect at the next ``_pick_window``."""
        if k not in self.window_ladder:
            raise ValueError(
                f"window cap {k} is not a warmed ladder rung "
                f"{self.window_ladder} — an off-ladder window would "
                "compile mid-traffic")
        with self._lock:
            self.window_cap = int(k)

    def set_max_active(self, n: int) -> None:
        """Move the active-set bound (the rollout controller's
        slot-resize move resizes the device cache first, then raises or
        lowers this to match). Bounded by the CURRENT slot count — the
        same invariant __init__ enforces: admission must always be able
        to pin a slot."""
        if n < 1:
            raise ValueError(f"max_active must be >= 1, got {n}")
        if n > self.engine.cache.num_slots:
            raise ValueError(
                f"max_active {n} exceeds the cache's "
                f"{self.engine.cache.num_slots} slots — resize the slot "
                "pool first (rollout controller resize move)")
        with self._lock:
            self.max_active = int(n)

    def set_prefill_chunk(self, chunk: int) -> None:
        """Move the prefill chunk size to ``chunk`` (the autotuner's
        chunk knob). Only members of the warmed ``prefill_chunk_choices``
        set are accepted — warmup() replayed the stop sequence for every
        choice, so no pick dispatches an uncompiled program."""
        if chunk not in self.prefill_chunk_choices:
            raise ValueError(
                f"prefill_chunk {chunk} is not in the warmed choice set "
                f"{self.prefill_chunk_choices} — an unwarmed chunk would "
                "compile mid-traffic")
        with self._lock:
            self.prefill_chunk = int(chunk)

    def set_spec_k(self, k: int) -> None:
        """Move the speculative K_draft to spec-ladder rung ``k`` (the
        autotuner's spec knob). Rung 0 is the plain-decode fallback —
        speculation off until the knob moves back up. Only warmed rungs
        are accepted, so no pick ever compiles mid-traffic; takes effect
        at the next ``_pick_spec_k``."""
        if not self.speculative:
            raise ValueError(
                "set_spec_k on a non-speculative scheduler — boot with "
                "speculative=True (and an attached draft) first")
        if k not in self.spec_ladder:
            raise ValueError(
                f"spec_k {k} is not a warmed spec-ladder rung "
                f"{self.spec_ladder} — an off-ladder K_draft would "
                "compile mid-traffic")
        with self._lock:
            self.spec_k = int(k)

    # ---- replica retirement (router-driven; see serve/router.py) -------
    #
    # These are called by the admission router ONLY after this scheduler's
    # thread has exited — they mutate scheduler-owned state from another
    # thread, which is safe precisely because the owner is gone (and every
    # guarded structure is still snapshotted under the lock, so a stats()
    # or health reader racing the retirement sees consistent views).

    def drain_queue(self) -> list[Request]:
        """Remove and return every not-yet-admitted request (the router
        requeues them onto live replicas), oldest-submitted first so the
        requeue preserves rough arrival order across the class queues."""
        with self._lock:
            out = [r for q in self._queues.values() for r in q]
            for q in self._queues.values():
                q.clear()
        out.sort(key=lambda r: (r.t_submit if r.t_submit is not None
                                else float("inf"), r.id))
        return out

    def fail_inflight(self, reason: str) -> int:
        """Fail every admitted (prefilling or decoding) request with
        ``reason`` and release its slot/prefix refs. Under dispatch-ahead
        windowed decode the host cannot know how many tokens an
        un-fetched window already consumed, so a dead scheduler's
        in-flight sessions cannot be resumed elsewhere without risking
        silent double-decode — honest failure is the only correct
        outcome. Returns the number of requests failed."""
        with self._lock:
            prefilling = list(self._prefilling)
            self._prefilling.clear()
            active = list(self._active)
            self._active.clear()
        self._pending = None  # scheduler-owned; the owner thread is dead
        for p in prefilling:
            if p.entry is not None:
                self.engine.prefix.release(p.entry)
                p.entry = None
            self.engine.cache.release(p.sess.sid)
            self._fail(p.sess.req, reason)
        for s in active:
            self.engine.cache.release(s.sid)
            self._fail(s.req, reason)
        return len(prefilling) + len(active)

    def fail_request(self, req: Request, reason: str) -> None:
        """Settle a request this batcher owns with an error (router use:
        a drained request that could not be requeued anywhere)."""
        self._fail(req, reason)

    # ---- scheduler side ------------------------------------------------

    def step(self) -> bool:
        """One scheduler iteration (admission + bounded prefill progress +
        a decode advance for every active session). Returns True when any
        work was done."""
        self.last_heartbeat = time.monotonic()
        # chaos drills: an armed replica_die/replica_wedge fault fires
        # here — the InjectedFault propagates out of run() and kills this
        # scheduler thread (death the router must retire), or the wedge
        # blocks with the heartbeat stale (the /healthz wedge case)
        _faults.serve_step_hook(self.replica)
        with tracing.span("serve:iteration") as iteration:
            with tracing.span("serve:admit"):
                did = self._admit()
            did = self._prefill_step() or did
            did = self._decode_all() or did
        with self._lock:
            queued, active = self._qlen_locked(), len(self._active)
            prefilling = len(self._prefilling)
        self._m_queue_depth.set(queued)
        self._m_active.set(active)
        self._m_prefilling.set(prefilling)
        if did:
            # idle passes are excluded: the histogram answers "how long
            # does a WORKING iteration hold the scheduler", not "how often
            # does the idle loop spin"
            self._m_iteration.observe(iteration.end - iteration.start)
        # beat AGAIN on completion: a step that spends its whole budget
        # inside one long dispatch (first-shape compile, big window)
        # must not leave the heartbeat aged by that dispatch — a fresh
        # pick racing it would misread this replica as wedged and fall
        # back onto genuinely stale ones. A step that truly never
        # returns (the wedge) never reaches this line, so staleness
        # still means stuck, not slow.
        self.last_heartbeat = time.monotonic()
        return did

    def _admit(self) -> bool:
        admit: list[Request] = []
        unfit: list[Request] = []
        dropped: list[Request] = []
        reaped: list[Request] = []
        now = time.perf_counter()
        with self._lock:
            # deadline reap across the WHOLE queue first: an expired
            # request must be settled here — never allowed to consume a
            # state-cache slot or burn a prefill dispatch further down.
            # One rebuild pass per class (not remove() per victim —
            # O(k·n) under the submit-shared lock during exactly the
            # mass-expiry bursts deadlines exist for), gated so
            # deadline-less workloads never pay the scan at all.
            if self._deadlines_queued:
                still_armed = False
                for q in self._queues.values():
                    keep: list[Request] = []
                    for r in q:
                        if r.expired(now):
                            reaped.append(r)
                        else:
                            keep.append(r)
                            still_armed = (still_armed
                                           or r.deadline is not None)
                    if len(keep) != len(q):
                        q.clear()
                        q.extend(keep)
                self._deadlines_queued = still_armed
            busy_sids = {s.sid for s in self._active}
            busy_sids.update(p.sess.sid for p in self._prefilling)
            capacity = min(
                self.max_active - len(self._active) - len(self._prefilling),
                self.engine.max_batch,
            )
            nwrr = len(self._wrr_seq)
            while len(admit) < capacity:
                # weighted round-robin over the non-empty class queues:
                # within a class the order stays FIFO, and with one class
                # waiting this degrades to exactly the old FIFO
                cls = jpos = None
                for i in range(nwrr):
                    j = (self._wrr_idx + i) % nwrr
                    if self._queues[self._wrr_seq[j]]:
                        cls, jpos = self._wrr_seq[j], j
                        break
                if cls is None:
                    break
                head = self._queues[cls][0]
                if head.cancelled:
                    # abandoned by its client (timeout): drop instead of
                    # spending decode steps on tokens nobody reads. A
                    # drop is not a service — the wrr cursor stays put.
                    self._queues[cls].popleft()
                    dropped.append(head)
                    continue
                # one prefill batch = one sampling config AND one model
                # (both are compile/dispatch keys); FIFO at the picked
                # head keeps admission starvation-free
                if admit and (head.sampling.key(), head.model) != (
                        admit[0].sampling.key(), admit[0].model):
                    break
                # admission by what the state can hold, not by free slots
                # alone: a family whose state grows (a decoder's pages)
                # leaves the head queued until sessions end and free what
                # it needs — or fails it, when nothing is running that
                # ever could
                if not self.engine.admits(head, admit):
                    if not (admit or self._active or self._prefilling):
                        self._queues[cls].popleft()
                        unfit.append(head)
                        continue
                    break
                self._queues[cls].popleft()
                self._wrr_idx = (jpos + 1) % nwrr
                admit.append(head)
        for r in dropped:
            self._fail(r, "cancelled before admission")
        for r in unfit:
            self._fail(r, "the session state this request needs does not "
                          "fit: nothing running will free it (release kept "
                          "sessions or shorten the request)")
        for r in reaped:
            # queue-only lifetime: the phase timeline records exactly the
            # submit→reap span, nothing else (tests pin this)
            if r.t_submit is not None:
                r.phases.append(("queue", r.t_submit, now))
            self._settle_timeout(r, "queue")
        if not admit:
            return bool(dropped or reaped or unfit)

        now = time.perf_counter()
        # admitted requests that need a tier fill (continuation whose
        # session is no longer device-resident): collected through the
        # loop and restored in ONE batched gather+scatter program
        # (SessionTiers.fill_batch) instead of a per-session dispatch —
        # the per-continuation admission cost under session churn
        records: list[list] = []  # [req, sid, slot, fresh, needs_fill]
        for req in admit:
            req.t_admit = now
            if req.t_submit is not None:
                self._m_queue_wait.observe(now - req.t_submit)
                req.phases.append(("queue", req.t_submit, now))
            sid = req.session_id
            if sid is None:
                # auto ids share a namespace with client-chosen ones:
                # skip any id the cache already holds, or an anonymous
                # request could silently inherit (and overwrite) a kept
                # session's carries. The prefix bakes in the replica
                # index AND a per-process random component so the ids
                # are unique across a replicated server and across the
                # fleet's processes (see __init__ — the router and the
                # shared disk tier both key on the sid).
                sid = f"{self._sid_prefix}-{next(self._sid_counter)}"
                while sid in self.engine.cache:
                    sid = f"{self._sid_prefix}-{next(self._sid_counter)}"
            if sid in busy_sids:
                # two in-flight requests on one session would share a cache
                # slot and corrupt each other's carries — reject the
                # newcomer loudly; the client serialises its own session
                self._fail(req, f"session {sid!r} is busy (another request "
                                "on it is still decoding)")
                continue
            busy_sids.add(sid)
            try:
                # acquire+pin ATOMICALLY: a tier fill (below) may read
                # the disk outside the cache lock, and a concurrent
                # fill_ahead's acquire must never evict this
                # just-acquired slot — neither mid-restore nor in the
                # window before a separate pin() call (release() on the
                # failure paths clears the pin along with the slot)
                slot, fresh = self.engine.admit_session(sid, req)
            except Exception as e:  # cache exhausted by pinned slots
                self._fail(req, f"{type(e).__name__}: {e}")
                continue
            # explicit continuation of a session no longer in a device
            # slot: a tiered engine restores the spilled state (pending
            # spill capture / host RAM / verified disk read) into the
            # fresh PINNED slot — the exact pre-eviction carries, so the
            # continuation decodes token-identically. The restore itself
            # is deferred to ONE fill_batch call below. Nothing
            # restorable (never created, spilled copy lost, corrupt disk
            # file quarantined): silently decoding from zero state would
            # return wrong tokens — fail loudly.
            needs_fill = req.session_id is not None and fresh
            if needs_fill and self.engine.tiers is None:
                self.engine.cache.release(sid)
                self._fail(req, f"unknown session {sid!r} (expired, "
                                "never created, or its spilled state "
                                "was lost; re-send the full prompt)")
                continue
            records.append([req, sid, slot, fresh, needs_fill])
        fill_res = {}
        if any(r[4] for r in records):
            fill_res = self.engine.tiers.fill_batch(
                [(sid, slot) for _, sid, slot, _, nf in records if nf])
        for req, sid, slot, fresh, needs_fill in records:
            if needs_fill:
                if not fill_res.get(sid):
                    self.engine.cache.release(sid)
                    self._fail(req, f"unknown session {sid!r} (expired, "
                                    "never created, or its spilled state "
                                    "was lost; re-send the full prompt)")
                    continue
                fresh = False
            sess = _Session(req, sid, slot)
            # prefix-cache lookup: fresh sessions only (a continuation's
            # prompt is a fragment, not an absolute prefix). The hit is
            # ref-held until its resumed prefill is dispatched.
            entry, matched = None, 0
            if fresh and req.use_prefix and self.engine.prefix is not None:
                entry, matched = self.engine.prefix.lookup(req.prompt)
            with self._lock:
                self._prefilling.append(
                    _Prefilling(sess, matched, entry, fresh))
        # dispatching happens in _prefill_step — same step() iteration, so
        # an unchunked admission still prefills (and gets TTFT) right here
        return True

    # ---- prefill scheduling (chunked + prefix-resumed; see module doc) --

    def _next_stop(self, p: _Prefilling,
                   chunk: int | None = None) -> int:
        """Prompt position the next dispatch advances ``p`` to: the prompt
        end, capped by the chunk size. With the prefix cache on, stops are
        stride-ALIGNED: every stop is a potential (deduped) insert point,
        so chunked prefill caches a shared prefix at block granularity —
        and without chunking, the single split lands at the largest stride
        boundary (the state after ``prompt[:k]`` must exist in the
        session's own slot for the one-copy insert). ``chunk`` pins the
        chunk size for one scheduler iteration — a live knob move
        (set_prefill_chunk) must land BETWEEN iterations, never between
        a batch's dispatch and its ``pos`` bookkeeping."""
        # opt-out requests never insert, so never pay the insert-boundary
        # split either — their prefill is the plain monolithic/chunked one
        return self._stop_from(p.pos, p.sess.req.prompt.size,
                               p.was_fresh and p.sess.req.use_prefix,
                               chunk=(self.prefill_chunk if chunk is None
                                      else chunk))

    def _stop_from(self, pos: int, total: int, fresh: bool,
                   chunk: int | None = None) -> int:
        """Pure arithmetic core of :meth:`_next_stop` — also replayed by
        :meth:`warmup` to enumerate the exact program lengths this
        scheduler will dispatch for a prompt length. ``chunk`` overrides
        the live ``prefill_chunk`` (warmup replays the stop sequence for
        every entry of the autotuner's choice set)."""
        if chunk is None:
            chunk = self.prefill_chunk
        stop = total
        if chunk is not None:
            stop = min(stop, pos + chunk)
        if self.engine.prefix is not None and fresh:
            k = self.engine.prefix.boundary(total)
            if pos < k:
                # never run past the last insertable boundary in one
                # dispatch, and keep chunk stops stride-aligned — every
                # stop is then an insert point
                stop = min(stop, k)
                if chunk is not None:
                    aligned = (stop // self.engine.prefix.stride
                               ) * self.engine.prefix.stride
                    if aligned > pos:
                        stop = aligned
        return stop

    def warmup(self, sampling: SamplingParams = GREEDY,
               prompt_lens: tuple[int, ...] = (1,)) -> int:
        """Pre-compile every program this scheduler can dispatch for the
        given prompt lengths. ``engine.warmup`` alone cannot know the
        chunk and prefix-insert split lengths — those are scheduler
        policy — so this replays :meth:`_stop_from`'s stop sequence per
        length (a cold fresh prompt, a fresh prompt resumed from a full
        prefix hit, and a continuation fragment) and warms the union of
        (phase, length) programs plus the window ladder. Callers should
        use this — or :meth:`ServeServer.warmup` — instead of calling
        the engine directly, or first traffic gets charged mid-run XLA
        compiles for the split programs."""
        finals: set[int] = set()
        chunks: set[int] = set()
        prefix = self.engine.prefix
        # every chunk size the scheduler can EVER run with: the live one
        # (read under the lock — it is a knob now) plus the autotuner's
        # whole choice set. The walk is a CLOSURE over choice MIXES, not
        # a per-choice replay: a knob move lands between scheduler
        # iterations, so one prompt's chunks may use different sizes —
        # e.g. chunk 16 then 32 on a 48-token prompt dispatches a
        # 32-length FINAL that neither pure-16 nor pure-32 replay ever
        # produces. Every position reachable under ANY mix is expanded
        # with EVERY choice, or the first mid-prompt knob move compiles
        # mid-traffic (caught by the bench's zero-compile assert).
        with self._lock:
            live_chunk = self.prefill_chunk
        chunk_values = sorted({live_chunk} | set(self.prefill_chunk_choices),
                              key=lambda c: (c is None, c))
        for t in prompt_lens:
            t = max(1, int(t))
            # (start position, was_fresh) dispatch sequences to replay —
            # longest-match lookup can resume from ANY stride multiple
            # up to boundary(t), not just the full boundary, so every
            # such start must be replayed or a partial hit's remainder
            # length dispatches an unwarmed program
            stack = [(0, True), (0, False)]
            if prefix is not None:
                for k in range(prefix.stride, prefix.boundary(t) + 1,
                               prefix.stride):
                    stack.append((k, True))
            # _stop_from is pure in (pos, fresh, chunk) for a given t,
            # so the BFS visits each (pos, fresh) once — bounded by
            # t/min_chunk * |choices| expansions
            seen: set[tuple[int, bool]] = set()
            while stack:
                pos, fresh = stack.pop()
                if pos >= t or (pos, fresh) in seen:
                    continue
                seen.add((pos, fresh))
                for chunk in chunk_values:
                    stop = self._stop_from(pos, t, fresh, chunk=chunk)
                    (finals if stop >= t else chunks).add(stop - pos)
                    if stop < t:
                        stack.append((stop, fresh))
        return self.engine.warmup(
            sampling, prompt_lens=tuple(sorted(finals)),
            windows=self.window_ladder,
            chunk_lens=tuple(sorted(chunks)),
            spec_windows=(tuple(k for k in self.spec_ladder if k > 0)
                          if self.speculative else ()))

    def _select_prefill_batch(
            self, chunk: int | None) -> tuple[list[_Prefilling], bool]:
        """FIFO-fair batch selection: the HEAD of the prefilling list
        always progresses (a stream of short prompts cannot starve a long
        prompt's chunks); compatible rows ride along — same phase
        (final/intermediate), and for finals the same sampling config
        (intermediate chunks are sampling-free programs)."""
        head = self._prefilling[0]
        final = self._next_stop(head, chunk) >= head.sess.req.prompt.size
        skey = head.sess.req.sampling.key()
        mdl = head.sess.req.model
        batch = []
        for p in self._prefilling:
            if len(batch) >= self.engine.max_prefill_batch:
                break
            if (self._next_stop(p, chunk)
                    >= p.sess.req.prompt.size) != final:
                continue
            if final and p.sess.req.sampling.key() != skey:
                continue
            # one dispatch is one model's params — intermediate chunks
            # included (the chunk program is sampling-free but not
            # model-free)
            if p.sess.req.model != mdl:
                continue
            batch.append(p)
        return batch, final

    def _prefill_step(self) -> bool:
        """Advance prompt consumption. Unchunked: run every pending
        prefill to completion now. Chunked: dispatch exactly ONE bounded
        program (<= prefill_chunk tokens per row) and return — decode
        interleaves between chunks, so a long prompt can only delay
        running sessions by one chunk's latency per token."""
        if not self._prefilling:
            return False
        # ONE chunk-size read per scheduler iteration: selection, the
        # dispatched slice, and the pos bookkeeping below must all agree
        # even while the autotuner moves the knob from its own thread —
        # a move lands between iterations, never inside one
        chunk = self.prefill_chunk
        now = time.perf_counter()
        for p in list(self._prefilling):
            if p.sess.req.cancelled:
                self._abort_prefilling(p, "cancelled during prefill")
            elif p.sess.req.expired(now):
                # mid-prefill expiry (chunked prefills span iterations):
                # stop burning chunk dispatches on a dead deadline
                self._abort_prefilling(p, None, timeout=True)
        while self._prefilling:
            batch, final = self._select_prefill_batch(chunk)
            self._dispatch_prefill(batch, final, chunk)
            if chunk is not None:
                break  # one bounded dispatch per scheduler iteration
        return True

    def _dispatch_prefill(self, batch: list[_Prefilling], final: bool,
                          chunk: int | None = None) -> None:
        # one span over building the items and the engine call (which, for
        # a final chunk, also fetches the first token: the engine's own
        # pack/launch/fetch spans split it); the rows' phase starts at the
        # engine call (t0) and ends with the span
        with tracing.span("serve:prefill_dispatch", rows=len(batch),
                          final=int(final)) as dispatch:
            prefix = self.engine.prefix
            items = []
            draft_items = []
            computed = 0  # prompt tokens this dispatch runs through the model
            # the draft is distilled against the DEFAULT model only — other
            # residents' sessions never speculate, so their prefills are not
            # mirrored either
            mirror = self.speculative and (
                batch[0].sess.req.model is None
                or batch[0].sess.req.model == self.engine.model_id)
            for p in batch:
                stop = self._next_stop(p, chunk)
                # stride-aligned insert point: the state after prompt[:pos]
                # sits in the session's own slot — one O(1) device copy caches
                # it for every future sharer (insert() dedups existing keys
                # itself, refreshing their LRU recency; rows resuming FROM an
                # entry this dispatch have p.entry set and skip)
                if (prefix is not None and p.was_fresh and p.entry is None
                        and p.sess.req.use_prefix
                        and p.pos >= prefix.stride
                        and p.pos % prefix.stride == 0):
                    prefix.insert(p.sess.req.prompt[: p.pos], p.sess.slot)
                src_slot, fresh = p.src()
                items.append((p.sess.slot, src_slot, fresh,
                              p.sess.req.prompt[p.pos: stop]))
                computed += stop - p.pos
                if mirror:
                    # mirror every target dispatch so the draft's slot state
                    # tracks the consumed context. The draft's FIRST fragment
                    # always starts from zero — it has no prefix entries or
                    # tier copies to resume from (prefix-resumed and
                    # tier-restored rows rebuild draft context from the
                    # fragment alone: lossless, lower acceptance until the
                    # draft catches up)
                    draft_items.append((p.sess.slot, not p.draft_started,
                                        p.sess.req.prompt[p.pos: stop]))
            t0 = time.perf_counter()
            try:
                if final:
                    first, logits = self.engine.prefill(
                        items, batch[0].sess.req.sampling,
                        model=batch[0].sess.req.model)
                else:
                    self.engine.prefill_chunk(items,
                                              model=batch[0].sess.req.model)
                    self.prefill_chunks_dispatched += 1
                    self._m_chunks.inc()
            except Exception as e:
                for p in batch:
                    self._abort_prefilling(
                        p, f"prefill failed: {type(e).__name__}: {e}")
                return
            # count AFTER the dispatch lands: the compute-savings gate
            # (saved vs computed) must not credit work an aborted batch
            # never did
            self.prefill_tokens_computed += computed
            if draft_items:
                try:
                    self.engine.draft_prefill(draft_items)
                    self.draft_prefills_dispatched += 1
                    for p in batch:
                        p.draft_started = True
                except Exception:
                    # draft state is acceptance-only — a failed mirror can
                    # never corrupt output (the verify window is teacher-
                    # forced by the TARGET), so the session proceeds with a
                    # stale draft instead of failing a healthy prefill; the
                    # counter is the failure's only surface (stats/bench)
                    self.draft_prefill_failures += 1
        now = dispatch.end
        phase = "prefill" if final else "prefill_chunk"
        for p in batch:
            # final prefill syncs on the first token (np.asarray), so its
            # span covers device compute; a chunk's span is dispatch only
            p.sess.req.phases.append((phase, t0, now))
        # a final dispatch hands each row its first token; a chunk's rows
        # only move on
        with tracing.span("serve:deliver"):
            for i, p in enumerate(batch):
                # the gather from a prefix slot is in flight and data-ordered:
                # the ref can drop now — and only now did the resume actually
                # happen (an aborted session must not count as savings)
                if p.entry is not None:
                    self.prefix_resumed += 1
                    self.prefix_tokens_saved += p.pos
                    prefix.release(p.entry)
                    p.entry = None
                if not final:
                    p.pos = self._next_stop(p, chunk)
                    continue
                with self._lock:
                    self._prefilling.remove(p)
                s = p.sess
                s.req.t_first_token = now
                if s.req.t_submit is not None:
                    self._m_ttft.observe(now - s.req.t_submit)
                self._append_token(
                    s, int(first[i]),
                    logit=None if logits is None else logits[i])
                if s.remaining == 0:
                    self._finish(s)
                else:
                    with self._lock:
                        self._active.append(s)

    def _abort_prefilling(self, p: _Prefilling, error: str | None,
                          *, timeout: bool = False) -> None:
        with self._lock:
            try:
                self._prefilling.remove(p)
            except ValueError:
                return  # already settled
        if p.entry is not None:
            self.engine.prefix.release(p.entry)
            p.entry = None
        self.engine.cache.release(p.sess.sid)
        if timeout:
            self._settle_timeout(p.sess.req, "prefill")
        else:
            self._fail(p.sess.req, error)

    def _decode_all(self) -> bool:
        did = False
        if self._pending is not None:
            self._resolve_pending()
            did = True
            if self._pending is not None:
                # pipelined: window i+1 is already in flight — it IS this
                # iteration's decode work
                return True
        with self._lock:
            active = list(self._active)
        if not active:
            return did
        now = time.perf_counter()
        for s in active:
            if s.req.cancelled:  # abandoned mid-decode: free the slot now
                self._retire(s)
                self.engine.cache.release(s.sid)
                self._fail(s.req, "cancelled mid-decode")
            elif s.req.expired(now):
                # deadline at a decode boundary: settle with the tokens
                # already delivered (honest partial output). The session
                # is NOT kept even under keep_session — dispatch-ahead
                # windows may have advanced the device state past the
                # returned tokens, and a continuation from an
                # indeterminate position could silently double-decode.
                self._retire(s)
                self._release_timed_out_session(s)
                self._settle_timeout(s.req, "decode")
        active = [s for s in active if not s.req.done.is_set()]
        if not active:
            return True
        # pack by (sampling config, model) — both are dispatch keys;
        # chunk to the engine's largest batch bucket; iteration order ==
        # admission order (fairness: every active session advances
        # exactly one token per step)
        groups: dict[tuple, list[_Session]] = {}
        for s in active:
            groups.setdefault((s.req.sampling.key(), s.req.model),
                              []).append(s)
        # steady-state fast path: the whole active set is one sampling
        # group in one batch bucket and nobody is waiting to be admitted —
        # advance K tokens in one program and let the NEXT iteration fetch
        # them (possibly after dispatching the window after that)
        if len(groups) == 1 and len(active) <= self.engine.max_batch:
            with self._lock:
                # a non-empty prefilling set pins K=1 like a non-empty
                # queue: decode must yield to the next prefill chunk every
                # iteration, or chunking's bounded-stall guarantee dies
                queue_empty = (not self._qlen_locked()
                               and not self._prefilling)
            if queue_empty:
                min_rem = min(s.remaining for s in active)
                kd = self._spec_k_for(active, min_rem)
                if kd > 0:
                    self._dispatch_spec_window(active, kd)
                    return True
                k = self._pick_window(min_rem)
                if k > 1:
                    self._dispatch_window(active, k)
                    return True
        for group in groups.values():
            for i in range(0, len(group), self.engine.max_batch):
                chunk = group[i : i + self.engine.max_batch]
                slots = [s.slot for s in chunk]
                toks = [s.last_token for s in chunk]
                try:
                    with tracing.span("serve:decode_dispatch",
                                      rows=len(chunk), k=1,
                                      pipelined=0) as dispatch:
                        nxt, logits = self.engine.decode(
                            slots, toks, chunk[0].req.sampling,
                            model=chunk[0].req.model)
                except Exception as e:
                    self._fail_chunk(
                        chunk, f"decode failed: {type(e).__name__}: {e}")
                    continue
                t0, t1 = dispatch.start, dispatch.end
                with tracing.span("serve:deliver"):
                    for i, (s, tok) in enumerate(zip(chunk, nxt)):
                        s.req.phases.append(("decode", t0, t1))
                        self._append_token(
                            s, int(tok), t1,
                            logit=None if logits is None else logits[i])
                        if s.remaining == 0:
                            self._retire(s)
                            self._finish(s)
        return True

    # ---- windowed decode (see module docstring) ------------------------

    def _pick_window(self, min_remaining: int) -> int:
        """Largest ladder rung no session would overshoot (a session
        within K tokens of its budget forces a smaller K — the on-device
        budget latch makes overshoot SAFE, this just keeps windows from
        decoding padding and delaying completion), additionally capped
        by ``window_cap`` — the autotuner's live K ceiling (default: the
        top rung, i.e. exactly the uncapped pick)."""
        k = 1
        cap = self.window_cap
        for w in self.window_ladder:
            if w <= min_remaining and w <= cap:
                k = max(k, w)
        return k

    def _spec_k_for(self, sessions: list[_Session],
                    min_remaining: int) -> int:
        """K_draft for a speculative window over ``sessions``, or 0 when
        plain decode is the right call. Speculation applies only to
        greedy default-model groups (the verify pass is pure argmax and
        the draft pairs the default model); the rung is the largest
        warmed ladder entry under the autotuner's ``spec_k`` cap whose
        window W=K+1 no session would overshoot — mirroring
        ``_pick_window``'s no-padding rule. ``min_remaining`` < 2 means
        at most one token is wanted, where speculation cannot win."""
        if not self.speculative:
            return 0
        cap = self.spec_k
        if cap <= 0 or min_remaining < 2:
            return 0
        s0 = sessions[0]
        if not s0.req.sampling.greedy:
            return 0
        if s0.req.model is not None and s0.req.model != self.engine.model_id:
            return 0
        k = 0
        for r in self.spec_ladder:
            if 0 < r <= cap and r + 1 <= min_remaining:
                k = max(k, r)
        return k

    def _dispatch_spec_window(self, sessions: list[_Session],
                              kd: int) -> None:
        """Dispatch a speculative verify window (draft proposes ``kd``
        tokens, target verifies all of them plus one correction in ONE
        pass); handles park in ``_pending`` like a plain window."""
        try:
            with tracing.span("serve:decode_dispatch", rows=len(sessions),
                              k=kd + 1, pipelined=0):
                win = self.engine.spec_window(
                    [s.slot for s in sessions],
                    [s.last_token for s in sessions],
                    [s.remaining for s in sessions],
                    [-1 if s.req.eos_id is None else s.req.eos_id
                     for s in sessions],
                    k_draft=kd, model=sessions[0].req.model,
                )
        except Exception as e:
            self._fail_chunk(sessions, f"decode failed: {type(e).__name__}: {e}")
            return
        self.spec_windows_dispatched[kd] = (
            self.spec_windows_dispatched.get(kd, 0) + 1)
        self._pending = (win, list(sessions))

    def _dispatch_window(self, sessions: list[_Session], k: int) -> None:
        """Dispatch a K-token window for ``sessions`` from host state; the
        handles park in ``_pending`` for the next iteration's fetch."""
        try:
            with tracing.span("serve:decode_dispatch", rows=len(sessions),
                              k=k, pipelined=0):
                win = self.engine.decode_window(
                    [s.slot for s in sessions],
                    [s.last_token for s in sessions],
                    [s.remaining for s in sessions],
                    [-1 if s.req.eos_id is None else s.req.eos_id
                     for s in sessions],
                    sessions[0].req.sampling, window=k,
                    model=sessions[0].req.model,
                )
        except Exception as e:
            self._fail_chunk(sessions, f"decode failed: {type(e).__name__}: {e}")
            return
        self.windows_dispatched[k] = self.windows_dispatched.get(k, 0) + 1
        self._count_window(k)
        self._pending = (win, list(sessions))

    def _count_window(self, k: int) -> None:
        m = self._m_window_k.get(k)
        if m is not None:  # ladder rungs are pre-resolved; others skipped
            m.inc()

    def _resolve_pending(self, pipeline: bool = True) -> None:
        """Resolve the in-flight window: if steady state still holds,
        dispatch its successor FROM ITS DEVICE HANDLES first (async
        dispatch — the fetch below then overlaps that window's compute),
        then fetch and distribute the tokens."""
        win, sessions = self._pending
        self._pending = None
        with self._lock:
            queue_empty = (not self._qlen_locked()
                           and not self._prefilling)
            same_rows = self._active == sessions
        now0 = time.perf_counter()
        # an expired (or cancelled/settled) row stops the pipeline: its
        # window boundary is where the deadline is honored, not deferred
        # behind yet another dispatched window
        stop = any(s.req.cancelled or s.req.done.is_set()
                   or s.req.expired(now0) for s in sessions)
        if pipeline and queue_empty and same_rows and not stop:
            # remaining budgets as of AFTER the unfetched window, assuming
            # full consumption (rows that EOS'd early are latched frozen on
            # device, so overestimating their budget is harmless)
            proj = [s.remaining - win.window for s in sessions]
            live = [r for r in proj if r > 0]
            if live and win.spec:
                # pipeline a speculative successor only while speculation
                # still picks a rung; a 0 pick falls through WITHOUT a
                # successor and the next _decode_all tick dispatches plain
                # (spec<->plain transitions always happen at a tick, never
                # inside the pipeline — the window types' device programs
                # differ)
                kd = self._spec_k_for(sessions, min(live))
                if kd > 0:
                    try:
                        with tracing.span("serve:decode_dispatch",
                                          rows=len(live), k=kd + 1,
                                          pipelined=1):
                            nxt = self.engine.spec_window_next(
                                win, k_draft=kd)
                    except Exception as e:
                        self._fail_chunk(
                            sessions,
                            f"decode failed: {type(e).__name__}: {e}")
                        return
                    self.spec_windows_dispatched[kd] = (
                        self.spec_windows_dispatched.get(kd, 0) + 1)
                    self.windows_pipelined += 1
                    self._pending = (nxt, list(sessions))
            elif live:
                k = self._pick_window(min(live))
                try:
                    # rows: the sessions still owed tokens after the
                    # unfetched window (the rest are latched dead on device)
                    with tracing.span("serve:decode_dispatch",
                                      rows=len(live), k=k, pipelined=1):
                        nxt = self.engine.decode_window_next(win, window=k)
                except Exception as e:
                    self._fail_chunk(
                        sessions, f"decode failed: {type(e).__name__}: {e}")
                    return
                self.windows_dispatched[nxt.window] = (
                    self.windows_dispatched.get(nxt.window, 0) + 1)
                self._count_window(nxt.window)
                self.windows_pipelined += 1
                self._pending = (nxt, list(sessions))
        # the pipeline's only sync point: blocks on window i while window
        # i+1 (if dispatched above) runs on device. Chaos drills inject
        # slow-readback latency here (the scheduler must absorb it as
        # latency, never as wrong tokens).
        _faults.serve_readback_hook()
        t_fetch = time.perf_counter()
        # ONE transfer for the token block AND the per-row summary the
        # window program latched on device (remaining budget + liveness):
        # the scheduler tick trusts the device latches instead of
        # re-deriving them per token host-side — with the fused Pallas
        # kernel those latches lived in VMEM for the whole window
        toks, dev_rem, dev_alive, logits = (
            self.engine.fetch_window_summary(win))
        now = time.perf_counter()
        # dispatch→fetch-complete: how long the window's tokens took to
        # reach the host after its program was dispatched (device compute
        # + readback, minus whatever the scheduler overlapped)
        self._m_readback.observe(now - win.t_dispatch)
        with tracing.span("serve:deliver"):
            for i, (s, row) in enumerate(zip(sessions, toks)):
                if s.req.cancelled or s.req.done.is_set():
                    continue  # the cancel sweep / a prior window settled it
                s.req.phases.append((
                    "spec_window" if win.spec else "decode_window",
                    win.t_dispatch, t_fetch))
                s.req.phases.append(("readback", t_fetch, now))
                if win.spec:
                    # accept accounting: a spec window emits accepted+1
                    # tokens per live row (the verify step that detects the
                    # first disagreement emits the target's own correction
                    # token). emitted == 0 means the row was dead at window
                    # entry — not a rejection, so it doesn't skew the
                    # histogram the autotuner steers by.
                    emitted = 0
                    for tok in row:
                        if tok == PAD_TOKEN:
                            break
                        emitted += 1
                    if emitted > 0:
                        accepted = emitted - 1
                        self.spec_accepted_tokens += accepted
                        self._m_spec_accept.observe(float(accepted))
                        if accepted >= win.window - 1:
                            outcome = "full"
                        elif accepted > 0:
                            outcome = "partial"
                        else:
                            outcome = "reject"
                        self._m_spec_outcome[outcome].inc()
                for j, tok in enumerate(row):
                    if tok == PAD_TOKEN:
                        break
                    self._append_token(
                        s, int(tok), now,
                        logit=None if logits is None else logits[i, j])
                    if s.remaining == 0:
                        break
                if not dev_alive[i] or dev_rem[i] <= 0:
                    # the device latch is the liveness authority (EOS hit or
                    # budget exhausted inside the window); the host token
                    # walk above agrees by construction — _append_token's
                    # bookkeeping mirrors the same latch rules
                    s.remaining = 0
                if s.remaining == 0:
                    self._retire(s)
                    self._finish(s)
                elif s.req.expired(now):
                    # window boundary = deadline boundary: this window's
                    # tokens were delivered above, the request settles now
                    # with that partial output (see the _decode_all sweep
                    # for why the session is never kept)
                    self._retire(s)
                    self._release_timed_out_session(s)
                    self._settle_timeout(s.req, "decode")

    def _fail_chunk(self, sessions: list[_Session], error: str) -> None:
        for s in sessions:
            self._retire(s)
            self.engine.cache.release(s.sid)
            self._fail(s.req, error)

    def _append_token(self, s: _Session, tok: int,
                      t: float | None = None, logit=None) -> None:
        if t is None:
            t = time.perf_counter()
        if logit is not None:
            s.req.token_logits.append((float(logit[0]), float(logit[1])))
        if s.req.t_tokens:
            # server-side inter-token latency: same gap definition as
            # Request.itl_gaps()/loadgen (host arrival deltas; a window's
            # burst contributes 0.0 gaps), so the two views must agree
            self._m_itl.observe(t - s.req.t_tokens[-1])
        s.req.tokens.append(tok)
        s.req.t_tokens.append(t)
        s.last_token = tok
        s.remaining -= 1
        self.tokens_generated += 1
        if s.req.eos_id is not None and tok == s.req.eos_id:
            s.remaining = 0

    def _release_timed_out_session(self, s: _Session) -> None:
        """Release a deadline-expired session's slot AND its tier copies.
        The client received PARTIAL tokens this turn, so a tier copy from
        the LAST COMPLETED boundary would resurrect the conversation
        WITHOUT them — a later continuation would silently decode a
        context inconsistent with what the client already displayed.
        Discarding makes that continuation fail "unknown session"
        loudly instead (the client re-sends its full history, exactly
        like after an un-kept completion). Contrast the FAILURE paths,
        which deliberately keep tier copies: a failed request delivered
        nothing, so the last completed boundary IS its token-identical
        recovery point."""
        self.engine.cache.release(s.sid)
        if self.engine.tiers is not None:
            self.engine.tiers.discard(s.sid)

    def _retire(self, s: _Session) -> None:
        with self._lock:
            try:
                self._active.remove(s)
            except ValueError:
                pass

    def _finish(self, s: _Session) -> None:
        if s.req.keep_session:
            # keep the carries cached (unpinned → LRU-evictable) so a
            # follow-up request with this session_id continues in place
            self.engine.cache.unpin(s.sid)
            s.req.session_id = s.sid
            if self.engine.tiers is not None:
                # durable serve-session checkpoint at the request
                # boundary (async write-behind to the disk tier): a
                # crashed-and-restarted server resumes this session
                # token-identically from the last completed request
                self.engine.tiers.checkpoint(s.sid)
        else:
            self.engine.cache.release(s.sid)
            if self.engine.tiers is not None:
                # the conversation ended un-kept: stale tier copies from
                # earlier boundaries must not resurrect it — a later fill
                # would decode from BEFORE this request's tokens, i.e.
                # wrong output. (Failure paths deliberately keep tier
                # copies: resuming a failed continuation from the last
                # completed boundary is the token-identical recovery.)
                self.engine.tiers.discard(s.sid)
        s.req.t_done = time.perf_counter()
        self.completed += 1
        self._m_req_completed.inc()
        self._emit_timeline(s.req)
        s.req.done.set()

    def _fail(self, req: Request, error: str) -> None:
        req.error = error
        req.t_done = time.perf_counter()
        self.failed += 1
        self._m_req_failed.inc()
        self._emit_timeline(req)
        req.done.set()

    def _settle_timeout(self, req: Request, stage: str) -> None:
        """Settle a deadline-expired request: its own outcome family
        (never "failed" — the client gets every token that was ready as
        a partial reply), counted by the stage that reaped it."""
        req.timed_out = True
        req.t_done = time.perf_counter()
        self.timed_out += 1
        self._m_req_timeout.inc()
        m = self._m_deadline.get(stage)
        if m is not None:
            m.inc()
        self._emit_timeline(req)
        req.done.set()

    @staticmethod
    def _emit_timeline(req: Request) -> None:
        """Emit the request's phase timeline into the installed Chrome
        tracer (``--trace``): one complete event per phase on a synthetic
        per-request row, so Perfetto shows each request's
        admit→queue→prefill→decode→readback lane. No tracer → free."""
        t = tracing.get_tracer()
        if t is None or not req.phases:
            return
        tid = req.id  # request ids are tiny; pthread idents are huge —
        t.set_tid_name(tid, f"request {req.id}")  # no collision in practice
        for name, a, b in req.phases:
            t.complete(name, a, b, tid=tid, request=req.id)
        if req.error is not None:
            t.complete("failed", req.phases[-1][2], req.t_done, tid=tid,
                       request=req.id, error=req.error)

    # ---- drivers -------------------------------------------------------

    def drain(self) -> None:
        """Drive the scheduler until no work remains (test/offline use)."""
        while self.step():
            pass

    def run(self, stop_event: threading.Event, idle_wait: float = 0.05) -> None:
        """Scheduler loop for the server's background thread: step while
        there is work, block on the submit condition when idle."""
        while not stop_event.is_set():
            if self.step():
                continue
            with self._work:
                if not self._qlen_locked() and not self._active:
                    with tracing.span("serve:wait_for_work"):
                        self._work.wait(timeout=idle_wait)
            # idle cycles beat the heartbeat too: "no traffic" and "thread
            # stuck" must look different to /healthz
            self.last_heartbeat = time.monotonic()
        if self._pending is not None:
            # graceful shutdown: the in-flight window's tokens are already
            # paid for — deliver them instead of hanging their requests
            # until client timeout (no follow-up dispatch: queue clients
            # waiting on THOSE must fail fast at stop, not decode on)
            self._resolve_pending(pipeline=False)
        # same fail-fast rule for mid-prefill sessions: a chunked prefill
        # spans many iterations, and nothing else settles its request
        for p in list(self._prefilling):
            self._abort_prefilling(p, "server stopped during prefill")

    def stats(self) -> dict:
        # one lock hold for the whole snapshot: submitted/rejected are
        # written under the lock by submit(), so reading them outside it
        # from this (client-thread) path is a data race — and a snapshot
        # whose fields come from different instants lies under load
        with self._lock:
            queued, active = self._qlen_locked(), len(self._active)
            queued_by_class = {c: len(q) for c, q in self._queues.items()}
            prefilling = len(self._prefilling)
            submitted, rejected = self.submitted, self.rejected
            window_cap, prefill_chunk = self.window_cap, self.prefill_chunk
            max_active = self.max_active
            spec_k = self.spec_k
        return {
            "replica": self.replica,
            "submitted": submitted,
            "completed": self.completed,
            "rejected": rejected,
            "failed": self.failed,
            "timed_out": self.timed_out,
            "queued_by_class": queued_by_class,
            "class_weights": list(self.class_weights),
            "tokens_generated": self.tokens_generated,
            "queued": queued,
            "active": active,
            "prefilling": prefilling,
            "max_active": max_active,
            "queue_size": self.queue_size,
            "window_ladder": list(self.window_ladder),
            "window_cap": window_cap,
            "windows_dispatched": dict(self.windows_dispatched),
            "windows_pipelined": self.windows_pipelined,
            "prefill_chunk": prefill_chunk,
            "prefill_chunk_choices": list(self.prefill_chunk_choices),
            "prefill_chunks_dispatched": self.prefill_chunks_dispatched,
            "prefix_resumed": self.prefix_resumed,
            "prefix_tokens_saved": self.prefix_tokens_saved,
            "prefill_tokens_computed": self.prefill_tokens_computed,
            "speculative": self.speculative,
            "spec_ladder": list(self.spec_ladder),
            "spec_k": spec_k,
            "spec_windows_dispatched": dict(self.spec_windows_dispatched),
            "spec_accepted_tokens": self.spec_accepted_tokens,
            "draft_prefills_dispatched": self.draft_prefills_dispatched,
            "draft_prefill_failures": self.draft_prefill_failures,
        }
