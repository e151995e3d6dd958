"""Serving subsystem: continuous-batching LM inference on the training cell.

An LSTM's per-session decode state is a fixed-size ``(h, c)`` pair per
layer — the portable O(1) autoregressive cache (PAPERS.md, "Compiler-First
State Space Duality and Portable O(1) Autoregressive Caching"). This
package turns the repo's training LM + one-shot sampler (models/generate.py)
into a serving engine:

- ``decoder_engine`` (+ ``state_cache.PagedCache``): the second
  FAMILY — a decoder with latent attention whose session state is pages of
  latents that grow with the session; it answers the same calls as
  ``engine`` (``engine.build_engine`` picks by the configuration's family)
- ``state_cache``: slot-based device-resident cache of per-session carries
  (LRU eviction, explicit detach/restore), plus ``PrefixCache`` — a
  shared-prompt prefix store (state after ``prompt[:k]`` is ONE (h, c)
  pair: exact prefix reuse is a slot copy) with longest-match lookup,
  refcounted backing slots, and LRU eviction that invalidates dependent
  entries — plus ``SessionTiers``: host-RAM and disk tiers below the
  device slots (async spill of evicted states, inline fill on
  continuation, sha256/fsync-durable session files so a restarted server
  resumes kept sessions token-identically; prefix entries spill/promote
  through the same tiers);
- ``prefix_trie``: the prefix-state FABRIC (``--prefix-fabric on``) — a
  radix trie over token sequences whose nodes own carry snapshots:
  longest-match over ANY shared prefix (tenant preambles, few-shot
  templates), leaf-first eviction with subtree accounting, tiered spill
  under a host-byte bound, and cross-replica propagation of hot nodes
  over the remote transport (idempotent by token-bytes hash);
- ``engine``: bucketed jitted prefill/decode programs over the cache —
  compile count bounded per (phase, bucket[, window], sampling), never
  per batch composition — including ``decode_window``: K tokens per XLA
  program with on-device per-row EOS/budget latching, returned as device
  handles so readback can be pipelined; prefill gathers from per-row src
  slots (resume at any offset from a cached prefix) and ``prefill_chunk``
  consumes a bounded slice of prompt per program;
- ``batcher``: continuous-batching scheduler (admission control, bounded
  queue backpressure, round-robin decode fairness) with an adaptive
  decode-window ladder, dispatch-ahead async readback (window i+1 is
  dispatched before window i's tokens are fetched), prefix-cache
  admission (fresh prompts resume from their longest cached prefix) and
  chunked prefill (<= one bounded prefill program per scheduler
  iteration — a long prompt cannot stall running sessions' decode);
- ``router``: the data-parallel admission front (``--replicas N``) —
  N engine+batcher replicas (thread-per-replica on CPU, device-per-
  replica on TPU, mesh-per-replica with ``--mesh-shards`` — a
  tensor-parallel engine whose params/state shard H across a device
  group), session→replica affinity so recurrent-state slots
  and prefix entries stay replica-local, one global bounded admission
  queue (429), and honest replica-death handling (queued work requeued,
  in-flight failed loudly, idle kept sessions migrated via
  detach/restore);
- ``remote``: the remote-replica RPC transport (``--remote-replica
  URL``) — a peer serve PROCESS satisfying the same router-facing
  surface over the stdlib HTTP endpoint (generate RPCs on
  ``/v1/generate``, liveness on ``/replica/heartbeat``, affinity on
  ``/replica/has_session``), so the admission router becomes a
  front-of-fleet tier and replica death generalises to host death
  (kept sessions fail over through the shared ``--session-dir`` tier);
- ``autotune``: the online serve autotuner (``--autotune on``) — a
  controller thread over windowed telemetry deltas that moves the
  decode-window cap, the prefill-chunk size, the host-tier bound and
  the best-effort admission fraction within pre-warmed bounds (it can
  never trigger a mid-traffic compile), with hysteresis so flat
  workloads never oscillate; decisions exported via ``/stats``
  ``autotune`` + ``serve_autotune_moves_total{knob,direction}``;
- ``registry``: sha256-verified model artifact store (the training→
  serving hand-off: ``supervise --registry-dir`` publishes each new
  best checkpoint; corrupt artifacts are quarantined, never served);
- ``rollout``: the zero-downtime rollout controller (``--registry-dir``
  / ``POST /rollout``) — rolls a registry version across the replicas
  one at a time (drain → swap → off-path warmup → rejoin; kept sessions
  migrate, queued work requeues, capacity stays >= N-1), with optional
  canary shadowing + token-diff before promotion, and the drain/rejoin
  machinery doubles as the device-slot RESIZE move the autotuner's
  capacity leg requests; the engine itself multiplexes N resident
  models (per-model compile-key namespaces and slot accounting,
  requests routed by their ``model`` field);
- ``server``: stdlib ThreadingHTTPServer JSON endpoint + in-process
  client over the replica set, with ``GET /metrics`` Prometheus
  exposition of the stack's telemetry registry (obs/, ``replica``-
  labelled serve families) and histogram summaries inside ``/stats``;
  ``/healthz`` fans per-replica heartbeats into ok/degraded/down;
- ``loadgen``: the in-process closed/open-loop driver the chaos drill and
  the serving tests push traffic through (not a benchmark: speed is
  measured by ``BENCHMARK.json`` + ``benchmark/``).

Telemetry: every layer records into ONE registry (``ServeEngine(
registry=...)``, default ``obs.REGISTRY``; ``obs.NULL_REGISTRY``
disables) — queue depth/wait, scheduler-iteration time, server-side
TTFT/ITL histograms, window-K and prefill-chunk counters, compile and
cache events. Spans: the scheduler (``serve:*``) and the engine
(``engine:*``) open ``utils.tracing.span``s where the work happens; under a
profiler session (``--profile-dir``, the benchmark's traced run) they land
on the trace's host plane beside the device's operations, and with
``--trace`` in the Chrome JSON, where the batcher also writes each request's
admit→queue→prefill→decode→readback timeline.

CLI: ``python -m lstm_tensorspark_tpu.cli serve --selftest`` (see cli.py).
"""

from .state_cache import (CacheFullError, PagedCache, PrefixCache,
                          SessionTiers, StateCache)
from .prefix_trie import PrefixPropagator, PrefixTrie
from .autotune import AutoTuneConfig, AutoTuner
from .engine import (
    PAD_TOKEN,
    DecodeWindow,
    SamplingParams,
    ServeEngine,
    UnknownModelError,
    build_engine,
)
from .decoder_engine import DecoderEngine
from .batcher import (
    CLASSES,
    Batcher,
    DeadlineExceededError,
    QueueFullError,
    Request,
)
from .registry import ModelRegistry, RegistryError, config_fingerprint
from .rollout import RolloutController, RolloutError
from .router import Replica, Router
from .remote import RemoteBatcher, RemoteReplica
from .server import InprocessClient, ServeServer
from .loadgen import run_loadgen

__all__ = [
    "AutoTuneConfig",
    "AutoTuner",
    "Batcher",
    "CLASSES",
    "CacheFullError",
    "DeadlineExceededError",
    "DecodeWindow",
    "DecoderEngine",
    "InprocessClient",
    "ModelRegistry",
    "PAD_TOKEN",
    "PagedCache",
    "PrefixCache",
    "PrefixPropagator",
    "PrefixTrie",
    "QueueFullError",
    "RegistryError",
    "RolloutController",
    "RolloutError",
    "RemoteBatcher",
    "RemoteReplica",
    "Replica",
    "Request",
    "Router",
    "SamplingParams",
    "ServeEngine",
    "ServeServer",
    "SessionTiers",
    "StateCache",
    "UnknownModelError",
    "build_engine",
    "config_fingerprint",
    "run_loadgen",
]
