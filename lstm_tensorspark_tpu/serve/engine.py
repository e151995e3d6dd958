"""Bucketed jitted prefill/decode programs over the recurrent-state cache.

The decode step for a packed batch is ONE compiled program: gather each
row's ``(h, c)`` from the cache by slot index, run the shared training cell
(`models.generate.decode_one` → `ops.lstm_cell.lstm_step` on pre-fused
kernels), sample with `models.generate.sample_logits`, scatter the new
carries back. Prefill is the same shape of program around the masked
`lm_backbone` scan (carry-freeze at padded steps), so a right-padded prompt
ends with exactly the state an unpadded run would produce and the first
sampled token is token-identical to `models/generate.py`.

**Windowed decode** (`decode_window`): the same fused step wrapped in a
`lax.scan` that advances the packed batch K tokens in ONE XLA program —
carries are gathered from the cache once at window entry and scattered
once at exit, so K-fold fewer dispatches, gathers, scatters and host
round-trips per generated token. Per-row liveness is latched **on
device**: a row that emits its ``eos_id`` or exhausts its token budget
freezes its carries for the rest of the window and emits ``PAD_TOKEN``
(-1), so a window is always safe to run even when rows finish mid-window
— frozen rows scatter their unchanged carries back. The window program
returns device HANDLES (:class:`DecodeWindow`), not host arrays: the
batcher can dispatch window i+1 from window i's ``next_tokens``/
``alive``/``remaining`` handles *before* fetching window i's tokens
(`fetch_window`), overlapping host readback and Python token
distribution with device compute (JAX async dispatch; program order is
enforced by the cache arrays threading functionally through every
dispatch).

**Resumable / chunked prefill**: every prefill program gathers carries
from per-row ``src`` slots and scatters to ``dst`` slots. With src == dst
that is the classic in-place prefill; with src pointing at a
prefix-cache slot (state_cache.PrefixCache) the program resumes prefill
at an arbitrary prompt offset from a cached carry — the src slot is
READ-ONLY in the program, so a shared prefix is never aliased by a
session's writes. ``prefill_chunk`` is the head-less variant
(consume up to C tokens, scatter state, sample nothing): the batcher
chains chunk programs — one bounded dispatch per scheduler iteration —
so a bucket-128 prompt no longer stalls every running session's decode
behind one monolithic prefill program.

Recompile discipline (the XLA-on-TPU cost that kills naive serving): every
host-visible batch is padded to a **bucket** —

- prompts pad to the smallest length bucket that fits (``prefill_buckets``);
- batches pad to the smallest batch bucket (``batch_buckets``), dead rows
  pointing at the cache's scratch slot;
- window sizes come from a small fixed ladder chosen by the batcher
  (e.g. 1/4/8), each a compile key: at most one compile per
  ``("decode_window", batch-bucket, K, sampling-config)``;
- intermediate prefill chunks are sampling-free: one compile per
  ``("prefill_chunk", batch-bucket, length-bucket)`` across ALL sampling
  configs;

so XLA compiles at most once per (phase, batch-bucket[, length-bucket]
[, window], sampling-config), never per batch composition.
`compile_counts` records actual traces (incremented at trace time) and is
asserted in tests/test_serve_batcher.py + tests/test_serve_window.py.

Sampling parameters are compile-time constants (they specialize the sampled
program, exactly as in `make_generate_fn`); the batcher groups requests by
`SamplingParams.key()` so one batch is one sampling config. Non-greedy
sampling draws from an engine-global rng chain — reproducible for a fixed
submission order, but not per-session; greedy decode is deterministic and
is the parity-tested mode.

**Mesh (tensor-parallel) engine** (``mesh_shards > 1``): the replica's
params and cache slots shard their hidden/gate dimension over a one-axis
``("model",)`` device mesh using the training-side GSPMD specs
(parallel/tensor_parallel.py) — the same jit programs then run sharded
with XLA deriving the per-step h all-gather and logits psum from the
placements, so a model too large for one chip serves behind the router
as just another replica. Compile-key families grow a trailing shard
axis (``("decode_window", bucket, K, sampling, shards)``); the Pallas
window kernel is single-device and falls back to the scan program,
loudly and counted (tests/test_serve_mesh.py pins token-identical
greedy AND sampled parity vs the single-device engine).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from .. import obs
from ..models.generate import decode_one, fuse_layers, sample_logits
from ..models.lstm_lm import LMConfig, _head_kernel, lm_backbone
from ..ops import pallas_decode
from ..resilience import faults as _faults
from ..utils.tracing import span
from .state_cache import DetachedState, PrefixCache, SessionTiers, StateCache

# Emitted by decode_window for a row that is no longer live (post-EOS /
# budget-exhausted / batch padding): the host stops distributing a row's
# tokens at the first PAD_TOKEN. -1 cannot collide with a vocab id.
PAD_TOKEN = -1
assert pallas_decode.PAD_TOKEN == PAD_TOKEN  # one wire contract, two files

#: decode_kernel choices: "scan" = the lax.scan window; "pallas" = the
#: fused VMEM-resident window kernel (ops/pallas_decode.py; interpreter
#: mode off-TPU so CPU tier-1 proves parity); "auto" = pallas on TPU
#: when the VMEM plan fits, scan otherwise (interpreted pallas is a
#: correctness path, not a fast one).
DECODE_KERNELS = ("auto", "pallas", "scan")


class UnknownModelError(Exception):
    """A request named a model that is not resident on this engine (or,
    at the router, on any live replica). Maps to HTTP 404 — the client
    asked for something the fleet does not currently serve, which is
    neither a bad request shape nor a capacity problem."""


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    """Per-request sampling config — static at trace time (one compiled
    program per distinct config, same contract as `make_generate_fn`)."""

    temperature: float = 1.0
    top_k: int | None = None
    top_p: float | None = None
    greedy: bool = False

    def key(self) -> tuple:
        return (self.temperature, self.top_k, self.top_p, self.greedy)


GREEDY = SamplingParams(greedy=True)


@dataclasses.dataclass(frozen=True)
class DecodeWindow:
    """A dispatched (possibly still in-flight) decode window.

    All array fields are DEVICE handles — nothing here forces a sync.
    ``tokens`` is the window's output ``[batch_b, window]`` (``PAD_TOKEN``
    for non-live rows); ``next_tokens``/``alive``/``remaining`` are the
    row states a follow-up window needs, so :meth:`ServeEngine.
    decode_window_next` can dispatch window i+1 from window i's handles
    before the host ever reads window i (`fetch_window`)."""

    tokens: jax.Array       # [batch_b, window] int32, PAD_TOKEN when dead
    next_tokens: jax.Array  # [batch_b] int32 — input for the next window
    alive: jax.Array        # [batch_b] bool — rows still decoding
    remaining: jax.Array    # [batch_b] int32 — per-row budget left
    slots: jax.Array        # [batch_b] int32 cache slots (reused as-is)
    eos_ids: jax.Array      # [batch_b] int32, -1 = no eos for that row
    batch_b: int
    window: int
    n: int                  # live (non-padding) rows; fetch strips the rest
    sampling: SamplingParams
    # host perf_counter stamp taken right after dispatch: the batcher
    # derives dispatch→fetch readback latency and the request timeline's
    # decode_window span from it (telemetry only — never device-ordered)
    t_dispatch: float = 0.0
    # which resident model produced this window — decode_window_next
    # dispatches the follow-up against the same model's params
    model: str | None = None
    # speculative verify window (spec_window): ``window`` is the verify
    # length W = K_draft + 1 (max tokens one spec step can emit), and the
    # follow-up dispatch goes through spec_window_next, never
    # decode_window_next — the two programs carry different device state
    # (the spec one also threads the draft model's carries)
    spec: bool = False


def _fetch_span(win: DecodeWindow) -> span:
    """The wait for a window's answer: the one readback span."""
    return span("engine:fetch", program="spec_fn" if win.spec else "window_fn",
                rows=win.n, k=win.window)


def _bucket_for(value: int, buckets: tuple[int, ...], what: str) -> int:
    for b in buckets:
        if value <= b:
            return b
    raise ValueError(f"{what} {value} exceeds the largest bucket {buckets[-1]}")


class ServeEngine:
    """Owns params, the fused kernels, the state cache, and the per-bucket
    compiled programs. Thread-safe: one lock serialises device dispatch
    (the cache arrays are threaded through jit functionally — concurrent
    steps would race on `cache.swap`)."""

    def __init__(
        self,
        params,
        cfg: LMConfig,
        *,
        num_slots: int = 64,
        prefill_buckets: tuple[int, ...] = (8, 16, 32, 64, 128),
        batch_buckets: tuple[int, ...] = (1, 2, 4, 8, 16),
        max_sampling_configs: int = 16,
        rng_seed: int = 0,
        prefix_cache: bool = False,
        prefix_stride: int = 8,
        prefix_entries: int = 16,
        prefix_fabric: bool = False,
        prefix_nodes: int = 64,
        prefix_host_mb: float = 64.0,
        tiered_cache: bool = False,
        host_tier_entries: int = 256,
        session_dir: str | None = None,
        replica: int = 0,
        registry=None,
        device=None,
        decode_kernel: str = "auto",
        mesh_shards: int = 1,
        mesh_devices=None,
        model_id: str = "default",
        model_version: int = 0,
    ):
        # serving never rematerialises (same override as generate())
        if cfg.remat_chunk is not None:
            cfg = dataclasses.replace(cfg, remat_chunk=None)
        self.cfg = cfg
        # device-per-replica serving (serve/router.py): committing params
        # + cache arrays pins every program of this engine to one device,
        # so N replicas spread across jax.devices() compute concurrently
        # (uncommitted host inputs follow the committed operands)
        self.device = device
        # ---- mesh-per-replica: tensor-parallel engine ----------------
        # mesh_shards > 1 shards THIS replica's params and cache slots
        # over a one-axis ("model",) mesh (parallel/mesh.make_serve_mesh)
        # using the exact GSPMD specs training uses
        # (parallel/tensor_parallel.lm_param_specs: gate kernels
        # column-sharded [D, H/P], recurrent [H, H/P], head row-sharded
        # [H/P, V], embedding replicated) — XLA derives the per-step h
        # all-gather and the logits psum from the placements, so every
        # existing jit program (prefill/decode/decode_window) runs
        # sharded UNCHANGED and the batcher/router never know. The model
        # no longer has to fit one chip; behind the router a mesh
        # replica is just another replica.
        self.mesh_shards = int(mesh_shards)
        self.mesh = None
        cache_sharding = None
        if self.mesh_shards > 1:
            if device is not None:
                raise ValueError(
                    "mesh_shards > 1 owns its own device group — do not "
                    "also pass device= (device-per-replica placement)")
            if cfg.hidden_size % self.mesh_shards != 0:
                raise ValueError(
                    f"hidden_size {cfg.hidden_size} is not divisible by "
                    f"mesh_shards {self.mesh_shards} — the gate/hidden "
                    "dimension shards evenly or not at all")
            from ..parallel.mesh import make_serve_mesh
            from ..parallel.tensor_parallel import place_lm_params
            from jax.sharding import NamedSharding, PartitionSpec as P

            self.mesh = make_serve_mesh(self.mesh_shards,
                                        devices=mesh_devices)
            params = place_lm_params(params, self.mesh)
            # cache slots shard over the hidden axis exactly like h: the
            # gather-by-slot, the step, and the scatter-back all stay on
            # the shard-local rows, with no resharding at window entry
            cache_sharding = NamedSharding(self.mesh, P(None, None, "model"))
        elif mesh_devices is not None:
            raise ValueError("mesh_devices needs mesh_shards > 1")
        else:
            params = self._place_params(params)
        self.params = params
        self.fused_layers = fuse_layers(params, cfg)  # once, at init
        # ---- resident models -----------------------------------------
        # N models (same LMConfig — the cache slots and bucket programs
        # are shape-compatible across residents) live side by side; each
        # dispatch resolves its (params, fused) pair by model id, and the
        # batcher groups batches so one dispatch is one model. The
        # DEFAULT model (``model_id``) keeps the legacy compile-key arity
        # — a single-model fleet's keys, stats, and tests are unchanged;
        # extra residents append their id to program/count keys (family
        # string stays FIRST: graftlint warmup-coverage reads elts[0]).
        self.model_id = str(model_id)
        self._residents: dict[str, dict] = {
            self.model_id: {"params": self.params,
                            "fused": self.fused_layers,
                            "version": model_version},
        }
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self.batch_buckets = tuple(sorted(batch_buckets))
        # the telemetry registry every serve-side component records into
        # (obs.REGISTRY process-wide default; obs.NULL_REGISTRY disables);
        # the batcher and server read engine.metrics so one constructor
        # argument scopes the whole stack
        self.metrics = obs.REGISTRY if registry is None else registry
        self.cache = StateCache(cfg.num_layers, num_slots, cfg.hidden_size,
                                registry=self.metrics, device=device,
                                sharding=cache_sharding)
        # tiered session-state cache (state_cache.SessionTiers): device
        # slots stay tier 0; LRU-evicted sessions spill async to host RAM
        # with a durable disk tier below (``session_dir`` — also what a
        # restarted server restores sessions from). A session_dir alone
        # implies the tiers: durability needs the spill plane.
        self.tiers = (
            SessionTiers(self.cache, host_entries=host_tier_entries,
                         directory=session_dir, registry=self.metrics,
                         replica=replica)
            if (tiered_cache or session_dir is not None) else None
        )
        # shared-prompt prefix reuse: opt-in at engine construction; the
        # batcher consults engine.prefix on every fresh admission when
        # present. ``prefix_fabric`` selects the radix PrefixTrie
        # (longest-match over ANY shared prefix, host-byte-bounded
        # spill, cross-replica propagation hooks) over the exact-match
        # PrefixCache — both duck-type the same store contract, so
        # everything downstream of engine.prefix is agnostic. With
        # tiers attached, an evicted backing slot SPILLS the entry
        # instead of invalidating it (either store).
        if prefix_fabric:
            from .prefix_trie import PrefixTrie
            self.prefix = PrefixTrie(
                self.cache, stride=prefix_stride, max_nodes=prefix_nodes,
                host_bytes=int(prefix_host_mb * 2 ** 20),
                registry=self.metrics, tiers=self.tiers)
        elif prefix_cache:
            self.prefix = PrefixCache(
                self.cache, stride=prefix_stride,
                max_entries=prefix_entries, registry=self.metrics,
                tiers=self.tiers)
        else:
            self.prefix = None
        # sampling params are compile keys and client-controlled at the
        # HTTP boundary: bound how many distinct configs this engine will
        # ever compile, or a client sweeping temperatures could thrash
        # XLA (~20-40 s per TPU compile) and grow the program cache
        # without limit
        self.max_sampling_configs = max_sampling_configs
        self._sampling_keys: set[tuple] = set()
        # ---- decode-kernel selection (ops/pallas_decode.py) ----------
        # resolved ONCE here to "pallas" or "scan"; per-dispatch the
        # pallas path still falls back to the scan window for sampling
        # configs / shapes the kernel does not cover (counted honestly
        # in decode_window_scan_fallbacks — a silent switch would make
        # the measured speedup a lie).
        if decode_kernel not in DECODE_KERNELS:
            raise ValueError(
                f"decode_kernel must be one of {DECODE_KERNELS}, got "
                f"{decode_kernel!r}")
        if self.mesh is not None:
            platform = self.mesh.devices.flat[0].platform
        else:
            platform = (device.platform if device is not None
                        else jax.default_backend())
        if decode_kernel == "auto":
            # off-TPU the interpreted kernel is a correctness path, not
            # a fast one — auto stays on the scan window there; a SHARDED
            # engine resolves to scan too (the fused kernel is a
            # single-device program — it cannot read sharded carries)
            use_pallas = (platform == "tpu" and self.mesh_shards == 1
                          and pallas_decode.plan_fits(
                self.batch_buckets[-1], 8, cfg.num_layers,
                cfg.hidden_size, cfg.embed, cfg.vocab_size, sampled=True))
            self.decode_kernel = "pallas" if use_pallas else "scan"
        else:
            self.decode_kernel = decode_kernel
        if self.decode_kernel == "pallas" and self.mesh_shards > 1:
            # the EXPLICIT pallas pick on a mesh engine: honored as a
            # request, unsatisfiable as a program — every window falls
            # back to the scan program (counted per dispatch in
            # decode_window_scan_fallbacks via _pallas_window_ok), and
            # this boot-time line says so before the first request pays
            # attention to the counter. Loud fallback, never a crash or
            # a silent resolve.
            print(
                f"serve: --decode-kernel pallas is not supported on a "
                f"{self.mesh_shards}-shard mesh engine (the fused window "
                "kernel is single-device) — every decode window falls "
                "back to the scan program, counted in "
                "decode_window_scan_fallbacks", flush=True)
        self._pallas_interpret = platform != "tpu"
        self.decode_window_scan_fallbacks = 0  # pallas→scan dispatches
        # sharded engines grow a trailing shard axis on every compile-key
        # family — ("decode_window", bucket, K, sampling, shards) — so a
        # mixed fleet's aggregated /stats can never conflate a sharded
        # program with a single-device one; single-device engines keep
        # the legacy arity (shards == 1 adds nothing to the key)
        self._shard_suffix: tuple = (
            (self.mesh_shards,) if self.mesh_shards > 1 else ())
        self.compile_counts: dict[tuple, int] = defaultdict(int)
        self._prefill_fns: dict[tuple, callable] = {}
        self._prefill_chunk_fns: dict[tuple, callable] = {}
        self._decode_fns: dict[tuple, callable] = {}
        self._decode_window_fns: dict[tuple, callable] = {}
        self._decode_window_pallas_fns: dict[tuple, callable] = {}
        # ---- speculative decoding (draft model) ----------------------
        # attach_draft installs a small distilled draft LM paired with
        # the DEFAULT model; spec_window then verifies K_draft proposed
        # tokens in one teacher-forced target pass. The draft's h/c live
        # in their own arrays indexed by the SAME slot numbers as the
        # state cache (never spilled through SessionTiers — draft state
        # is acceptance-only, rebuilt from zero on restore).
        self.draft: dict | None = None
        self._draft_h = None
        self._draft_c = None
        self._draft_prefill_fns: dict[tuple, callable] = {}
        self._spec_window_fns: dict[tuple, callable] = {}
        self._spec_window_pallas_fns: dict[tuple, callable] = {}
        self._rng = jax.random.PRNGKey(rng_seed)
        self._dummy_rng = jax.random.PRNGKey(0)
        self._lock = threading.RLock()
        # compile_counts gets its own tiny mutex: _lock is held across
        # entire device calls (dispatch serialization), and stats/health
        # readers must never block behind an in-flight — possibly
        # wedged — dispatch just to copy a counter dict
        self._counts_lock = threading.Lock()
        self._warming = False  # warmup decodes bypass the fault hook
        # per-phase compile counter for /metrics, bumped at trace time
        # alongside compile_counts (which keeps the full per-key detail
        # for /stats — bucket/window/sampling tuples are too wide for
        # Prometheus label cardinality)
        fam = self.metrics.counter(
            "serve_compiles_total", "XLA traces by program phase",
            labelnames=("phase",))
        self._m_compiles = {
            phase: fam.labels(phase=phase)
            for phase in ("prefill", "prefill_chunk", "decode",
                          "decode_window", "decode_window_pallas",
                          "spec_window", "spec_window_pallas",
                          "draft_prefill")
        }

    # ---- limits --------------------------------------------------------

    @property
    def max_prompt_len(self) -> int:
        return self.prefill_buckets[-1]

    @property
    def max_batch(self) -> int:
        return self.batch_buckets[-1]

    # ---- the family seam (what a scheduler asks of ANY engine) ----------
    # An LSTM session's state is O(1): a free slot is all it needs, a
    # prefill batch is as wide as a decode batch, and the logits handed
    # back with the tokens (`prefill`, `decode`, `fetch_window_summary`)
    # are None. `DecoderEngine` answers the same questions by pages, by
    # its flat token axis and with logits.

    family = "lstm"

    @property
    def max_prefill_batch(self) -> int:
        return self.batch_buckets[-1]

    def admits(self, req, ahead=()) -> bool:
        return True

    def admit_session(self, sid: str, req) -> tuple[int, bool]:
        return self.cache.acquire_pinned(sid)

    # ---- resident models ----------------------------------------------

    # The ``self._residents`` reads below are DELIBERATELY lock-free:
    # updates REPLACE the dict wholesale (add/remove/swap never mutate it
    # in place), so a reader sees either the whole old table or the whole
    # new one — and stats/health/routing probes can never block behind an
    # in-flight (possibly wedged) dispatch holding _lock.

    def _resolve_model(self, model: str | None):
        """``(model_id, params, fused, key_suffix)`` for one dispatch.
        ``None`` means the default model; the default's suffix is empty so
        its compile keys keep the legacy arity."""
        mid = self.model_id if model is None else model
        res = self._residents.get(mid)  # graftlint: disable=cross-thread-state
        if res is None:
            raise UnknownModelError(
                f"model {mid!r} is not resident on this engine "
                f"(resident: {sorted(self._residents)})")  # graftlint: disable=cross-thread-state
        suffix = () if mid == self.model_id else (mid,)
        return mid, res["params"], res["fused"], suffix

    def _place_params(self, params):
        """Single-device placement: COMMITTED to this replica's device
        (device-per-replica — an uncommitted tree on device 0 would be
        copied across on every dispatch of a replica on device 3), or
        at least resident on the default device (a checkpoint restore
        hands over numpy arrays, which every dispatch would upload)."""
        return jax.device_put(params, self.device)

    def has_model(self, model_id: str | None) -> bool:
        return (model_id is None
                or model_id in self._residents)  # graftlint: disable=cross-thread-state

    @property
    def model_version(self) -> int | str:
        """The DEFAULT model's resident version (what a versionless
        request is served by) — rollout observability's convergence
        check."""
        return self._residents[self.model_id]["version"]  # graftlint: disable=cross-thread-state

    def resident_models(self) -> dict[str, int | str]:
        """{model_id: version} of every resident (default included),
        read via the wholesale-replace protocol above."""
        residents = self._residents  # graftlint: disable=cross-thread-state
        return {mid: res["version"] for mid, res in residents.items()}

    def add_model(self, model_id: str, params, *, version: int | str = 0):
        """Make a model resident (or replace one): mesh-place its params
        like __init__ did for the boot model, fuse once, and install
        under the dispatch lock — in-flight dispatches finish on the old
        pair, the next dispatch reads the new one. Same-shape params
        reuse the already-compiled programs (params are traced arguments,
        not constants), so a same-model weight swap costs ZERO compiles;
        a NEW model id gets its own compile-key namespace and must be
        warmed before taking traffic (rollout controller's warmup
        phase)."""
        model_id = str(model_id)
        if self.mesh is not None:
            from ..parallel.tensor_parallel import place_lm_params
            params = place_lm_params(params, self.mesh)
        else:
            params = self._place_params(params)
        fused = fuse_layers(params, self.cfg)
        with self._lock:
            residents = dict(self._residents)
            residents[model_id] = {
                "params": params, "fused": fused, "version": version}
            # REPLACE the table (resident_models reads it lock-free)
            self._residents = residents
            if model_id == self.model_id:
                self.params = params
                self.fused_layers = fused

    def swap_model(self, params, *, model_id: str | None = None,
                   version: int | str | None = None) -> None:
        """Replace an ALREADY-resident model's params (the rolling-reload
        swap step). Unlike :meth:`add_model` this refuses unknown ids —
        a typoed rollout must fail loudly, not silently grow a second
        resident nobody routes to."""
        mid = self.model_id if model_id is None else str(model_id)
        with self._lock:
            if mid not in self._residents:
                raise UnknownModelError(
                    f"cannot swap model {mid!r}: not resident "
                    f"(resident: {sorted(self._residents)})")
            if version is None:
                version = self._residents[mid]["version"]
            self.add_model(mid, params, version=version)

    def remove_model(self, model_id: str) -> None:
        """Evict a non-default resident and its compiled programs. The
        caller (rollout controller / server) is responsible for having
        drained the model's sessions first — the engine only owns
        params and programs."""
        with self._lock:
            if model_id == self.model_id:
                raise ValueError(
                    f"cannot remove the default model {model_id!r}")
            if model_id not in self._residents:
                raise UnknownModelError(
                    f"model {model_id!r} is not resident")
            residents = dict(self._residents)
            residents.pop(model_id)
            self._residents = residents
            for cache in (self._prefill_fns, self._prefill_chunk_fns,
                          self._decode_fns, self._decode_window_fns,
                          self._decode_window_pallas_fns):
                for key in [k for k in cache if k and k[-1] == model_id]:
                    cache.pop(key)

    def resize_slots(self, num_slots: int) -> None:
        """Reallocate the state cache at a new device-slot count — the
        rollout controller's drain-and-rejoin resize move (the PR 14
        autotuner residual: slot count is no longer a frozen boot
        shape). Only legal with no resident sessions; prefix entries are
        dropped first (they are derived state, re-insertable)."""
        prefix = self.prefix  # outside _lock: stats() reads it lock-free
        if prefix is not None:
            prefix.clear()  # takes the prefix cache's own lock
        with self._lock:
            self.cache.resize(num_slots)
            if self.draft is not None:
                # draft state is slot-indexed alongside the cache: resize
                # reallocates it to the new slot count (zeros — legal,
                # resize requires no resident sessions)
                self._alloc_draft_state_locked()

    # ---- speculative decoding: draft model ----------------------------

    # ``self.draft`` follows the ``_residents`` wholesale-replace
    # protocol above: attach_draft REPLACES the dict under _lock (never
    # mutates it in place), so the lock-free probes below see either no
    # draft or a whole one — and never block behind an in-flight
    # (possibly wedged) dispatch holding _lock. The draft h/c arrays are
    # NOT covered by this: they are swapped on every spec dispatch, so
    # every ``_draft_h``/``_draft_c`` touch stays under _lock.

    @property
    def has_draft(self) -> bool:
        return self.draft is not None  # graftlint: disable=cross-thread-state

    def attach_draft(self, draft_params, draft_cfg: LMConfig, *,
                     version: int | str = 0) -> None:
        """Install the distilled draft LM paired with the DEFAULT model.
        The draft proposes K_draft greedy tokens per :meth:`spec_window`
        dispatch; the target verifies them all in one teacher-forced
        pass, so greedy output stays token-identical by construction no
        matter how bad the draft is — draft quality only moves the
        acceptance rate. Single-device engines only: the draft cache and
        the fused spec kernel are unsharded programs."""
        if self.mesh_shards > 1:
            raise ValueError(
                "speculative decoding is not supported on a mesh "
                f"({self.mesh_shards}-shard) engine — the draft cache and "
                "the spec verify programs are single-device")
        if draft_cfg.vocab_size != self.cfg.vocab_size:
            raise ValueError(
                f"draft vocab {draft_cfg.vocab_size} != target vocab "
                f"{self.cfg.vocab_size} — proposals must share the "
                "token space they are verified in")
        if draft_cfg.remat_chunk is not None:
            draft_cfg = dataclasses.replace(draft_cfg, remat_chunk=None)
        with self._lock:
            self.draft = {
                "params": draft_params,
                "fused": fuse_layers(draft_params, draft_cfg),
                "cfg": draft_cfg,
                "version": version,
            }
            self._alloc_draft_state_locked()

    def _alloc_draft_state_locked(self) -> None:
        """(Re)allocate the draft h/c arrays: ``[L_draft, num_slots + 1,
        H_draft]`` f32, same slot indexing (scratch row included) as the
        state cache. Zero state is always SAFE here — the draft never
        affects emitted tokens, only how many of its proposals the
        target accepts."""
        dcfg = self.draft["cfg"]
        total = int(self.cache.h.shape[1])
        zeros = jnp.zeros((dcfg.num_layers, total, dcfg.hidden_size),
                          jnp.float32)
        if self.device is not None:
            zeros = jax.device_put(zeros, self.device)
        self._draft_h = zeros
        self._draft_c = zeros

    # ---- compiled programs --------------------------------------------

    def _admit_sampling(self, sampling: SamplingParams) -> None:
        key = sampling.key()
        if key in self._sampling_keys:
            return
        if len(self._sampling_keys) >= self.max_sampling_configs:
            raise ValueError(
                f"engine already compiled {self.max_sampling_configs} "
                "distinct sampling configs; rejecting a new one (raise "
                "max_sampling_configs if this workload is legitimate)"
            )
        self._sampling_keys.add(key)

    def _next_rng(self, sampling: SamplingParams):
        if sampling.greedy:
            return self._dummy_rng  # greedy ignores the key: skip the split
        self._rng, sub = jax.random.split(self._rng)
        return sub

    def _consume_prompt(self, h_cache, c_cache, params, src_slots, dst_slots,
                        fresh, prompts, lengths, len_b, cfg=None):
        """Shared traced body of BOTH prefill programs: gather carries
        FROM src (a prefix-cache slot for resumed prefill, the session's
        own slot otherwise), consume the masked prompt tokens, and scatter
        the advanced state TO dst. The prefix slot is read-only in the
        program, so a refcounted prefix entry is never aliased by a
        session's writes. Returns the updated cache arrays plus the
        per-position backbone outputs ``ys`` — the final program's head
        reads them; the chunk program drops them (XLA dead-code-eliminates
        the head-side compute). ``cfg`` overrides the target config — the
        draft-prefill program runs this same body over the DRAFT model's
        arrays."""
        if cfg is None:
            cfg = self.cfg
        h_in = h_cache[:, src_slots, :]  # [L, B, H]
        c_in = c_cache[:, src_slots, :]
        # fresh rows start from zero state — no device-side slot
        # zeroing on acquire, the zero rides along in this program
        live = ~fresh[None, :, None]
        h_in = jnp.where(live, h_in, 0.0)
        c_in = jnp.where(live, c_in, 0.0)
        carries = [(h_in[l], c_in[l]) for l in range(cfg.num_layers)]
        mask = jnp.arange(len_b)[None, :] < lengths[:, None]  # [B, T]
        finals, ys = lm_backbone(params, prompts, cfg, carries=carries,
                                 mask=mask)
        new_h = jnp.stack([f[0] for f in finals])  # [L, B, H]
        new_c = jnp.stack([f[1] for f in finals])
        h_cache = h_cache.at[:, dst_slots, :].set(new_h.astype(jnp.float32))
        c_cache = c_cache.at[:, dst_slots, :].set(new_c.astype(jnp.float32))
        return h_cache, c_cache, ys

    def _get_prefill_fn(self, batch_b: int, len_b: int,
                        sampling: SamplingParams, mkey: tuple = ()):
        key = (batch_b, len_b, sampling.key(), *mkey)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        count_key = ("prefill", batch_b, len_b, sampling.key(),
                     *self._shard_suffix, *mkey)

        def prefill_fn(params, h_cache, c_cache, src_slots, dst_slots,
                       fresh, prompts, lengths, rng):
            # trace-time side effect: one bump per XLA compile of this shape
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["prefill"].inc()
            h_cache, c_cache, ys = self._consume_prompt(
                h_cache, c_cache, params, src_slots, dst_slots, fresh,
                prompts, lengths, len_b)
            # logits at each row's true last position (same head math, same
            # ldtype as lm_forward — near-tied logits must argmax alike)
            last = jnp.take_along_axis(
                ys, (lengths - 1)[:, None, None], axis=1
            )[:, 0, :]  # [B, H]
            kernel, bias = _head_kernel(params, cfg)
            logits = (
                jnp.dot(last.astype(kernel.dtype), kernel,
                        preferred_element_type=cfg.ldtype)
                + bias.astype(cfg.ldtype)
            )
            token = sample_logits(
                rng, logits, temperature=sampling.temperature,
                top_k=sampling.top_k, top_p=sampling.top_p,
                greedy=sampling.greedy,
            )
            return h_cache, c_cache, token

        fn = jax.jit(prefill_fn)
        self._prefill_fns[key] = fn
        return fn

    def _get_prefill_chunk_fn(self, batch_b: int, len_b: int,
                              mkey: tuple = ()):
        """An intermediate prefill chunk: consume up to ``len_b`` prompt
        tokens from a gathered state and scatter the advanced state — no
        head, no sampling (the final chunk's program does those), so one
        compile per ("prefill_chunk", batch-bucket, length-bucket) covers
        EVERY sampling config."""
        key = (batch_b, len_b, *mkey)
        fn = self._prefill_chunk_fns.get(key)
        if fn is not None:
            return fn
        count_key = ("prefill_chunk", batch_b, len_b, *self._shard_suffix,
                     *mkey)

        def chunk_fn(params, h_cache, c_cache, src_slots, dst_slots, fresh,
                     prompts, lengths):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["prefill_chunk"].inc()
            h_cache, c_cache, _ = self._consume_prompt(
                h_cache, c_cache, params, src_slots, dst_slots, fresh,
                prompts, lengths, len_b)
            return h_cache, c_cache

        fn = jax.jit(chunk_fn)
        self._prefill_chunk_fns[key] = fn
        return fn

    def _get_decode_fn(self, batch_b: int, sampling: SamplingParams,
                       mkey: tuple = ()):
        key = (batch_b, sampling.key(), *mkey)
        fn = self._decode_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        count_key = ("decode", batch_b, sampling.key(), *self._shard_suffix,
                     *mkey)

        def decode_fn(params, fused, h_cache, c_cache, slots, tokens, rng):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["decode"].inc()
            h_in = h_cache[:, slots, :]
            c_in = c_cache[:, slots, :]
            carries = [(h_in[l], c_in[l]) for l in range(cfg.num_layers)]
            logits, new_carries = decode_one(params, fused, cfg, carries,
                                             tokens)
            nxt = sample_logits(
                rng, logits, temperature=sampling.temperature,
                top_k=sampling.top_k, top_p=sampling.top_p,
                greedy=sampling.greedy,
            )
            new_h = jnp.stack([nc[0] for nc in new_carries])
            new_c = jnp.stack([nc[1] for nc in new_carries])
            h_cache = h_cache.at[:, slots, :].set(new_h.astype(jnp.float32))
            c_cache = c_cache.at[:, slots, :].set(new_c.astype(jnp.float32))
            return h_cache, c_cache, nxt

        fn = jax.jit(decode_fn)
        self._decode_fns[key] = fn
        return fn

    def _get_decode_window_fn(self, batch_b: int, window: int,
                              sampling: SamplingParams, mkey: tuple = ()):
        key = (batch_b, window, sampling.key(), *mkey)
        fn = self._decode_window_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        count_key = ("decode_window", batch_b, window, sampling.key(),
                     *self._shard_suffix, *mkey)

        def window_fn(params, fused, h_cache, c_cache, slots, tokens,
                      alive, remaining, eos_ids, rng):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["decode_window"].inc()
            h_in = h_cache[:, slots, :]
            c_in = c_cache[:, slots, :]
            carries = [(h_in[l], c_in[l]) for l in range(cfg.num_layers)]

            def step(carry, rng_step):
                carries, token, alive, remaining = carry
                logits, new_carries = decode_one(params, fused, cfg,
                                                 carries, token)
                nxt = sample_logits(
                    rng_step, logits, temperature=sampling.temperature,
                    top_k=sampling.top_k, top_p=sampling.top_p,
                    greedy=sampling.greedy,
                )
                # rows alive at step entry emit this step's token and
                # commit its carry update (exactly the K=1 semantics:
                # the EOS-emitting step still writes its carries, the
                # steps after it never run)
                emit = alive
                out_tok = jnp.where(emit, nxt, PAD_TOKEN).astype(jnp.int32)
                new_remaining = remaining - emit.astype(remaining.dtype)
                hit_eos = emit & (eos_ids >= 0) & (nxt == eos_ids)
                new_alive = emit & ~hit_eos & (new_remaining > 0)
                frozen = [
                    (jnp.where(emit[:, None], hn, ho),
                     jnp.where(emit[:, None], cn, co))
                    for (ho, co), (hn, cn) in zip(carries, new_carries)
                ]
                # dead rows feed token 0 onward — their carries are frozen
                # and their outputs PAD, so the value never matters, but a
                # PAD_TOKEN (-1) embedding lookup must not happen
                next_tok = jnp.where(new_alive, nxt, 0).astype(jnp.int32)
                return (frozen, next_tok, new_alive, new_remaining), out_tok

            rngs = jax.random.split(rng, window)
            (carries, next_tok, alive_out, rem_out), toks = lax.scan(
                step, (carries, tokens, alive, remaining), rngs
            )
            new_h = jnp.stack([nc[0] for nc in carries])
            new_c = jnp.stack([nc[1] for nc in carries])
            h_cache = h_cache.at[:, slots, :].set(new_h.astype(jnp.float32))
            c_cache = c_cache.at[:, slots, :].set(new_c.astype(jnp.float32))
            toks = jnp.moveaxis(toks, 0, 1)  # [K, B] → [B, K]
            return h_cache, c_cache, toks, next_tok, alive_out, rem_out

        fn = jax.jit(window_fn)
        self._decode_window_fns[key] = fn
        return fn

    def _get_decode_window_pallas_fn(self, batch_b: int, window: int,
                                     sampling: SamplingParams,
                                     mkey: tuple = ()):
        """The fused Pallas decode window (ops/pallas_decode.py): same
        host-facing signature and handle shapes as the scan window fn,
        so `decode_window`/`decode_window_next` can dispatch either per
        compile key and the batcher's pipeline never knows which kernel
        produced a `DecodeWindow`. Compile-key family
        ``("decode_window_pallas", bucket, K, sampling)`` — covered by
        `warmup` through the same `decode_window` calls."""
        key = (batch_b, window, sampling.key(), *mkey)
        fn = self._decode_window_pallas_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        count_key = ("decode_window_pallas", batch_b, window,
                     sampling.key(), *self._shard_suffix, *mkey)
        interpret = self._pallas_interpret

        def window_fn(params, fused, h_cache, c_cache, slots, tokens,
                      alive, remaining, eos_ids, rng):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["decode_window_pallas"].inc()
            h_in = h_cache[:, slots, :]
            c_in = c_cache[:, slots, :]
            noise = None
            if not sampling.greedy:
                # the scan window's EXACT rng discipline: one split per
                # step, categorical == Gumbel-argmax — drawing the
                # noise here (traced, outside the kernel) keeps the
                # sampled tokens bit-identical to sample_logits
                rngs = jax.random.split(rng, window)
                noise = jnp.stack([
                    jax.random.gumbel(r, (batch_b, cfg.vocab_size),
                                      jnp.float32)
                    for r in rngs
                ])
            h_out, c_out, toks, next_tok, alive_out, rem_out = (
                pallas_decode.decode_window_call(
                    params, fused, cfg, h_in, c_in, tokens, alive,
                    remaining, eos_ids, noise, window=window,
                    temperature=sampling.temperature,
                    greedy=sampling.greedy, interpret=interpret))
            h_cache = h_cache.at[:, slots, :].set(h_out)
            c_cache = c_cache.at[:, slots, :].set(c_out)
            return h_cache, c_cache, toks, next_tok, alive_out, rem_out

        fn = jax.jit(window_fn)
        self._decode_window_pallas_fns[key] = fn
        return fn

    def _get_draft_prefill_fn(self, batch_b: int, len_b: int):
        """The draft model's prompt-consumption program: same masked
        backbone body as ``prefill_chunk`` but over the DRAFT params and
        the draft h/c arrays — no head, no sampling (the draft only
        proposes during decode). One compile per ``("draft_prefill",
        batch-bucket, length-bucket)``; the batcher mirrors every target
        prefill dispatch (chunk and final alike) with one of these, so
        the length lattice is exactly the target's."""
        key = (batch_b, len_b)
        fn = self._draft_prefill_fns.get(key)
        if fn is not None:
            return fn
        with self._lock:  # reentrant: the dispatch path already holds it
            dcfg = self.draft["cfg"]
        count_key = ("draft_prefill", batch_b, len_b)

        def draft_fn(dparams, dh, dc, src_slots, dst_slots, fresh,
                     prompts, lengths):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["draft_prefill"].inc()
            dh, dc, _ = self._consume_prompt(
                dh, dc, dparams, src_slots, dst_slots, fresh,
                prompts, lengths, len_b, cfg=dcfg)
            return dh, dc

        fn = jax.jit(draft_fn)
        self._draft_prefill_fns[key] = fn
        return fn

    def _get_spec_window_fn(self, batch_b: int, k_draft: int):
        """The speculative verify window (scan form), greedy-only. ONE
        program does both phases:

        1. **Propose** — the draft decodes ``k_draft`` greedy tokens from
           its slot state (a plain K-step scan; its propose-time carries
           are DISCARDED).
        2. **Verify** — ``W = k_draft + 1`` joint steps. Step ``i`` feeds
           the target the (i-1)-th proposal (step 0 feeds the last
           committed token) and takes the target's argmax ``t`` as the
           emitted token; the row keeps emitting only while the NEXT
           proposal agrees with ``t`` (sentinel -2 at the last step never
           agrees). The step that detects the disagreement still emits
           its own ``t`` — that is the correction token — so every spec
           step with a live row emits >= 1 token and the emitted
           sequence is EXACTLY the plain greedy sequence (the target
           carries latch on the same ``emit`` mask as the plain window,
           so after m emissions the committed state consumed exactly the
           plain window's inputs). The draft runs alongside
           teacher-forced on the same inputs with the same latch, which
           IS its state commit — rejected proposals beyond the accepted
           prefix roll back for free because neither model's carry ever
           latched past the last emission (the O(1)-rollback property).

        A draft disagreement ends the WINDOW, not the session: the
        returned ``alive`` handle is the session latch (EOS/budget only),
        so the batcher's liveness authority keeps its plain-window
        meaning."""
        key = (batch_b, k_draft)
        fn = self._spec_window_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        with self._lock:  # reentrant: the dispatch path already holds it
            dcfg = self.draft["cfg"]
        count_key = ("spec_window", batch_b, k_draft)

        def spec_fn(params, fused, dparams, dfused, h_cache, c_cache,
                    dh_cache, dc_cache, slots, tokens, alive, remaining,
                    eos_ids):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["spec_window"].inc()
            h_in = h_cache[:, slots, :]
            c_in = c_cache[:, slots, :]
            carries = [(h_in[l], c_in[l]) for l in range(cfg.num_layers)]
            dh_in = dh_cache[:, slots, :]
            dc_in = dc_cache[:, slots, :]
            dcarries = [(dh_in[l], dc_in[l])
                        for l in range(dcfg.num_layers)]

            def propose(carry, _):
                dcar, tok = carry
                logits, ndc = decode_one(dparams, dfused, dcfg, dcar, tok)
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                return (ndc, nxt), nxt

            (_, _), props = lax.scan(propose, (dcarries, tokens), None,
                                     length=k_draft)  # [K, B]
            # verify inputs: step 0 re-feeds the last committed token,
            # steps 1..K feed the proposals; the "next proposal" stream
            # ends in a sentinel no argmax can equal, so the last step
            # always closes the window
            inputs = jnp.concatenate([tokens[None, :], props], axis=0)
            next_prop = jnp.concatenate(
                [props, jnp.full((1, batch_b), -2, jnp.int32)], axis=0)

            def verify(carry, xs):
                (tcar, dcar, alive_w, sess_alive, rem, final_tok) = carry
                inp, nprop = xs
                logits, ntc = decode_one(params, fused, cfg, tcar, inp)
                _, ndc = decode_one(dparams, dfused, dcfg, dcar, inp)
                t = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                emit = alive_w
                out_tok = jnp.where(emit, t, PAD_TOKEN).astype(jnp.int32)
                new_rem = rem - emit.astype(rem.dtype)
                hit_eos = emit & (eos_ids >= 0) & (t == eos_ids)
                live_on = ~hit_eos & (new_rem > 0)
                # the session latch (plain-window rule) and the window
                # latch (additionally needs the next proposal to agree)
                # MUST be separate: a mismatch stops emission, not the
                # conversation
                new_sess = jnp.where(emit, live_on, sess_alive)
                new_alive_w = emit & live_on & (nprop == t)
                t_frozen = [
                    (jnp.where(emit[:, None], hn, ho),
                     jnp.where(emit[:, None], cn, co))
                    for (ho, co), (hn, cn) in zip(tcar, ntc)
                ]
                d_frozen = [
                    (jnp.where(emit[:, None], hn, ho),
                     jnp.where(emit[:, None], cn, co))
                    for (ho, co), (hn, cn) in zip(dcar, ndc)
                ]
                new_final = jnp.where(emit, t, final_tok).astype(jnp.int32)
                return (t_frozen, d_frozen, new_alive_w, new_sess,
                        new_rem, new_final), out_tok

            init = (carries, dcarries, alive, alive, remaining, tokens)
            (tcar, dcar, _aw, sess_alive, rem_out, final_tok), toks = (
                lax.scan(verify, init, (inputs, next_prop)))
            # next window's input is the LAST EMITTED token (dead rows
            # feed 0 — value never used, but PAD must not hit the
            # embedding)
            next_tok = jnp.where(sess_alive, final_tok, 0).astype(jnp.int32)
            new_h = jnp.stack([nc[0] for nc in tcar])
            new_c = jnp.stack([nc[1] for nc in tcar])
            h_cache = h_cache.at[:, slots, :].set(new_h.astype(jnp.float32))
            c_cache = c_cache.at[:, slots, :].set(new_c.astype(jnp.float32))
            dnew_h = jnp.stack([nc[0] for nc in dcar])
            dnew_c = jnp.stack([nc[1] for nc in dcar])
            dh_cache = dh_cache.at[:, slots, :].set(
                dnew_h.astype(jnp.float32))
            dc_cache = dc_cache.at[:, slots, :].set(
                dnew_c.astype(jnp.float32))
            toks = jnp.moveaxis(toks, 0, 1)  # [W, B] → [B, W]
            return (h_cache, c_cache, dh_cache, dc_cache, toks, next_tok,
                    sess_alive, rem_out)

        fn = jax.jit(spec_fn)
        self._spec_window_fns[key] = fn
        return fn

    def _get_spec_window_pallas_fn(self, batch_b: int, k_draft: int):
        """The fused Pallas spec window (ops/pallas_decode.py): identical
        host-facing contract to the scan spec fn — same handles, same
        latch algebra — with both models' weights and carries VMEM-
        resident for the whole propose+verify pass. Compile-key family
        ``("spec_window_pallas", bucket, K_draft)``."""
        key = (batch_b, k_draft)
        fn = self._spec_window_pallas_fns.get(key)
        if fn is not None:
            return fn
        cfg = self.cfg
        with self._lock:  # reentrant: the dispatch path already holds it
            dcfg = self.draft["cfg"]
        count_key = ("spec_window_pallas", batch_b, k_draft)
        interpret = self._pallas_interpret

        def spec_fn(params, fused, dparams, dfused, h_cache, c_cache,
                    dh_cache, dc_cache, slots, tokens, alive, remaining,
                    eos_ids):
            with self._counts_lock:
                self.compile_counts[count_key] += 1
            self._m_compiles["spec_window_pallas"].inc()
            h_in = h_cache[:, slots, :]
            c_in = c_cache[:, slots, :]
            dh_in = dh_cache[:, slots, :]
            dc_in = dc_cache[:, slots, :]
            (h_out, c_out, dh_out, dc_out, toks, next_tok, sess_alive,
             rem_out) = pallas_decode.spec_window_call(
                params, fused, cfg, dparams, dfused, dcfg,
                h_in, c_in, dh_in, dc_in, tokens, alive, remaining,
                eos_ids, k_draft=k_draft, interpret=interpret)
            h_cache = h_cache.at[:, slots, :].set(h_out)
            c_cache = c_cache.at[:, slots, :].set(c_out)
            dh_cache = dh_cache.at[:, slots, :].set(dh_out)
            dc_cache = dc_cache.at[:, slots, :].set(dc_out)
            return (h_cache, c_cache, dh_cache, dc_cache, toks, next_tok,
                    sess_alive, rem_out)

        fn = jax.jit(spec_fn)
        self._spec_window_pallas_fns[key] = fn
        return fn

    def _spec_pallas_ok(self, batch_b: int, k_draft: int) -> bool:
        cfg = self.cfg
        with self._lock:  # reentrant: the dispatch path already holds it
            dcfg = self.draft["cfg"]
        return pallas_decode.spec_plan_fits(
            batch_b, k_draft, cfg.num_layers, cfg.hidden_size, cfg.embed,
            cfg.vocab_size, dcfg.num_layers, dcfg.hidden_size, dcfg.embed)

    def _spec_window_fn_for(self, batch_b: int, k_draft: int):
        """Spec-window program pick, same policy as ``_window_fn_for``:
        fused Pallas when selected AND the joint (target + draft) VMEM
        plan fits, scan otherwise — fallbacks counted in the same
        ``decode_window_scan_fallbacks`` (a silently-switched kernel
        would fake the measured speedup)."""
        if self.decode_kernel == "pallas":
            if self._spec_pallas_ok(batch_b, k_draft):
                return self._get_spec_window_pallas_fn(batch_b, k_draft)
            with self._counts_lock:
                self.decode_window_scan_fallbacks += 1
        return self._get_spec_window_fn(batch_b, k_draft)

    def _pallas_window_ok(self, batch_b: int, window: int,
                          sampling: SamplingParams) -> bool:
        cfg = self.cfg
        if self.mesh_shards > 1:
            # the fused kernel is a single-device program: on a sharded
            # engine every pallas pick falls back to the scan window —
            # counted per dispatch (in _window_fn_for), announced once
            # at boot (__init__'s log line)
            return False
        return (pallas_decode.sampling_supported(
                    sampling.temperature, sampling.top_k, sampling.top_p,
                    sampling.greedy)
                and pallas_decode.plan_fits(
                    batch_b, window, cfg.num_layers, cfg.hidden_size,
                    cfg.embed, cfg.vocab_size,
                    sampled=not sampling.greedy))

    def _window_fn_for(self, batch_b: int, window: int,
                       sampling: SamplingParams, mkey: tuple = ()):
        """Pick the window program for this compile key: the fused
        Pallas kernel when selected AND it covers this (shape, sampling)
        — otherwise the scan window, with the fallback counted (a
        silently-switched kernel would fake the measured speedup)."""
        if self.decode_kernel == "pallas":
            if self._pallas_window_ok(batch_b, window, sampling):
                return self._get_decode_window_pallas_fn(
                    batch_b, window, sampling, mkey)
            with self._counts_lock:
                self.decode_window_scan_fallbacks += 1
        return self._get_decode_window_fn(batch_b, window, sampling, mkey)

    # ---- host-facing steps --------------------------------------------

    @staticmethod
    def _norm_prefill_items(items):
        """Normalise prefill items to ``(dst_slot, src_slot, fresh,
        prompt)`` quads. The legacy triple ``(slot, fresh, prompt)`` means
        src == dst (prefill in place); a quad names a separate gather
        source — a prefix-cache slot for resumed prefill."""
        out = []
        for it in items:
            if len(it) == 3:
                slot, fresh, prompt = it
                out.append((slot, slot, fresh, prompt))
            else:
                out.append(tuple(it))
        return out

    def _pack_prefill(self, items):
        """Pad normalised items to (batch, length) buckets; returns the
        padded arrays, on the device, + (n, batch_b, len_b). Final and intermediate
        chunk programs share ONE length-bucket lattice (prefill_buckets) —
        Batcher.warmup's replay assumes this."""
        with span("engine:pack"):
            n = len(items)
            lengths = [int(np.asarray(p).size) for _, _, _, p in items]
            for t in lengths:
                if t < 1:
                    raise ValueError("empty prompt")
            batch_b = _bucket_for(n, self.batch_buckets, "prefill batch")
            len_b = _bucket_for(max(lengths), self.prefill_buckets,
                                "prompt length")
            scratch = self.cache.scratch_slot
            src = np.full((batch_b,), scratch, np.int32)
            dst = np.full((batch_b,), scratch, np.int32)
            fresh = np.ones((batch_b,), bool)
            prompts = np.zeros((batch_b, len_b), np.int32)
            lens = np.ones((batch_b,), np.int32)
            for i, (d, s, is_fresh, prompt) in enumerate(items):
                p = np.asarray(prompt, np.int32).reshape(-1)
                dst[i] = d
                src[i] = s
                fresh[i] = bool(is_fresh)
                prompts[i, : p.size] = p
                lens[i] = p.size
            arrays = [jnp.asarray(a)
                      for a in (src, dst, fresh, prompts, lens)]
            return (*arrays, n, batch_b, len_b)

    def prefill(self, items, sampling: SamplingParams = GREEDY, *,
                model: str | None = None) -> tuple[np.ndarray, None]:
        """Run one bucketed prefill batch (the FINAL — or only — chunk of
        each row's prompt: ends with the head + sampler).

        ``items``: ``(slot, fresh, prompt)`` triples or ``(dst_slot,
        src_slot, fresh, prompt)`` quads (see ``_norm_prefill_items``) with
        ``prompt`` a 1-D int array (1 <= len <= max_prompt_len). Rows are
        padded up to the batch bucket (dead rows target the scratch slot)
        and prompts are right-padded to the length bucket (carry-freeze
        mask). Returns ``(tokens, logits)``: the first sampled token per
        item, ``[len(items)]`` int32, and None (the decoder family hands
        each token's logits back there).
        """
        if len(items) == 0:
            return np.zeros((0,), np.int32), None
        self._admit_sampling(sampling)
        src, dst, fresh, prompts, lens, n, batch_b, len_b = (
            self._pack_prefill(self._norm_prefill_items(items)))
        with self._lock:
            _, params, _, mkey = self._resolve_model(model)
            fn = self._get_prefill_fn(batch_b, len_b, sampling, mkey)
            rng = self._next_rng(sampling)
            with span("engine:launch", program="prefill_fn",
                      batch_bucket=batch_b, len_bucket=len_b):
                h, c, tok = fn(params, self.cache.h, self.cache.c,
                               src, dst, fresh, prompts, lens, rng)
            self.cache.swap(h, c)
        with span("engine:fetch", program="prefill_fn", rows=n, k=1):
            return np.asarray(tok)[:n], None

    def prefill_chunk(self, items, *, model: str | None = None) -> None:
        """Dispatch one INTERMEDIATE prefill chunk batch: advance each
        row's state over its chunk tokens and scatter it — no head, no
        sampling, nothing returned (async dispatch; the final chunk via
        :meth:`prefill` emits the first token). ``items`` as in
        :meth:`prefill`."""
        if len(items) == 0:
            return
        src, dst, fresh, prompts, lens, _, batch_b, len_b = (
            self._pack_prefill(self._norm_prefill_items(items)))
        with self._lock:
            _, params, _, mkey = self._resolve_model(model)
            fn = self._get_prefill_chunk_fn(batch_b, len_b, mkey)
            with span("engine:launch", program="chunk_fn",
                      batch_bucket=batch_b, len_bucket=len_b):
                h, c = fn(params, self.cache.h, self.cache.c,
                          src, dst, fresh, prompts, lens)
            self.cache.swap(h, c)

    def draft_prefill(self, items) -> None:
        """Advance the DRAFT model's slot state over prompt fragments —
        the batcher mirrors every target prefill dispatch (chunk and
        final) with one of these so the draft's h/c track the session's
        consumed context. ``items`` are ``(slot, fresh, fragment)``
        triples; ``fresh`` starts the draft from zero (a session's first
        fragment — including prefix-resumed rows, which the draft cannot
        resume: it has no prefix entries, so it rebuilds from zero at
        the offset, losslessly trading acceptance rate). Async dispatch,
        nothing returned."""
        if self.draft is None:  # graftlint: disable=cross-thread-state
            raise ValueError("draft_prefill needs an attached draft "
                             "(attach_draft)")
        if len(items) == 0:
            return
        src, dst, fresh, prompts, lens, _, batch_b, len_b = (
            self._pack_prefill(self._norm_prefill_items(items)))
        with self._lock:
            fn = self._get_draft_prefill_fn(batch_b, len_b)
            with span("engine:launch", program="draft_fn",
                      batch_bucket=batch_b, len_bucket=len_b):
                dh, dc = fn(self.draft["params"], self._draft_h,
                            self._draft_c, src, dst, fresh, prompts, lens)
            self._draft_h, self._draft_c = dh, dc

    def decode(self, slots, tokens, sampling: SamplingParams = GREEDY, *,
               model: str | None = None) -> tuple[np.ndarray, None]:
        """Advance each session one token: gather carries by ``slots`` [B],
        feed ``tokens`` [B], return ``(tokens, logits)``: the next token
        per row ``[B]`` int32, and None (as `prefill`). Pads to the batch
        bucket (dead rows at the scratch slot)."""
        n = len(slots)
        if n == 0:
            return np.zeros((0,), np.int32), None
        # chaos drills: an armed serve_error fault raises InjectedFault out
        # of the Nth decode call — the batcher must fail ONLY that chunk's
        # requests and keep serving (tests/test_serve_health.py). Warmup's
        # dummy decodes neither count nor fire: the drill targets traffic,
        # and an InjectedFault inside warmup() would kill the whole server
        # at startup instead of one mid-traffic chunk.
        if not self._warming:
            _faults.serve_decode_hook()
        self._admit_sampling(sampling)
        batch_b = _bucket_for(n, self.batch_buckets, "decode batch")
        with span("engine:pack"):
            slots_p = np.full((batch_b,), self.cache.scratch_slot, np.int32)
            slots_p[:n] = np.asarray(slots, np.int32)
            tokens_p = np.zeros((batch_b,), np.int32)
            tokens_p[:n] = np.asarray(tokens, np.int32)
            slots_d, tokens_d = jnp.asarray(slots_p), jnp.asarray(tokens_p)

        with self._lock:
            _, params, fused, mkey = self._resolve_model(model)
            fn = self._get_decode_fn(batch_b, sampling, mkey)
            rng = self._next_rng(sampling)
            with span("engine:launch", program="decode_fn",
                      batch_bucket=batch_b):
                h, c, tok = fn(params, fused, self.cache.h, self.cache.c,
                               slots_d, tokens_d, rng)
            self.cache.swap(h, c)
        with span("engine:fetch", program="decode_fn", rows=n, k=1):
            return np.asarray(tok)[:n], None

    def _pack_window(self, slots, tokens, remaining, eos_ids):
        """A window's per-row host values padded to the batch bucket (dead
        rows: scratch slot, alive=False) and put on the device:
        ``(batch_b, slots, tokens, alive, remaining, eos_ids)``."""
        n = len(slots)
        with span("engine:pack"):
            batch_b = _bucket_for(n, self.batch_buckets, "decode batch")
            slots_p = np.full((batch_b,), self.cache.scratch_slot, np.int32)
            slots_p[:n] = np.asarray(slots, np.int32)
            tokens_p = np.zeros((batch_b,), np.int32)
            tokens_p[:n] = np.asarray(tokens, np.int32)
            rem_p = np.zeros((batch_b,), np.int32)
            rem_p[:n] = np.asarray(remaining, np.int32)
            eos_p = np.full((batch_b,), -1, np.int32)
            if eos_ids is not None:
                eos_p[:n] = np.asarray(eos_ids, np.int32)
            alive_p = np.zeros((batch_b,), bool)
            alive_p[:n] = rem_p[:n] > 0
            return (batch_b, *(jnp.asarray(a) for a in (
                slots_p, tokens_p, alive_p, rem_p, eos_p)))

    def decode_window(self, slots, tokens, remaining, eos_ids=None,
                      sampling: SamplingParams = GREEDY, *,
                      window: int, model: str | None = None) -> DecodeWindow:
        """Dispatch one K-token decode window and return device HANDLES
        (no sync — pair with :meth:`fetch_window`).

        ``slots``/``tokens``/``remaining`` are per-row [B] host values
        (current slot, last emitted token, tokens-of-budget left);
        ``eos_ids`` [B] uses -1 for "no eos". Rows are padded to the batch
        bucket (dead rows: scratch slot, alive=False → all-PAD output,
        frozen carries). Rows latch dead on device when they emit their
        eos or exhaust ``remaining``, so ``window`` may exceed a row's
        budget safely."""
        n = len(slots)
        if n == 0 or window < 1:
            raise ValueError(f"decode_window needs rows and window >= 1, "
                             f"got {n} rows, window {window}")
        if not self._warming:
            _faults.serve_decode_hook()
        self._admit_sampling(sampling)
        batch_b, slots_d, tokens_d, alive_d, rem_d, eos_d = (
            self._pack_window(slots, tokens, remaining, eos_ids))

        with self._lock:
            mid, params, fused, mkey = self._resolve_model(model)
            fn = self._window_fn_for(batch_b, window, sampling, mkey)
            rng = self._next_rng(sampling)
            with span("engine:launch", program="window_fn",
                      batch_bucket=batch_b):
                h, c, toks, next_tok, alive, rem = fn(
                    params, fused, self.cache.h, self.cache.c,
                    slots_d, tokens_d, alive_d, rem_d, eos_d, rng,
                )
            self.cache.swap(h, c)
        return DecodeWindow(
            tokens=toks, next_tokens=next_tok, alive=alive, remaining=rem,
            slots=slots_d, eos_ids=eos_d, batch_b=batch_b, window=window,
            n=n, sampling=sampling, t_dispatch=time.perf_counter(),
            model=mid,
        )

    def decode_window_next(self, prev: DecodeWindow, *,
                           window: int | None = None) -> DecodeWindow:
        """Dispatch the follow-up window for the SAME packed rows entirely
        from ``prev``'s device handles — callable before ``prev`` has been
        fetched (or even finished computing): this is the dispatch-ahead
        half of the async-readback pipeline. Rows ``prev`` latched dead
        stay frozen, so running ahead never corrupts a finished session's
        cached state."""
        window = prev.window if window is None else window
        if window < 1:
            raise ValueError(f"window must be >= 1, got {window}")
        if not self._warming:
            _faults.serve_decode_hook()
        with self._lock:
            _, params, fused, mkey = self._resolve_model(prev.model)
            fn = self._window_fn_for(prev.batch_b, window, prev.sampling,
                                     mkey)
            rng = self._next_rng(prev.sampling)
            with span("engine:launch", program="window_fn",
                      batch_bucket=prev.batch_b):
                h, c, toks, next_tok, alive, rem = fn(
                    params, fused, self.cache.h, self.cache.c,
                    prev.slots, prev.next_tokens, prev.alive,
                    prev.remaining, prev.eos_ids, rng,
                )
            self.cache.swap(h, c)
        return dataclasses.replace(
            prev, tokens=toks, next_tokens=next_tok, alive=alive,
            remaining=rem, window=window, t_dispatch=time.perf_counter(),
        )

    def spec_window(self, slots, tokens, remaining, eos_ids=None, *,
                    k_draft: int, model: str | None = None) -> DecodeWindow:
        """Dispatch one speculative step: the draft proposes ``k_draft``
        tokens, the target verifies them all in ONE teacher-forced pass
        of ``W = k_draft + 1`` joint steps, and the longest agreeing
        prefix plus the target's own correction token emit (1..W tokens
        per live row — see ``_get_spec_window_fn`` for the latch
        algebra). Greedy-only (speculation never changes the sampled
        distribution here because only greedy verification is
        implemented); the emitted tokens are token-identical to plain
        greedy decode by construction. Returns a :class:`DecodeWindow`
        with ``spec=True`` and ``window = k_draft + 1`` — fetch with the
        same ``fetch_window_summary``; chain with
        :meth:`spec_window_next`."""
        n = len(slots)
        if self.draft is None:  # graftlint: disable=cross-thread-state
            raise ValueError("spec_window needs an attached draft "
                             "(attach_draft)")
        if n == 0 or k_draft < 1:
            raise ValueError(f"spec_window needs rows and k_draft >= 1, "
                             f"got {n} rows, k_draft {k_draft}")
        if not self._warming:
            _faults.serve_decode_hook()
        batch_b, slots_d, tokens_d, alive_d, rem_d, eos_d = (
            self._pack_window(slots, tokens, remaining, eos_ids))

        with self._lock:
            mid, params, fused, _ = self._resolve_model(model)
            if mid != self.model_id:
                raise ValueError(
                    f"spec_window serves the DEFAULT model only (the "
                    f"draft is distilled against it); got model {mid!r}")
            fn = self._spec_window_fn_for(batch_b, k_draft)
            with span("engine:launch", program="spec_fn",
                      batch_bucket=batch_b):
                h, c, dh, dc, toks, next_tok, alive, rem = fn(
                    params, fused, self.draft["params"], self.draft["fused"],
                    self.cache.h, self.cache.c, self._draft_h, self._draft_c,
                    slots_d, tokens_d, alive_d, rem_d, eos_d,
                )
            self.cache.swap(h, c)
            self._draft_h, self._draft_c = dh, dc
        return DecodeWindow(
            tokens=toks, next_tokens=next_tok, alive=alive, remaining=rem,
            slots=slots_d, eos_ids=eos_d, batch_b=batch_b,
            window=k_draft + 1, n=n, sampling=GREEDY,
            t_dispatch=time.perf_counter(), model=mid, spec=True,
        )

    def spec_window_next(self, prev: DecodeWindow, *,
                         k_draft: int | None = None) -> DecodeWindow:
        """Dispatch the follow-up speculative step for the SAME packed
        rows from ``prev``'s device handles — the spec half of the
        dispatch-ahead pipeline (``prev.next_tokens`` is the last
        EMITTED token per row, so the successor's step 0 re-verifies
        from exactly the committed state). ``k_draft`` may differ from
        ``prev``'s (the autotuner's knob moves between windows)."""
        if not prev.spec:
            raise ValueError("spec_window_next needs a spec DecodeWindow")
        if self.draft is None:  # graftlint: disable=cross-thread-state
            raise ValueError("spec_window_next needs an attached draft")
        k = (prev.window - 1) if k_draft is None else k_draft
        if k < 1:
            raise ValueError(f"k_draft must be >= 1, got {k}")
        if not self._warming:
            _faults.serve_decode_hook()
        with self._lock:
            _, params, fused, _ = self._resolve_model(prev.model)
            fn = self._spec_window_fn_for(prev.batch_b, k)
            with span("engine:launch", program="spec_fn",
                      batch_bucket=prev.batch_b):
                h, c, dh, dc, toks, next_tok, alive, rem = fn(
                    params, fused, self.draft["params"], self.draft["fused"],
                    self.cache.h, self.cache.c, self._draft_h, self._draft_c,
                    prev.slots, prev.next_tokens, prev.alive, prev.remaining,
                    prev.eos_ids,
                )
            self.cache.swap(h, c)
            self._draft_h, self._draft_c = dh, dc
        return dataclasses.replace(
            prev, tokens=toks, next_tokens=next_tok, alive=alive,
            remaining=rem, window=k + 1, t_dispatch=time.perf_counter(),
        )

    @staticmethod
    def fetch_window(win: DecodeWindow) -> np.ndarray:
        """Block until the window's tokens are on host; returns ``[n, K]``
        int32 (padding rows stripped; ``PAD_TOKEN`` after a row's EOS or
        budget end). The ONLY sync point of the windowed decode path."""
        with _fetch_span(win):
            return np.asarray(jax.device_get(win.tokens))[: win.n]

    @staticmethod
    def fetch_window_summary(
            win: DecodeWindow) -> tuple[np.ndarray, np.ndarray, np.ndarray,
                                        None]:
        """Fetch the token block AND the per-row on-device scheduler
        summary in ONE transfer: ``(tokens [n, K], remaining [n],
        alive [n], logits)``, ``logits`` None as in `prefill`. The window
        program already latched EOS/budget per
        row on device, so the scheduler tick reads this summary instead
        of re-deriving liveness host-side per token — same single sync
        point as :meth:`fetch_window` (graftlint host-sync allow-list),
        one ``device_get`` for all three arrays."""
        with _fetch_span(win):
            toks, rem, alive = jax.device_get(
                (win.tokens, win.remaining, win.alive))
        n = win.n
        return (np.asarray(toks)[:n], np.asarray(rem)[:n],
                np.asarray(alive)[:n], None)

    def warmup(self, sampling: SamplingParams = GREEDY,
               prompt_lens: tuple[int, ...] = (1,),
               batch_sizes: tuple[int, ...] | None = None,
               windows: tuple[int, ...] = (),
               chunk_lens: tuple[int, ...] = (),
               models: tuple[str, ...] | None = None,
               spec_windows: tuple[int, ...] = ()) -> int:
        """Pre-compile the bucket lattice a workload will touch (every
        batch bucket x the length buckets covering ``prompt_lens``, both
        phases, plus a ``decode_window`` program per batch bucket x each
        K > 1 in ``windows``, plus a ``prefill_chunk`` program per batch
        bucket x the length buckets covering ``chunk_lens`` — chunked
        prefill / prefix-insert splits dispatch those mid-traffic) by
        running dummy steps against the scratch slot — so the first real
        traffic burst is never charged the compiles. Front-ends should
        call ``Batcher.warmup`` / ``ServeServer.warmup`` instead: the
        split and window lengths are scheduler policy, and only the
        batcher can derive them. Returns the number of (phase, bucket)
        programs now cached."""
        batch_sizes = tuple(batch_sizes or self.batch_buckets)
        len_buckets = sorted({
            _bucket_for(t, self.prefill_buckets, "prompt length")
            for t in prompt_lens
        })
        chunk_buckets = sorted({
            _bucket_for(t, self.prefill_buckets, "chunk length")
            for t in chunk_lens
        })
        # every RESIDENT model warms its own program namespace (extra
        # residents are separate traces — the rollout/canary path must
        # never charge the first routed request a compile)
        model_ids = (tuple(models) if models is not None
                     else tuple(self._residents))  # graftlint: disable=cross-thread-state
        scratch = self.cache.scratch_slot
        self._warming = True
        try:
            for mid in model_ids:
                for b in batch_sizes:
                    bb = _bucket_for(b, self.batch_buckets, "batch")
                    for t in len_buckets:
                        items = [(scratch, True,
                                  np.zeros((t,), np.int32))] * bb
                        self.prefill(items, sampling, model=mid)
                    for t in chunk_buckets:
                        items = [(scratch, True,
                                  np.zeros((t,), np.int32))] * bb
                        self.prefill_chunk(items, model=mid)
                    self.decode([scratch] * bb, [0] * bb, sampling,
                                model=mid)
                    # every rung compiles as a window program — INCLUDING
                    # k=1: the batcher's sync path uses the fused decode
                    # fn for K=1, but the pipelined window tail dispatches
                    # K=1 as a decode_window, and an unwarmed one would
                    # compile in the middle of serving traffic
                    for k in sorted(set(windows)):
                        win = self.decode_window(
                            [scratch] * bb, [0] * bb, [k] * bb,
                            sampling=sampling, window=k, model=mid,
                        )
                        self.fetch_window(win)
                    if (self.draft is not None  # graftlint: disable=cross-thread-state
                            and mid == self.model_id):
                        # the speculative plane's whole program lattice:
                        # a draft_prefill per length bucket the batcher
                        # can mirror (finals AND chunks — it mirrors
                        # both), and a spec_window per warmed K_draft
                        # rung, so the autotuner moving spec_k among
                        # warmed rungs never costs a mid-traffic compile
                        for t in sorted({*len_buckets, *chunk_buckets}):
                            items = [(scratch, True,
                                      np.zeros((t,), np.int32))] * bb
                            self.draft_prefill(items)
                        for k in sorted(set(spec_windows)):
                            if k < 1:
                                continue  # rung 0 = plain decode
                            win = self.spec_window(
                                [scratch] * bb, [0] * bb, [k + 1] * bb,
                                k_draft=k,
                            )
                            self.fetch_window(win)
            if self.tiers is not None:
                # the tier-fill scatter lattice is warmup-covered like
                # every other program family: a continuation burst must
                # never pay a mid-traffic compile for its batched fill
                self.tiers.warmup_fills(self.batch_buckets[-1])
            if self.prefix is not None and hasattr(self.prefix,
                                                   "adopt_remote"):
                # the fabric's remote-adopt path lands a propagated node
                # via a batch-1 write_slots scatter; warm it against the
                # scratch slot so the first mid-traffic adoption does
                # not compile (slot S is scratch — nothing reads it back)
                scratch = self.cache.scratch_slot
                zeros = np.zeros((self.cfg.num_layers, 1,
                                  self.cfg.hidden_size), np.float32)
                self.cache.write_slots(np.asarray([scratch]), zeros,
                                       zeros)
        finally:
            self._warming = False
        return (len(self._prefill_fns) + len(self._prefill_chunk_fns)
                + len(self._decode_fns) + len(self._decode_window_fns)
                + len(self._decode_window_pallas_fns)
                + len(self._draft_prefill_fns) + len(self._spec_window_fns)
                + len(self._spec_window_pallas_fns))

    # ---- session lifecycle (thin wrappers over the cache) -------------

    def detach_session(self, session_id: str) -> DetachedState:
        with self._lock:
            return self.cache.detach(session_id)

    def restore_session(self, session_id: str, state: DetachedState) -> int:
        with self._lock:
            return self.cache.restore(session_id, state)

    def has_session(self, session_id: str) -> bool:
        """Affinity probe (serve/router.py): True when the session is
        device-resident OR restorable from a tier (host RAM / disk)."""
        if session_id in self.cache:
            return True
        return self.tiers is not None and self.tiers.has(session_id)

    def num_compiles(self, phase: str | None = None) -> int:
        # snapshot under the COUNTS lock (not _lock, which is held across
        # whole device calls): a first-time compile inserts into
        # compile_counts at trace time, and iterating concurrently from a
        # stats/health handler would raise "dictionary changed size
        # during iteration" — while blocking on _lock would park the
        # handler behind an in-flight (possibly wedged) dispatch
        with self._counts_lock:
            items = list(self.compile_counts.items())
        return sum(v for k, v in items if phase is None or k[0] == phase)

    def stats(self) -> dict:
        with self._counts_lock:
            compiles = dict(self.compile_counts)
            fallbacks = self.decode_window_scan_fallbacks
        draft = self.draft  # graftlint: disable=cross-thread-state
        # every result names the device it ran on — read off the arrays
        # themselves, not off what placement was asked for
        def ids(x):
            return sorted(d.id for d in x.devices())

        # lock-free like every resident read (wholesale-replace protocol)
        params = self._residents[self.model_id]["params"]  # graftlint: disable=cross-thread-state
        dev = min(self.cache.h.devices(), key=lambda d: d.id)
        memory = dev.memory_stats() or {}  # None on the CPU
        return {
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "process_devices": jax.device_count(),
                       "cache_on": ids(self.cache.h),
                       "params_on": ids(jax.tree.leaves(params)[0]),
                       "peak_bytes_in_use": memory.get("peak_bytes_in_use")},
            "decode_kernel": self.decode_kernel,
            "mesh_shards": self.mesh_shards,
            "model_id": self.model_id,
            "models": self.resident_models(),
            "draft": None if draft is None else {
                "hidden_size": draft["cfg"].hidden_size,
                "num_layers": draft["cfg"].num_layers,
                "version": draft["version"],
            },
            "decode_window_scan_fallbacks": fallbacks,
            "cache": self.cache.stats(),
            "prefix_cache": None if self.prefix is None else self.prefix.stats(),
            "tiers": None if self.tiers is None else self.tiers.stats(),
            "compiles": {repr(k): v for k, v in compiles.items()},
            "prefill_buckets": self.prefill_buckets,
            "batch_buckets": self.batch_buckets,
        }


def build_engine(params, cfg, **kw):
    """The serve engine of ``cfg``'s family (`models.generate.family_of`):
    `ServeEngine` for an LSTM LM, `DecoderEngine` for a decoder. ``kw`` are
    the engine's own constructor arguments."""
    from ..models.generate import family_of

    if family_of(cfg) == "decoder":
        from .decoder_engine import DecoderEngine

        return DecoderEngine(params, cfg, **kw)
    return ServeEngine(params, cfg, **kw)
