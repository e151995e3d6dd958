"""Slot-based device-resident cache of per-session recurrent state.

An LSTM session's entire decode state is ``(h, c)`` per layer — fixed-size,
independent of how many tokens the session has consumed (the O(1)
autoregressive cache; contrast a transformer's O(T) KV cache). The cache
stores it as two stacked device arrays ``[L, S+1, H]`` (layers x slots x
hidden, float32 — `lstm_step` computes carries in f32, so storage is exact)
plus a host-side session table:

- sessions map to integer **slots**; the jitted engine programs
  (serve/engine.py) gather carries by slot index, run the step, and
  scatter results back — the cache arrays are threaded through jit
  functionally and replaced via :meth:`swap`;
- slot ``S`` (the last row) is a **scratch slot**: decode batches padded
  up to a bucket size point their dead rows at it, so padding writes
  never corrupt a live session;
- **LRU eviction** frees the least-recently-used unpinned slot when the
  cache is full; the batcher pins slots while their session is active in
  a batch, so eviction only ever hits idle (kept-alive) sessions;
- **detach/restore**: `detach` pulls a session's carries to host numpy
  (releasing the slot), `restore` re-admits them later — the round trip
  is exact (tests/test_serve_cache.py proves continued decode is
  token-identical to an uninterrupted run).

Window-grain accounting: with windowed decode (serve/engine.py
`decode_window`) the cache arrays advance once per WINDOW, not per token,
and under the batcher's dispatch-ahead pipeline `swap` may install a
handle whose program has not finished (or started) executing — that is
safe because every consumer (the next window, a prefill, `detach`)
receives the handle and is therefore data-ordered after it on device.
``generation`` counts swaps (device programs applied to the cache), so
``stats()`` exposes how coarse the update grain actually is:
``tokens_generated / generation`` ≈ effective window size.

Host-side bookkeeping is lock-protected; device reads/writes are plain
jnp gather/scatter ops (one compile each per batch-shape, amortised).

:class:`PagedCache` (same file) is the second KIND of state: a cache that
grows with the session (pages of latent rows, or of keys and values, for a
decoder), behind the same session table; nothing of it is evicted, window
layers' pages are returned as a session outgrows them, and admission is by
free pages (see its docstring).

:class:`PrefixCache` (same file) layers shared-prompt reuse on top: a
store of "state after token-prefix P" entries, each backed by a
state-cache slot under the reserved ``prefix/`` session namespace —
longest-match lookup, refcounted use, LRU eviction in both directions
(see its docstring).
"""

from __future__ import annotations

import json
import hashlib
import os
import threading
import time
from collections import OrderedDict, deque
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..resilience import faults as _faults
from ..train.checkpoint import CorruptCheckpointError, atomic_write, read_verified
from ..utils.tracing import span as _span


class CacheFullError(RuntimeError):
    """No free slot and every occupied slot is pinned."""


# jitted h/c slot scatter: the eager ``.at[].set()`` pair costs ~1 ms of
# dispatch overhead per call on CPU (two un-jitted ops each tracing
# through the eager path), the dominant per-continuation fill cost under
# session churn. One jitted program
# (cached per shape; fill batches are power-of-two padded so the shape
# set stays tiny) makes a warm fill dispatch sub-millisecond.
@jax.jit
def _scatter_slots(h, c, idx, hs, cs):
    return (h.at[:, idx, :].set(hs.astype(h.dtype)),
            c.at[:, idx, :].set(cs.astype(c.dtype)))


# jitted gather+scatter for pending-capture fills: rows gathered from an
# immutable captured snapshot and scattered into the live arrays as ONE
# program (the eager form paid two slice ops + two scatter ops of
# dispatch overhead per fill)
@jax.jit
def _gather_scatter_slots(h, c, src_h, src_c, src, dst):
    return (h.at[:, dst, :].set(src_h[:, src, :].astype(h.dtype)),
            c.at[:, dst, :].set(src_c[:, src, :].astype(c.dtype)))


#: session-id namespace for prefix-cache backing slots. Client-facing
#: layers (batcher Request) reject ids under it: a client naming a prefix
#: entry's session would inherit — and corrupt — the shared prefix state.
PREFIX_SID_NAMESPACE = "prefix/"

#: prefix-store stats() keys that are per-replica CONFIG (or mode
#: labels), not counters — cross-replica aggregation (loadgen
#: ``prefix_totals``, ServeServer's heartbeat fan-in) keeps replica 0's
#: value for these instead of summing. One constant shared by the
#: exact-match PrefixCache and the radix PrefixTrie so the two
#: aggregations can never drift.
PREFIX_STATS_CONFIG_KEYS = ("stride", "max_entries", "max_nodes",
                            "host_bytes", "state_bytes", "mode")


class DetachedState(NamedTuple):
    """Host-resident session state: h, c each ``[L, H]`` float32 numpy."""

    h: np.ndarray
    c: np.ndarray


class SessionTable:
    """Which session holds which slot, and which slots are pinned by active
    work: the host-side table BOTH kinds of session state keep
    (`StateCache`: a slot is a row of carries; `PagedCache`: a slot is
    a row of the page bookkeeping). One reentrant lock guards it and
    whatever a subclass keeps per slot; the subclass creates it and hands
    it in (graftlint's lock model is per class: a lock made here would be
    unknown to the classes that share ``cache._lock``). A subclass says
    what happens when no slot is free (`_no_free_slot_locked`: evict one,
    or raise `CacheFullError`)."""

    def __init__(self, num_slots: int, lock):
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        self.num_slots = num_slots
        self._lock = lock
        self._slots: OrderedDict[str, int] = OrderedDict()  # LRU: oldest first
        self._free: list[int] = list(range(num_slots))
        self._pinned: set[str] = set()
        self.evictions = 0

    @property
    def scratch_slot(self) -> int:
        """The slot of padded batch rows (index == num_slots)."""
        # lock-free on the hot dispatch path: resize() only rebinds
        # num_slots with the cache drained (no sessions, no dispatches),
        # and a plain int rebind cannot tear
        return self.num_slots  # graftlint: disable=cross-thread-state

    def lookup(self, session_id: str) -> int | None:
        """Slot for a live session (refreshes LRU recency), else None."""
        with self._lock:
            if session_id not in self._slots:
                return None
            self._slots.move_to_end(session_id)
            return self._slots[session_id]

    def acquire(self, session_id: str) -> tuple[int, bool]:
        """Return ``(slot, fresh)`` for the session, allocating if needed.

        ``fresh`` is True when the slot holds no prior state for this
        session (new allocation) — the engine's prefill zeroes the initial
        carries for fresh rows instead of trusting the slot contents, so
        acquire never needs a device-side zeroing dispatch.
        """
        with self._lock:
            if session_id in self._slots:
                self._slots.move_to_end(session_id)
                return self._slots[session_id], False
            if self._free:
                slot = self._free.pop()
            else:
                slot = self._no_free_slot_locked()
            self._slots[session_id] = slot
            return slot, True

    def _no_free_slot_locked(self) -> int:
        raise CacheFullError(
            f"all {self.num_slots} session slots hold a session")

    def release(self, session_id: str) -> int | None:
        """Drop the session (its slot returns to the free list) and return
        the slot it held. No-op (None) for unknown sessions — release after
        eviction must be safe."""
        with self._lock:
            self._pinned.discard(session_id)
            slot = self._slots.pop(session_id, None)
            if slot is not None:
                self._free.append(slot)
            return slot

    def acquire_pinned(self, session_id: str) -> tuple[int, bool]:
        """:meth:`acquire` + :meth:`pin` under ONE lock hold — with
        concurrent acquirers (the router's fill_ahead), a separate
        acquire→pin pair leaves a window where the fresh unpinned slot
        is LRU-evicted from under the caller and pin() raises. The
        batcher's admission uses this."""
        with self._lock:
            slot, fresh = self.acquire(session_id)
            self._pinned.add(session_id)
            return slot, fresh

    def pin(self, session_id: str) -> None:
        with self._lock:
            if session_id not in self._slots:
                raise KeyError(f"cannot pin unknown session {session_id!r}")
            self._pinned.add(session_id)

    def unpin(self, session_id: str) -> None:
        with self._lock:
            self._pinned.discard(session_id)

    def is_pinned(self, session_id: str) -> bool:
        """True while the session's slot is held by active work — the
        router's drain path must not detach a pinned session (its
        in-flight decode still writes the slot)."""
        with self._lock:
            return session_id in self._pinned

    def __contains__(self, session_id: str) -> bool:
        with self._lock:
            return session_id in self._slots

    def session_ids(self) -> list[str]:
        """Live session ids, LRU-oldest first (includes the ``prefix/``
        namespace — callers that only want client sessions filter it).
        The router's replica-retirement path enumerates these to migrate
        a dead replica's idle kept sessions via detach/restore."""
        with self._lock:
            return list(self._slots)

    def __len__(self) -> int:
        with self._lock:
            return len(self._slots)

    def stats(self) -> dict:
        with self._lock:
            return {
                "slots": self.num_slots,
                "live_sessions": len(self._slots),
                "pinned": len(self._pinned),
                "free": len(self._free),
                "evictions": self.evictions,
            }


class StateCache(SessionTable):
    def __init__(self, num_layers: int, num_slots: int, hidden_size: int,
                 registry=None, device=None, sharding=None):
        self._lock = threading.RLock()
        super().__init__(num_slots, self._lock)
        if device is not None and sharding is not None:
            raise ValueError("pass device OR sharding, not both")
        self.num_layers = num_layers
        self.hidden_size = hidden_size
        # remembered for resize(): a reallocated array pair must land
        # exactly where the originals did (committed device / mesh
        # sharding), or the engine's programs would recompile against a
        # different placement
        self._placement = device if device is not None else sharding
        # +1: the scratch slot for padded batch rows (index == num_slots)
        self.h = jnp.zeros((num_layers, num_slots + 1, hidden_size), jnp.float32)
        self.c = jnp.zeros((num_layers, num_slots + 1, hidden_size), jnp.float32)
        if device is not None:
            # device-per-replica serving: commit the cache arrays so every
            # program touching them (and their uncommitted host inputs)
            # runs on this replica's device
            self.h = jax.device_put(self.h, device)
            self.c = jax.device_put(self.c, device)
        elif sharding is not None:
            # mesh-per-replica serving (ServeEngine mesh_shards > 1): the
            # cache slots shard over the hidden axis like the params —
            # every gather/scatter/step program then runs sharded with
            # XLA deriving the collectives, and detach/device_get
            # assemble the full rows host-side
            self.h = jax.device_put(self.h, sharding)
            self.c = jax.device_put(self.c, sharding)
        self.generation = 0  # device programs applied via swap()
        # registry counters feed /metrics; the per-instance ints above stay
        # the source for this instance's stats() (the registry aggregates
        # across every cache in the process — Prometheus semantics)
        reg = obs.REGISTRY if registry is None else registry
        self._m_evictions = reg.counter(
            "serve_state_cache_evictions_total",
            "LRU evictions of unpinned session slots")
        self._m_swaps = reg.counter(
            "serve_state_cache_swaps_total",
            "device programs applied to the cache arrays (generation)")
        # eviction listeners: called (under the cache lock) with the
        # ``(sid, slot)`` of every LRU-evicted session — the prefix cache
        # registers here so a slot eviction INVALIDATES (or, tiered,
        # SPILLS) the dependent prefix entry instead of leaving it
        # pointing at a slot another session now owns; SessionTiers
        # registers here to capture the evicted state's device handles
        # for the async host-tier spill
        self.evict_listeners: list = []

    def _no_free_slot_locked(self) -> int:
        """Evict the least recently used unpinned session."""
        for sid in self._slots:  # oldest-recency first
            if sid not in self._pinned:
                slot = self._slots.pop(sid)
                self.evictions += 1
                self._m_evictions.inc()
                for listener in self.evict_listeners:
                    listener(sid, slot)
                return slot
        raise CacheFullError(
            f"all {self.num_slots} slots pinned by active sessions"
        )

    # ---- device state --------------------------------------------------

    def swap(self, h: jnp.ndarray, c: jnp.ndarray) -> None:
        """Install updated cache arrays (the jitted step's outputs — may
        still be computing under async dispatch; consumers are
        data-ordered through the handles). Handle installation takes the
        cache lock: the engine lock serialises dispatchers, but detach()
        reads ``h``/``c`` from client threads and must never observe the
        ``h``/``c`` pair mid-replacement."""
        with self._lock:
            self.h, self.c = h, c
            self.generation += 1
        self._m_swaps.inc()

    def read_slots(self, slots) -> tuple[jnp.ndarray, jnp.ndarray]:
        """Gather carries for ``slots`` [B] → (h, c) each ``[L, B, H]``."""
        idx = jnp.asarray(slots, jnp.int32)
        with self._lock:
            return self.h[:, idx, :], self.c[:, idx, :]

    def write_slots(self, slots, h, c) -> None:
        """Scatter (h, c) each ``[L, B, H]`` into ``slots`` [B] — one
        jitted program (see ``_scatter_slots``), so a tier fill on the
        admission path costs a cheap jit dispatch, not two eager ops."""
        idx = jnp.asarray(slots, jnp.int32)
        with self._lock:
            self.h, self.c = _scatter_slots(self.h, self.c, idx,
                                            jnp.asarray(h), jnp.asarray(c))

    def gather_scatter(self, dst_slots, src_h, src_c, src_slots) -> None:
        """Gather ``src_slots`` rows from a CAPTURED snapshot pair
        (``src_h``/``src_c`` — immutable functional snapshots, possibly
        generations old) and scatter them into ``dst_slots`` of the live
        arrays, as one jitted program (the pending-capture tier fill)."""
        src = jnp.asarray(src_slots, jnp.int32)
        dst = jnp.asarray(dst_slots, jnp.int32)
        with self._lock:
            self.h, self.c = _gather_scatter_slots(
                self.h, self.c, src_h, src_c, src, dst)

    def copy_slot(self, src: int, dst: int) -> None:
        """O(1) on-device copy of one slot's carries (src read, dst
        written) — how a prefix entry snapshots a session's state. Threads
        through the cache arrays, so it is data-ordered after any
        in-flight program that writes ``src``."""
        with self._lock:
            self.h = self.h.at[:, dst, :].set(self.h[:, src, :])
            self.c = self.c.at[:, dst, :].set(self.c[:, src, :])

    # ---- detach / restore ---------------------------------------------

    @staticmethod
    def fetch_detached(h_handle, c_handle) -> DetachedState:
        """Blocking device→host fetch of one session's sliced carries —
        the spill plane's ONE designated sync point (graftlint
        ``host-sync`` allow-list, like the batcher's ``fetch_window``).
        The handles are functional snapshots, so this may run long after
        the slot was reused and still reads the pre-eviction values."""
        return DetachedState(h=np.asarray(h_handle), c=np.asarray(c_handle))

    @staticmethod
    def fetch_detached_batch(captures) -> list[DetachedState]:
        """Batched form of :meth:`fetch_detached` for the spill worker:
        ``captures`` is a list of ``(h_array, c_array, slot)`` triples —
        FULL cache-array snapshots plus the slot to extract, or
        pre-sliced ``[L, H]`` handles with ``slot=None`` (the tiers'
        memory-pressure valve). One blocking ``device_get`` over the
        deduplicated arrays fetches everything (N spills cost one
        pipeline wait), and the per-slot extraction happens in numpy —
        ZERO per-job device ops on the fast path."""
        uniq: dict[int, object] = {}
        for h, c, slot in captures:
            uniq.setdefault(id(h), h)
            uniq.setdefault(id(c), c)
        fetched = jax.device_get(list(uniq.values()))
        by_id = dict(zip(uniq.keys(), fetched))
        out = []
        for h, c, slot in captures:
            fh, fc = by_id[id(h)], by_id[id(c)]
            if slot is None:  # pre-sliced capture: already [L, H]
                out.append(DetachedState(h=fh, c=fc))
            else:
                out.append(DetachedState(h=fh[:, slot, :].copy(),
                                         c=fc[:, slot, :].copy()))
        return out

    def detach(self, session_id: str) -> DetachedState:
        """Pull a session's carries to host and release its slot.

        The returned :class:`DetachedState` is exact (f32 both ways) —
        restoring it and continuing decode is bit-identical to never
        having detached.
        """
        with self._lock:
            if session_id not in self._slots:
                raise KeyError(f"cannot detach unknown session {session_id!r}")
            slot = self._slots[session_id]
            # slice the handles under the lock; the blocking host fetch
            # happens OUTSIDE it — holding the (scheduler-shared) lock
            # across a device drain would stall every dispatch behind
            # this client-thread call
            h_handle = self.h[:, slot, :]
            c_handle = self.c[:, slot, :]
            self.release(session_id)
        return DetachedState(h=np.asarray(h_handle), c=np.asarray(c_handle))

    def restore(self, session_id: str, state: DetachedState) -> int:
        """Re-admit a detached session; returns its (new) slot."""
        if state.h.shape != (self.num_layers, self.hidden_size):
            raise ValueError(
                f"detached state shape {state.h.shape} does not match cache "
                f"({self.num_layers}, {self.hidden_size})"
            )
        with self._lock:
            if session_id in self._slots:
                raise ValueError(f"session {session_id!r} already live")
            slot, _ = self.acquire(session_id)
            self.write_slots(
                np.asarray([slot]),
                jnp.asarray(state.h)[:, None, :],
                jnp.asarray(state.c)[:, None, :],
            )
            return slot

    def resize(self, num_slots: int) -> None:
        """Reallocate the slot arrays at a new slot count (the rollout
        controller's drained-replica resize move). Only legal while NO
        sessions are resident — live carries would not survive the
        reallocation, so the caller drains/migrates first. The new
        arrays keep the original placement (committed device or mesh
        sharding); the bucket programs themselves are slot-count
        agnostic (slots are a gather index, the array's slot axis is a
        shape), so a resize invalidates compiled programs exactly like
        any other shape change — warm up before rejoining traffic."""
        if num_slots < 1:
            raise ValueError(f"num_slots must be >= 1, got {num_slots}")
        with self._lock:
            if self._slots:
                raise RuntimeError(
                    f"cannot resize with {len(self._slots)} resident "
                    "sessions — drain and migrate them first")
            self.num_slots = num_slots
            h = jnp.zeros((self.num_layers, num_slots + 1,
                           self.hidden_size), jnp.float32)
            c = jnp.zeros((self.num_layers, num_slots + 1,
                           self.hidden_size), jnp.float32)
            if self._placement is not None:
                h = jax.device_put(h, self._placement)
                c = jax.device_put(c, self._placement)
            self.h, self.c = h, c
            self._free = list(range(num_slots))
            self._pinned.clear()
            self.generation += 1
        self._m_swaps.inc()

    def stats(self) -> dict:
        with self._lock:
            return {**super().stats(), "generation": self.generation}


class PrefixEntry:
    """One cached prefix: the exact token prefix, its backing state-cache
    session/slot, and a refcount of in-flight prefills reading it.
    ``slot`` is None while the entry is SPILLED (its backing slot was
    LRU-evicted under a tiered cache — the state lives in the host tier
    until a lookup promotes it back)."""

    __slots__ = ("key", "length", "sid", "slot", "refs")

    def __init__(self, key: bytes, length: int, sid: str, slot: int | None):
        self.key = key
        self.length = length
        self.sid = sid
        self.slot = slot
        self.refs = 0


class PrefixCache:
    """Shared-prompt prefix store over the :class:`StateCache`.

    An LSTM's state after ANY prefix is one O(1) ``(h, c)`` pair per layer,
    so exact prefix reuse is a slot copy — not a KV-cache re-plumb. Entries
    are keyed by the **exact token bytes** of the prefix (the dict hash IS
    the prefix hash; storing the bytes makes collisions impossible) and
    live at ``stride``-aligned lengths, so :meth:`lookup` probes the few
    distinct entry lengths longest-first. Each entry owns a state-cache
    slot under the reserved ``prefix/`` session namespace:

    - **refcounting**: ``lookup`` pins the backing slot and bumps ``refs``
      until the resumed prefill has been *dispatched* (`release`) — device
      data-ordering through the cache arrays makes it safe to release at
      dispatch, not completion;
    - **LRU eviction**: a full prefix cache evicts its own oldest
      zero-ref entry (releasing the backing slot); conversely a state-cache
      LRU eviction of a backing slot **invalidates** the dependent entry
      via the cache's eviction listener — an invalidated prefix is a miss,
      never a read of a slot someone else now owns;
    - a matched length is capped at ``len(prompt) - 1``: at least one real
      prompt token is always prefilled, so the first sampled token comes
      from the same head math as an uncached run (token-identical greedy
      parity, tests/test_serve_prefix.py).

    Synchronisation: shares the state cache's reentrant lock — the
    eviction listener fires under it, and a private lock here would ABBA
    with ``acquire``/``pin`` calls made from prefix methods.
    """

    def __init__(self, cache: StateCache, *, stride: int = 8,
                 max_entries: int = 16, registry=None, tiers=None):
        if stride < 1:
            raise ValueError(f"stride must be >= 1, got {stride}")
        if max_entries < 1:
            raise ValueError(f"max_entries must be >= 1, got {max_entries}")
        self.cache = cache
        self.stride = stride
        self.max_entries = max_entries
        # tiered spill/promote (SessionTiers): with tiers attached, a
        # state-cache eviction of a backing slot SPILLS the entry (state
        # survives in the host tier, slot=None) instead of invalidating
        # it — a later hit pays one host→device copy, not a re-prefill
        self.tiers: SessionTiers | None = tiers
        self._lock = cache._lock  # shared on purpose (see docstring)
        self._entries: OrderedDict[bytes, PrefixEntry] = OrderedDict()
        self._by_sid: dict[str, bytes] = {}
        # distinct entry lengths, maintained incrementally (descending
        # list + per-length entry counts) so lookup never re-sorts the
        # whole entry set under the shared lock on every admission
        self._lengths_desc: list[int] = []
        self._length_counts: dict[int, int] = {}
        self._sid_counter = 0
        self.hits = 0
        self.misses = 0
        self.inserts = 0
        self.evictions = 0     # own LRU (full prefix cache)
        self.invalidated = 0   # backing slot evicted under us, state lost
        self.spilled = 0       # backing slot evicted, state kept in a tier
        self.promoted = 0      # spilled entry restored to a device slot
        # /metrics mirror of the per-instance counters above (one registry
        # family per outcome; stats() keeps serving the instance's ints)
        reg = obs.REGISTRY if registry is None else registry
        self._m = reg.counter(
            "serve_prefix_cache_events_total",
            "prefix-cache outcomes (hit/miss/insert/evict/invalidate/"
            "spill/promote)",
            labelnames=("event",))
        self._m_hit = self._m.labels(event="hit")
        self._m_miss = self._m.labels(event="miss")
        self._m_insert = self._m.labels(event="insert")
        self._m_evict = self._m.labels(event="evict")
        self._m_invalidate = self._m.labels(event="invalidate")
        self._m_spill = self._m.labels(event="spill")
        self._m_promote = self._m.labels(event="promote")
        cache.evict_listeners.append(self._on_slot_evicted_locked)

    @staticmethod
    def _key(tokens) -> bytes:
        return np.asarray(tokens, np.int32).tobytes()

    def boundary(self, length: int) -> int:
        """Largest cacheable prefix length for a ``length``-token prompt:
        stride-aligned and <= length - 1 (>= 1 token must remain to
        prefill). 0 = prompt too short to cache."""
        k = ((length - 1) // self.stride) * self.stride
        return k if k >= self.stride else 0

    # ---- incremental distinct-length index (lookup's probe order) ------

    def _length_add_locked(self, n: int) -> None:
        count = self._length_counts.get(n, 0)
        self._length_counts[n] = count + 1
        if count == 0:
            # descending insert: bisect on the negated view keeps the
            # list sorted without a per-lookup re-sort
            lo, hi = 0, len(self._lengths_desc)
            while lo < hi:
                mid = (lo + hi) // 2
                if self._lengths_desc[mid] > n:
                    lo = mid + 1
                else:
                    hi = mid
            self._lengths_desc.insert(lo, n)

    def _length_drop_locked(self, n: int) -> None:
        count = self._length_counts.get(n, 0) - 1
        if count > 0:
            self._length_counts[n] = count
            return
        self._length_counts.pop(n, None)
        try:
            self._lengths_desc.remove(n)
        except ValueError:
            pass

    def lookup(self, prompt) -> tuple[PrefixEntry | None, int]:
        """Longest exact-prefix match for ``prompt`` with matched length
        <= len(prompt) - 1. A hit returns ``(entry, matched_len)`` with
        the entry ref-held and its slot pinned — the caller MUST
        :meth:`release` after dispatching the resumed prefill."""
        p = np.asarray(prompt, np.int32).reshape(-1)
        with self._lock:
            # the distinct-length probe order is maintained incrementally
            # on insert/evict (_length_add/drop_locked) — re-sorting the
            # entry set here would put an O(entries log entries) scan on
            # every fresh admission's hot path. list() snapshot: a probe
            # can drop an entry (_promote_locked loss) mid-iteration.
            for n in list(self._lengths_desc):
                if n > p.size - 1:
                    continue
                entry = self._entries.get(self._key(p[:n]))
                if entry is None:
                    continue
                if entry.slot is None and not self._promote_locked(entry):
                    # spilled entry whose state the tiers lost: the entry
                    # was dropped — keep probing shorter lengths
                    continue
                self._entries.move_to_end(entry.key)
                # refresh the BACKING slot's recency too — the state-cache
                # LRU must not evict the hottest prefix's slot first just
                # because pin/unpin never reorder it (reentrant RLock)
                self.cache.lookup(entry.sid)
                if entry.refs == 0:
                    self.cache.pin(entry.sid)
                entry.refs += 1
                self.hits += 1
                self._m_hit.inc()
                return entry, entry.length
            self.misses += 1
            self._m_miss.inc()
            return None, 0

    def _promote_locked(self, entry: PrefixEntry) -> bool:
        """Restore a SPILLED entry's state from the tiers into a fresh
        slot — the one host→device copy a tiered eviction costs instead
        of re-prefilling the shared prefix. Returns False (and drops the
        entry) when the tiered state is gone; False without dropping when
        no slot can be had right now (every slot pinned — transient)."""
        try:
            slot, fresh = self.cache.acquire(entry.sid)
        except CacheFullError:
            return False  # transient: entry stays spilled, miss this time
        # fill_memory, not fill: this runs with the shared cache lock
        # HELD (lookup's reentrant RLock), where fill()'s out-of-lock
        # disk read would silently re-enter the lock and stall every
        # admission behind the filesystem (graftlint io-under-lock).
        # Prefix states are host-only — the disk tier never holds them —
        # so the memory-only fill is semantically identical.
        if fresh and (self.tiers is None
                      or not self.tiers.fill_memory(entry.sid, slot)):
            self.cache.release(entry.sid)
            self._by_sid.pop(entry.sid, None)
            if self._entries.pop(entry.key, None) is not None:
                self._length_drop_locked(entry.length)
            self.invalidated += 1
            self._m_invalidate.inc()
            return False
        entry.slot = slot
        self.promoted += 1
        self._m_promote.inc()
        return True

    def release(self, entry: PrefixEntry) -> None:
        """Drop one ref; the last ref unpins the backing slot (making the
        entry LRU-evictable again). Safe after invalidation."""
        with self._lock:
            if entry.refs > 0:
                entry.refs -= 1
            if entry.refs == 0 and self._by_sid.get(entry.sid) == entry.key:
                self.cache.unpin(entry.sid)

    def insert(self, tokens, src_slot: int) -> bool:
        """Snapshot the state in ``src_slot`` (== the state after exactly
        ``tokens``) into a new prefix entry. Returns False — never raises —
        when the entry already exists, every entry is ref-held, or the
        state cache has no evictable slot left: prefix caching is an
        optimisation and must degrade, not fail requests."""
        key = self._key(tokens)
        length = int(np.asarray(tokens).size)
        with self._lock:
            if key in self._entries:
                # a dedup-hit is a hotness signal too: refresh the backing
                # slot's state-cache recency like the lookup path does
                self._entries.move_to_end(key)
                self.cache.lookup(self._entries[key].sid)
                return False
            while len(self._entries) >= self.max_entries:
                victim = next(
                    (e for e in self._entries.values() if e.refs == 0), None)
                if victim is None:
                    return False  # every entry is mid-use
                self._evict_entry_locked(victim)
            self._sid_counter += 1
            sid = f"{PREFIX_SID_NAMESPACE}{self._sid_counter}"
            try:
                slot, _ = self.cache.acquire(sid)
            except CacheFullError:
                return False
            self.cache.copy_slot(src_slot, slot)
            entry = PrefixEntry(key, length, sid, slot)
            self._entries[key] = entry
            self._by_sid[sid] = key
            self._length_add_locked(length)
            self.inserts += 1
            self._m_insert.inc()
            return True

    def _evict_entry_locked(self, entry: PrefixEntry) -> None:
        if self._entries.pop(entry.key, None) is not None:
            self._length_drop_locked(entry.length)
        self._by_sid.pop(entry.sid, None)
        self.cache.release(entry.sid)
        if self.tiers is not None:
            # drop any spilled copy too, or the tiers would hold state
            # for an entry that no longer exists. Memory tiers only:
            # this fires under the shared cache lock (insert's eviction
            # loop), and prefix states never reach the disk tier — the
            # full discard()'s file unlink would be IO under the hot
            # lock for a file that cannot exist (graftlint io-under-lock)
            self.tiers.discard_memory(entry.sid)
        self.evictions += 1
        self._m_evict.inc()

    def clear(self) -> None:
        """Evict every entry that is not mid-use (refs == 0), releasing
        its backing slot. The rollout controller calls this on a DRAINED
        replica before a slot-count resize — prefix entries are derived
        state (re-insertable from traffic), so dropping them is the
        cheap half of emptying the cache."""
        with self._lock:
            for entry in list(self._entries.values()):
                if entry.refs == 0:
                    self._evict_entry_locked(entry)

    def _on_slot_evicted_locked(self, sid: str, slot: int) -> None:
        # state-cache LRU took a backing slot. Untiered: the dependent
        # entry is now garbage — drop it so lookups miss instead of
        # reading a slot a live session owns. Tiered: the SessionTiers
        # listener captured the state's device handles, so the entry
        # survives SPILLED (slot=None) and a later hit promotes it back
        # for one host→device copy. The _locked suffix is the held-lock
        # calling contract (docs/LINT.md): eviction listeners fire under
        # the shared cache lock.
        key = self._by_sid.get(sid)
        if key is None:
            return
        entry = self._entries.get(key)
        if self.tiers is not None and entry is not None:
            entry.slot = None
            self.spilled += 1
            self._m_spill.inc()
            return
        self._by_sid.pop(sid, None)
        dropped = self._entries.pop(key, None)
        if dropped is not None:
            self._length_drop_locked(dropped.length)
        self.invalidated += 1
        self._m_invalidate.inc()

    def __len__(self) -> int:
        with self._lock:
            return len(self._entries)

    def stats(self) -> dict:
        with self._lock:
            return {
                "mode": "exact",
                "entries": len(self._entries),
                "stride": self.stride,
                "max_entries": self.max_entries,
                "hits": self.hits,
                "misses": self.misses,
                "inserts": self.inserts,
                "evictions": self.evictions,
                "invalidated": self.invalidated,
                "spilled": self.spilled,
                "promoted": self.promoted,
            }


def _pad_pow2(n: int) -> int:
    """Next power of two >= n — the fill-batch bucket lattice (a handful
    of compiled scatter shapes instead of one per distinct batch size)."""
    return 1 << max(0, n - 1).bit_length()


class _SpillJob:
    """A spill in flight: REFERENCES to the cache arrays captured (under
    the cache lock) at enqueue time plus the slot index — capturing is
    zero device ops (jax arrays are immutable functional snapshots;
    later writes to the slot create new arrays), and the actual slicing
    happens on the worker thread / at fill time, OFF the scheduler's
    admission path. ``in_queue`` tracks whether a worker queue entry
    still points here (a merged re-enqueue must not double-queue; an
    in-flight job must re-queue)."""

    __slots__ = ("h", "c", "slot", "sliced", "t0", "to_host", "to_disk",
                 "in_queue")

    def __init__(self, h, c, slot: int, t0: float, *, to_host: bool,
                 to_disk: bool, sliced: bool = False):
        self.h = h
        self.c = c
        self.slot = slot
        # sliced=True: h/c are already the [L, H] row handles (the
        # memory-pressure valve sliced at capture — see _enqueue_locked);
        # False: h/c are FULL cache-array snapshots to slice at ``slot``
        self.sliced = sliced
        self.t0 = t0
        self.to_host = to_host
        self.to_disk = to_disk
        self.in_queue = False


def session_file_path(directory: str, sid: str) -> str:
    """THE disk-tier session-file naming scheme, in one place: session
    ids are client-controlled strings, so the name is a digest
    (filesystem-safe, length-bounded) and the sid itself lives in the
    file's JSON header. Exposed module-level because the chaos drill
    and the host-kill tests probe checkpoint freshness by path — a
    private copy of the scheme would silently stop matching if it ever
    changed here."""
    digest = hashlib.sha256(sid.encode()).hexdigest()[:24]
    return os.path.join(directory, f"sess-{digest}{_DiskTier.SUFFIX}")


class _DiskTier:
    """Durable session files under one directory — the serve twin of the
    training checkpoint story (train/checkpoint.py): every file is
    written via the same fsync-before-rename ``atomic_write``, with the
    state's sha256 embedded IN the JSON header — ONE file, so
    ``os.replace`` alone decides atomically which complete payload wins
    even under concurrent same-path writers (a payload can never pair
    with another writer's stale sidecar). A file that fails its hash
    (or cannot be parsed) is QUARANTINED (renamed ``*.quarantined``,
    kept for forensics) and reported as state honestly lost — never
    served as wrong tokens.

    File name = ``sess-<sha256(sid)[:24]>.state`` (session ids are
    client-controlled strings — hashing keeps them filesystem-safe); the
    sid itself lives in the JSON header line, so a startup scan rebuilds
    the sid→file index and a restarted server can serve every session
    the previous process checkpointed.

    A private lock guards only the in-memory index; file IO runs outside
    it (and the spill worker writes files without holding the cache
    lock, so an fsync never stalls the scheduler)."""

    SUFFIX = ".state"

    def __init__(self, directory: str):
        self.directory = directory
        os.makedirs(directory, exist_ok=True)
        self._lock = threading.Lock()
        self._index: dict[str, str] = {}
        self._scan()

    def _scan(self) -> None:
        for name in sorted(os.listdir(self.directory)):
            if not name.endswith(self.SUFFIX):
                continue
            path = os.path.join(self.directory, name)
            try:
                with open(path, "rb") as f:
                    meta = json.loads(f.readline())
                sid = meta["sid"]
                if not isinstance(sid, str):
                    raise ValueError(f"bad sid {sid!r}")
            except (OSError, ValueError, KeyError, TypeError,
                    json.JSONDecodeError):
                # TypeError: header parsed as non-dict JSON — the same
                # corruption class, quarantined not crashed-on-boot
                self._quarantine(None, path)
                continue
            with self._lock:
                self._index[sid] = path

    def _path(self, sid: str) -> str:
        return session_file_path(self.directory, sid)

    def _quarantine(self, sid: str | None, path: str) -> None:
        for p in (path, path + ".sha256"):
            try:
                # no exists() pre-check: the file can vanish between the
                # stat and the rename (a peer replica quarantining the
                # same corrupt file) — FileNotFoundError lands in the
                # same best-effort OSError as every other race
                os.replace(p, p + ".quarantined")
            except OSError:
                pass  # best effort: a vanished file is already gone
        if sid is not None:
            with self._lock:
                self._index.pop(sid, None)

    def has_indexed(self, sid: str) -> bool:
        """Index-only probe — no filesystem IO, safe under hot locks
        (the eviction listener's to_disk decision; a false negative
        merely costs one redundant write)."""
        with self._lock:
            return sid in self._index

    def has(self, sid: str) -> bool:
        with self._lock:
            if sid in self._index:
                return True
        # shared-directory fallback: another replica (or a previous
        # process) may have written this session AFTER our startup scan —
        # the filename is deterministic from the sid, so one stat makes
        # peers' files visible without a rescan (the router's
        # evacuate-to-shared-disk migration depends on this)
        path = self._path(sid)
        if os.path.exists(path):
            with self._lock:
                self._index[sid] = path
            return True
        return False

    def sids(self) -> list[str]:
        with self._lock:
            return list(self._index)

    def __len__(self) -> int:
        with self._lock:
            return len(self._index)

    def put(self, sid: str, state: DetachedState) -> None:
        # chaos drills: an armed disk_write_err fault raises OSError here
        # — the same path a full/failing filesystem takes, so callers'
        # disk_error accounting (durability lost, correctness kept) is
        # exercised for real
        _faults.serve_disk_hook("write")
        body = (state.h.astype(np.float32).tobytes()
                + state.c.astype(np.float32).tobytes())
        # the sha256 lives IN the header, not a sidecar: a session file
        # is then ONE file whose os.replace alone decides, atomically,
        # which complete payload wins — concurrent same-path writers
        # (shared --session-dir retirement races) can never pair one
        # writer's payload with another's sidecar hash
        meta = {"sid": sid, "layers": int(state.h.shape[0]),
                "hidden": int(state.h.shape[1]), "dtype": "float32",
                "sha256": hashlib.sha256(body).hexdigest()}
        payload = json.dumps(meta).encode() + b"\n" + body
        path = self._path(sid)
        atomic_write(path, payload)
        # chaos drills: session_corrupt damages the COMPLETED file (the
        # bit-rot/torn-write class the embedded sha256 must catch at
        # fill time with a quarantine + honest "state lost")
        _faults.maybe_corrupt_session(path)
        with self._lock:
            self._index[sid] = path

    def get(self, sid: str, num_layers: int,
            hidden_size: int) -> DetachedState | None:
        """Read + verify one session file. None = not present; raises
        :class:`CorruptCheckpointError` AFTER quarantining the file when
        it exists but cannot be trusted."""
        with self._lock:
            path = self._index.get(sid)
        if path is None:
            # same shared-directory fallback as has(): a peer replica may
            # have written the file after our startup scan
            cand = self._path(sid)
            if not os.path.exists(cand):
                return None
            path = cand
            with self._lock:
                self._index[sid] = path
        try:
            # chaos drills: disk_read_err raises OSError inside this try
            # — the same honest-miss path a vanished/unreadable file
            # takes ("state lost", never wrong tokens)
            _faults.serve_disk_hook("read")
            data = read_verified(path)
        except CorruptCheckpointError:
            self._quarantine(sid, path)
            raise
        except OSError:
            # vanished/unreadable: a miss, not corruption — keep the file
            with self._lock:
                self._index.pop(sid, None)
            return None
        try:
            head, _, body = data.partition(b"\n")
            meta = json.loads(head)
            n = num_layers * hidden_size * 4
            if meta.get("sid") != sid or len(body) != 2 * n:
                raise ValueError(
                    f"session payload mismatch (sid {meta.get('sid')!r}, "
                    f"{len(body)} state bytes, expected {2 * n})")
            got = hashlib.sha256(body).hexdigest()
            if meta.get("sha256") != got:
                raise ValueError(
                    f"state sha256 mismatch (header "
                    f"{str(meta.get('sha256'))[:12]}…, got {got[:12]}…) — "
                    "truncated or corrupted write")
            h = np.frombuffer(body[:n], np.float32).reshape(
                num_layers, hidden_size).copy()
            c = np.frombuffer(body[n:], np.float32).reshape(
                num_layers, hidden_size).copy()
        except (ValueError, KeyError, TypeError, AttributeError,
                json.JSONDecodeError) as e:
            # TypeError/AttributeError: header parsed as non-dict JSON —
            # corruption, not a crash for the scheduler thread
            self._quarantine(sid, path)
            raise CorruptCheckpointError(f"{path}: {e}") from e
        return DetachedState(h=h, c=c)

    def discard(self, sid: str) -> None:
        with self._lock:
            path = self._index.pop(sid, None)
        if path is not None:
            for p in (path, path + ".sha256"):
                try:
                    # exists+remove is the TOCTOU the flush-vs-discard
                    # race exercises for real: just remove, a vanished
                    # file is already the desired state
                    os.remove(p)
                except OSError:
                    pass


class SessionTiers:
    """Host-RAM and disk tiers under the device :class:`StateCache`.

    Device slots stay tier 0. When the state cache LRU-evicts an idle
    session, the eviction listener (fired under the cache lock) captures
    REFERENCES to the current ``(h, c)`` cache arrays plus the slot
    index — zero device ops on the serving path; jax arrays are
    immutable functional snapshots, so the capture stays valid after the
    slot is reused — and enqueues an ASYNC spill: a background worker
    thread drains the queue in batches and performs the one designated
    device→host fetch (``StateCache.fetch_detached_batch`` — deduped
    full-snapshot ``device_get`` + numpy slot extraction, ONE pipeline
    wait per batch; graftlint ``host-sync`` covers this thread exactly
    like the batcher's scheduler loop) and stores the states in the host
    tier. Host-tier overflow cascades the oldest entry down to the disk
    tier (:class:`_DiskTier` — the PR 2 sha256/fsync checkpoint
    machinery applied to session files), or drops it honestly when no
    directory is configured.

    **Fill** is the reverse path: a continuation for a spilled session
    restores its state into a freshly acquired slot — from the pending
    spill's device handles (a device→device copy; the fetch never
    happened), the host tier (one host→device copy), or a verified disk
    read. Fills run inline under the shared cache lock (admission calls
    :meth:`fill`; the router's affinity probe calls :meth:`fill_ahead`
    before the continuation reaches the scheduler), so a session is
    either resident or honestly absent — there is no window where a
    racing eviction can hand a continuation someone else's slot.

    **Serve-session checkpointing**: :meth:`checkpoint` (called by the
    batcher when a ``keep_session`` request completes) write-behinds the
    session's request-boundary state to the disk tier. Because sessions
    are only evictable while idle, and idle state always equals the last
    request boundary, a disk file is never stale while its session is
    fillable — so a crashed-and-restarted server (supervise.py) resumes
    every checkpointed session token-identically from disk. The
    durability boundary is the last COMPLETED request whose write-behind
    flushed (``flush()``; a clean ``ServeServer.stop`` flushes).

    Synchronisation: shares the state cache's reentrant lock (the evict
    listener fires under it; a private lock would ABBA with the
    ``acquire``/``write_slots`` calls made from fill paths). The worker
    fetches and writes files OUTSIDE the lock."""

    def __init__(self, cache: StateCache, *, host_entries: int = 256,
                 directory: str | None = None, registry=None,
                 replica: int = 0):
        if host_entries < 1:
            raise ValueError(f"host_entries must be >= 1, got {host_entries}")
        self.cache = cache
        self.host_entries = host_entries
        self._lock = cache._lock  # shared on purpose (see docstring)
        self._work = threading.Condition(self._lock)
        self._pending: dict[str, _SpillJob] = {}
        self._queue: deque[str] = deque()
        self._host: OrderedDict[str, DetachedState] = OrderedDict()
        # host-overflow victims whose disk write is IN FLIGHT: they stay
        # fillable here until the write lands — without this, a
        # continuation arriving between the host-tier pop and the fsync
        # would spuriously fail "state lost"
        self._evacuating: dict[str, DetachedState] = {}
        # sids discarded WHILE a disk flush is running: the flusher
        # deletes any file it just wrote for them (a stale write landing
        # after an un-kept completion's discard must not resurrect the
        # session). Only populated during a flush; cleared after.
        self._dropped: set[str] = set()
        self._flushing = 0
        self._disk = _DiskTier(directory) if directory else None
        self._thread: threading.Thread | None = None
        self._closed = False  # close() parks the worker; enqueue revives
        self._in_flight = 0
        self.spills = {"host": 0, "disk": 0}
        self.fills = {"host": 0, "disk": 0}
        self.misses = 0
        self.corrupt = 0
        self.lost = 0  # host overflow dropped without a disk tier
        self.disk_errors = 0  # failed disk writes (state kept in RAM)
        self._registry = obs.REGISTRY if registry is None else registry
        self._bind_metrics(replica)
        cache.evict_listeners.append(self._on_slot_evicted_locked)

    def _bind_metrics(self, replica: int) -> None:
        """Resolve the labelled instruments for ``replica``. Plain
        attribute assignment on purpose (NOT under the lock): rebinding
        happens before traffic (construction / ServeServer wiring), and
        the record sites read these without holding the lock."""
        reg = self._registry
        rl = str(replica)
        fam = reg.counter(
            "serve_tier_spills_total",
            "session states spilled into a tier (host = RAM spill of an "
            "evicted slot; disk = durable session file written)",
            labelnames=("tier", "replica"))
        self._m_spill = {t: fam.labels(tier=t, replica=rl)
                         for t in ("host", "disk")}
        fam = reg.counter(
            "serve_tier_fills_total",
            "spilled session states restored into a device slot, by "
            "source tier",
            labelnames=("tier", "replica"))
        self._m_fill = {t: fam.labels(tier=t, replica=rl)
                        for t in ("host", "disk")}
        fam = reg.counter(
            "serve_tier_lost_total",
            "tier state trouble, by reason (miss = no tier holds it, "
            "corrupt = disk file quarantined, overflow = host tier full "
            "with no disk tier; disk_error = a disk write failed — state "
            "stays in RAM, durability lost, correctness kept)",
            labelnames=("reason", "replica"))
        self._m_lost = {r: fam.labels(reason=r, replica=rl)
                        for r in ("miss", "corrupt", "overflow",
                                  "disk_error")}
        self._m_spill_lat = reg.histogram(
            "serve_tier_spill_seconds",
            "eviction → spilled state stored (device fetch + optional "
            "disk write), per spill job",
            labelnames=("replica",)).labels(replica=rl)
        self._m_fill_lat = reg.histogram(
            "serve_tier_fill_seconds",
            "tier fill: probe → state written back into a device slot",
            labelnames=("replica",)).labels(replica=rl)

    def set_replica(self, replica: int) -> None:
        """Re-bind the metric children to a replica index (ServeServer
        wires this so tier metrics carry the right ``replica`` label even
        for engines built without one). Call before taking traffic."""
        self._bind_metrics(replica)

    # ---- spill capture (under the cache lock) --------------------------

    def _on_slot_evicted_locked(self, sid: str, slot: int) -> None:
        # fired by the state cache's LRU under the shared lock: capture
        # REFERENCES to the current cache arrays (zero device ops — the
        # functional snapshot means later writes to the slot create new
        # arrays) and let the worker slice + fetch them off-thread.
        # Evicted sids are idle kept sessions (active ones are pinned)
        # and prefix/ backing slots; prefix states stay host-only (their
        # entries die with the process anyway).
        # has_indexed (no filesystem stat): this fires on the scheduler's
        # admission path under the shared lock — a false negative only
        # costs one redundant disk write
        to_disk = (self._disk is not None
                   and not sid.startswith(PREFIX_SID_NAMESPACE)
                   and not self._disk.has_indexed(sid))
        self._enqueue_locked(sid, slot, to_host=True, to_disk=to_disk)

    def _enqueue_locked(self, sid: str, slot: int, *, to_host: bool,
                        to_disk: bool) -> None:
        h, c = self.cache.h, self.cache.c  # refs, not slices: zero ops
        sliced = False
        if len(self._pending) >= self.SPILL_BATCH:
            # memory-pressure valve: each full-array capture pins one
            # whole cache-array generation on device, so a backed-up
            # queue (e.g. a disk stall) must not hold O(pending x cache)
            # device memory. Under pressure, pay the two slice dispatches
            # here so the job holds only this session's [L, H] rows.
            h = h[:, slot, :]
            c = c[:, slot, :]
            sliced = True
        job = self._pending.get(sid)
        if job is not None:
            # merge: an existing job for this sid describes the same
            # request-boundary state (sessions are only spillable /
            # checkpointable while idle) — refresh the capture, OR the
            # destinations
            job.h, job.c, job.slot = h, c, slot
            job.sliced = sliced
            job.to_host = job.to_host or to_host
            job.to_disk = job.to_disk or to_disk
        else:
            job = _SpillJob(h, c, slot, time.perf_counter(),
                            to_host=to_host, to_disk=to_disk,
                            sliced=sliced)
            self._pending[sid] = job
        if not job.in_queue:
            job.in_queue = True
            self._queue.append(sid)
        # deliberately NO notify: enqueue fires on the scheduler's
        # admission path (evictions) and at every request finish
        # (checkpoints), and waking the worker per event makes it
        # contend for this very lock mid-admission. The worker POLLS
        # (short timed wait), so spills batch up and the serving path
        # pays a deque append, nothing more.
        self._ensure_worker_locked()

    def _ensure_worker_locked(self) -> None:
        self._closed = False
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self.run, name="serve-tier-spill", daemon=True)
            self._thread.start()

    def close(self) -> None:
        """Park the spill worker (ServeServer.stop calls flush() then
        this): without it, every retired serve stack would leak one
        forever-polling daemon thread pinning the engine's arrays. A
        later enqueue/flush lazily revives the worker, so restartable
        servers keep working."""
        with self._work:
            self._closed = True
            self._work.notify_all()

    def checkpoint(self, sid: str) -> bool:
        """Write-behind the session's current (request-boundary) state to
        the disk tier while it stays device-resident — the serve-session
        checkpoint a restarted server restores from. No-op without a
        disk tier or for unknown sids."""
        if self._disk is None:
            return False
        with self._lock:
            slot = self.cache.lookup(sid)
            if slot is None:
                return False
            self._enqueue_locked(sid, slot, to_host=False, to_disk=True)
            return True

    # ---- the spill worker (graftlint host-sync scheduler scope) --------

    #: max spill jobs fetched per worker batch (one blocking device_get
    #: per batch — bounds the latency any single flush() waits on)
    SPILL_BATCH = 64

    def run(self) -> None:
        """Worker loop: drain the spill queue forever, a BATCH at a time
        (one blocking device fetch per batch — N spills cost one
        pipeline wait, not N serialized ones). Daemon thread — started
        lazily on the first enqueue; ``flush()`` is the synchronisation
        point for callers that need durability."""
        while True:
            with self._work:
                while not self._queue:
                    if self._closed:
                        return  # close(): park until a revive
                    # timed wait: enqueues do NOT notify (see
                    # _enqueue_locked) — the poll is the worker's only
                    # wake-up for new work, and bounds the write-behind
                    # delay a spill can sit unfetched
                    self._work.wait(timeout=0.05)
                batch: list[tuple[str, _SpillJob]] = []
                while self._queue and len(batch) < self.SPILL_BATCH:
                    sid = self._queue.popleft()
                    job = self._pending.get(sid)
                    if job is None or not job.in_queue:
                        continue  # cancelled or superseded
                    job.in_queue = False
                    batch.append((sid, job))
                self._in_flight += len(batch)
            try:
                if batch:
                    self._spill_batch(batch)
            finally:
                # decremented HERE — after the disk writes — so flush()
                # is a real durability barrier, and decremented on EVERY
                # path (the finally covers the empty batch with -= 0
                # too, so the inc/dec pairing is unconditional — the
                # graftlint resource-pairing contract), so flush can
                # never wedge on a stuck in-flight count
                with self._work:
                    self._in_flight -= len(batch)
                    self._work.notify_all()

    def _spill_batch(self, batch: list[tuple[str, _SpillJob]]) -> None:
        # chaos drills: spill_stall delays this batch (runs on the worker
        # thread, OUTSIDE the shared lock) — the write-behind-delay drill:
        # flush() must still be a real barrier and fills must keep
        # finding the pending capture while the worker sleeps
        _faults.serve_spill_hook()
        # the ONE designated device→host fetch of the spill plane
        # (StateCache.fetch_detached_batch; graftlint host-sync
        # allow-list): full-snapshot fetch + numpy slot extraction —
        # no per-job device ops anywhere in the spill pipeline
        states = self.cache.fetch_detached_batch(
            [(job.h, job.c, None if job.sliced else job.slot)
             for _, job in batch])
        disk_writes: list[tuple[str, DetachedState]] = []
        stored: list[_SpillJob] = []
        dropped = 0
        with self._work:
            for (sid, job), state in zip(batch, states):
                cur = self._pending.get(sid)
                if cur is not job or job.in_queue:
                    continue  # superseded / re-queued while fetching
                del self._pending[sid]
                stored.append(job)
                if job.to_host:
                    self._host[sid] = state
                    self._host.move_to_end(sid)
                    self.spills["host"] += 1
                    self._m_spill["host"].inc()
                    dropped += self._cascade_overflow_locked(disk_writes)
                if job.to_disk:
                    disk_writes.append((sid, state))
        if dropped:
            self._m_lost["overflow"].inc(dropped)
        self._flush_disk_writes(disk_writes)
        # latency observed AFTER the disk writes (the histogram's help
        # promises "stored", fsync included) and only for jobs that
        # actually stored — superseded ones are not phantom spills
        end = time.perf_counter()
        for job in stored:
            self._m_spill_lat.observe(end - job.t0)

    def set_host_entries(self, n: int) -> None:
        """Resize the host-tier bound at runtime — the serve autotuner's
        capacity (autoscaler) knob. Growing is free; shrinking cascades
        overflow victims through the exact spill-time overflow path
        (disk-bound victims park in ``_evacuating`` until their write
        lands, the rest are dropped honestly and counted). The disk
        writes themselves run OUTSIDE the shared lock, like every other
        flush."""
        if n < 1:
            raise ValueError(f"host_entries must be >= 1, got {n}")
        disk_writes: list = []
        with self._lock:
            self.host_entries = int(n)
            dropped = self._cascade_overflow_locked(disk_writes)
        if dropped:
            self._m_lost["overflow"].inc(dropped)
        self._flush_disk_writes(disk_writes)

    def _cascade_overflow_locked(self, disk_writes: list) -> int:
        """Pop host-tier overflow victims. Disk-bound victims PARK in
        ``_evacuating`` (still fillable) until their write lands; the
        rest are dropped honestly. Returns the dropped count."""
        dropped = 0
        while len(self._host) > self.host_entries:
            vsid, vstate = self._host.popitem(last=False)
            if (self._disk is not None
                    and not vsid.startswith(PREFIX_SID_NAMESPACE)):
                self._evacuating[vsid] = vstate
                disk_writes.append((vsid, vstate))
            else:
                self.lost += 1
                dropped += 1
        return dropped

    def _flush_disk_writes(self, writes: list) -> None:
        """Write session files OUTSIDE the shared lock, with two honesty
        guards: a write is SKIPPED when its session no longer exists
        anywhere (discarded while queued — a stale file must not
        resurrect it), and a file written concurrently with a discard is
        deleted afterwards (``_dropped`` tombstones, alive only while a
        flush runs). A failed write keeps the state in RAM
        (``disk_error`` — durability lost, correctness kept)."""
        if not writes:
            return
        with self._lock:
            self._flushing += 1
        try:
            for sid, state in writes:
                with self._lock:
                    current = (sid in self._evacuating
                               or sid in self._pending
                               or sid in self._host or sid in self.cache)
                if not current:
                    continue  # discarded while queued: nothing to persist
                try:
                    self._write_disk(sid, state)
                except OSError as e:
                    # disk trouble loses durability, not correctness:
                    # keep the state in RAM and keep the worker alive
                    print(f"serve tiers: disk-tier write failed for "
                          f"{sid!r}: {e}", flush=True)
                    with self._lock:
                        self.disk_errors += 1
                        st = self._evacuating.pop(sid, None)
                        if st is not None:
                            self._host[sid] = st
                            self._host.move_to_end(sid)
                    self._m_lost["disk_error"].inc()
                    continue
                with self._lock:
                    self._evacuating.pop(sid, None)
                    undo = sid in self._dropped
                if undo:
                    # discard() raced the write: the file we just wrote
                    # describes a session that ended — remove it
                    self._disk.discard(sid)
        finally:
            with self._lock:
                self._flushing -= 1
                if not self._flushing:
                    self._dropped.clear()

    def _write_disk(self, sid: str, state: DetachedState) -> None:
        self._disk.put(sid, state)
        with self._lock:
            self.spills["disk"] += 1
        self._m_spill["disk"].inc()

    def flush(self, timeout: float | None = None) -> bool:
        """Block until every queued/in-flight spill has landed (True) or
        the timeout expired (False) — the durability barrier for clean
        shutdown and for tests/tools that must observe the disk tier."""
        deadline = (None if timeout is None
                    else time.monotonic() + timeout)
        with self._work:
            while self._queue or self._in_flight:
                self._ensure_worker_locked()
                if deadline is None:
                    self._work.wait(timeout=1.0)
                else:
                    left = deadline - time.monotonic()
                    if left <= 0:
                        return False
                    self._work.wait(timeout=min(left, 1.0))
            return True

    # ---- fill (promote back to the device tier) ------------------------

    @property
    def disk_dir(self) -> str | None:
        """Disk-tier directory, or None — the router dedupes its
        disk-residency stats per distinct directory."""
        return None if self._disk is None else self._disk.directory

    def has(self, sid: str) -> bool:
        """Tier residency probe (the router's affinity extension): does
        any tier hold restorable state for ``sid``?"""
        with self._lock:
            return self._has_locked(sid)

    def has_memory(self, sid: str) -> bool:
        """MEMORY-tier residency only (pending capture / host RAM /
        evacuating overflow). The router prefers this over any disk
        match: the replica holding a memory copy is the session's owner
        with the freshest request boundary, while a shared disk file may
        be an older not-yet-overwritten boundary."""
        with self._lock:
            job = self._pending.get(sid)
            return ((job is not None and (job.to_host or job.to_disk))
                    or sid in self._host or sid in self._evacuating)

    def _has_locked(self, sid: str) -> bool:
        job = self._pending.get(sid)
        if job is not None and (job.to_host or job.to_disk):
            return True
        if sid in self._host or sid in self._evacuating:
            return True
        return self._disk is not None and self._disk.has(sid)

    def resident_tier(self, sid: str) -> str | None:
        """'pending' | 'host' | 'disk' | None — observability/tests."""
        with self._lock:
            if sid in self._pending and (self._pending[sid].to_host
                                         or self._pending[sid].to_disk):
                return "pending"
            if sid in self._host or sid in self._evacuating:
                return "host"
            if self._disk is not None and self._disk.has(sid):
                return "disk"
            return None

    def _fill_memory_locked(self, sid: str, idx, t0: float) -> bool:
        """Restore from the in-memory tiers (pending capture, host RAM,
        evacuating overflow) — called with the shared lock held."""
        job = self._pending.get(sid)
        if job is not None and (job.to_host or job.to_disk):
            # device→device: gather the captured snapshot's slot row
            # (index as an ARRAY so one gather program covers every
            # slot value) and scatter into the new slot. Any pending
            # capture is the freshest copy, whatever its destination
            # flags — a to_disk-only job's file may not be written yet
            if job.sliced:  # pressure-valve capture: already [L, H]
                self.cache.write_slots(idx, job.h[:, None, :],
                                       job.c[:, None, :])
            else:
                self.cache.gather_scatter(idx, job.h, job.c, [job.slot])
            job.to_host = False  # the disk leg (if any) still runs:
            # the file stays the valid request-boundary checkpoint
            if not job.to_disk and not job.in_queue:
                del self._pending[sid]
            self._host.pop(sid, None)
            return self._count_fill_locked("host", t0)
        state = self._host.pop(sid, None)
        if state is None:
            # overflow victim mid-evacuation: still RAM-resident (its
            # disk write — which stays valid — may even land after this)
            state = self._evacuating.get(sid)
        if state is not None:
            self.cache.write_slots(idx, state.h[:, None, :],
                                   state.c[:, None, :])
            return self._count_fill_locked("host", t0)
        return False

    def fill(self, sid: str, slot: int) -> bool:
        """Restore ``sid``'s spilled state into the (already acquired —
        and PINNED, so no concurrent eviction can reuse it) ``slot``:
        pending capture (device→device — the spill fetch never ran),
        host RAM, then disk. The disk read + sha256 verify runs OUTSIDE
        the shared lock (a slow filesystem must not stall the scheduler
        or the health probes). Returns False when no tier holds usable
        state (miss, or a corrupt disk file — quarantined and counted;
        the caller fails the continuation honestly)."""
        t0 = time.perf_counter()
        idx = np.asarray([slot])
        with self._lock:
            if self._fill_memory_locked(sid, idx, t0):
                return True
            if self._disk is None:
                self.misses += 1
                self._m_lost["miss"].inc()
                return False
        # disk branch: probe + read + verify all OUTSIDE the lock (get
        # returns None for absent — no separate stat-under-lock)
        try:
            state = self._disk.get(sid, self.cache.num_layers,
                                   self.cache.hidden_size)
        except CorruptCheckpointError as e:
            print(f"serve tiers: QUARANTINED corrupt session file "
                  f"for {sid!r}: {e}", flush=True)
            with self._lock:
                self.corrupt += 1
            self._m_lost["corrupt"].inc()
            state = None
        with self._lock:
            if state is None:
                self.misses += 1
                self._m_lost["miss"].inc()
                return False
            self.cache.write_slots(idx, state.h[:, None, :],
                                   state.c[:, None, :])
            return self._count_fill_locked("disk", t0)

    def _count_fill_locked(self, tier: str, t0: float) -> bool:
        self.fills[tier] += 1
        self._m_fill[tier].inc()
        self._m_fill_lat.observe(time.perf_counter() - t0)
        return True

    def fill_batch(self, pairs) -> dict[str, bool]:
        """Batched :meth:`fill`: restore MANY sessions' spilled states
        into their (already acquired AND PINNED) slots with ONE scatter
        program per source class, instead of one gather+scatter dispatch
        per session — the admission path's per-continuation device cost
        under session churn.

        ``pairs`` is ``[(sid, slot), ...]`` with UNIQUE sids (admission
        guarantees it — one in-flight request per session). Returns
        ``{sid: filled}``. Three phases:

        1. under the shared lock: classify each sid's freshest source
           (pending capture / host RAM / evacuating overflow / disk
           candidate) and do ALL the tier-dict bookkeeping — one lock
           hold for the whole batch, no device dispatch inside it (the
           per-session ``fill`` dispatched its scatter under the lock);
        2. outside the lock: disk reads + sha256 verify (per file, as
           before — the filesystem must never stall the scheduler);
        3. one stacked host→device scatter for every host/disk state,
           and one gather+scatter per distinct pending-capture array
           pair (usually one — jobs captured from the same cache
           generation share the arrays).

        Token-identity with per-session fills is pinned by
        tests/test_serve_tiers.py."""
        pairs = list(pairs)
        if not pairs:
            return {}
        t0 = time.perf_counter()
        results = {sid: False for sid, _ in pairs}
        host_fills: list[tuple[str, int, DetachedState]] = []
        dev_fills: list[tuple[str, int, object, object, int | None]] = []
        disk_cands: list[tuple[str, int]] = []
        misses = 0
        with self._lock:
            for sid, slot in pairs:
                job = self._pending.get(sid)
                if job is not None and (job.to_host or job.to_disk):
                    # freshest copy; the disk leg (if any) still runs —
                    # the file stays the valid request-boundary
                    # checkpoint (same bookkeeping as fill())
                    dev_fills.append((sid, slot, job.h, job.c,
                                      None if job.sliced else job.slot))
                    job.to_host = False
                    if not job.to_disk and not job.in_queue:
                        del self._pending[sid]
                    self._host.pop(sid, None)
                    continue
                state = self._host.pop(sid, None)
                if state is None:
                    # overflow victim mid-evacuation: still RAM-resident
                    # (its in-flight disk write stays valid — no pop)
                    state = self._evacuating.get(sid)
                if state is not None:
                    host_fills.append((sid, slot, state))
                elif self._disk is not None:
                    disk_cands.append((sid, slot))
                else:
                    misses += 1
        # phase 2: MEMORY-sourced fills complete first — their states are
        # already in RAM / captured on device, so they must never wait
        # behind batch-mates' filesystem IO (and their fill-latency
        # samples keep fill()'s per-source semantics: host-class numbers
        # never include a disk read). One stacked scatter for the host/
        # evacuating states, PADDED to a power-of-two bucket (extra rows
        # re-write row 0's state into the scratch slot — harmless by
        # definition): without the bucket, every distinct batch size N
        # would trace a fresh XLA scatter program MID-RUN, and the
        # compile (tens of ms) lands on exactly the admission latency
        # the batching exists to remove (measured: fill p99 0.76 s
        # unbucketed vs sub-ms warm).
        if host_fills:
            idx = [slot for _, slot, _ in host_fills]
            hs = [st.h for _, _, st in host_fills]
            cs = [st.c for _, _, st in host_fills]
            n = _pad_pow2(len(host_fills))
            idx += [self.cache.scratch_slot] * (n - len(host_fills))
            hs += [hs[0]] * (n - len(host_fills))
            cs += [cs[0]] * (n - len(host_fills))
            self.cache.write_slots(np.asarray(idx), np.stack(hs, axis=1),
                                   np.stack(cs, axis=1))
        # pending captures — one gather+scatter per distinct captured
        # array pair (immutable snapshots; usually ONE — jobs captured
        # from the same cache generation share the arrays), bucket-padded
        # the same way (src padding repeats src[0]; dst padding targets
        # the scratch slot). Sliced pressure-valve captures are [L, H]
        # handles, scattered individually.
        groups: dict[tuple[int, int], list] = {}
        for ent in dev_fills:
            groups.setdefault((id(ent[2]), id(ent[3])), []).append(ent)
        for ents in groups.values():
            full = [e for e in ents if e[4] is not None]
            if full:
                dst = [e[1] for e in full]
                src = [e[4] for e in full]
                n = _pad_pow2(len(full))
                dst += [self.cache.scratch_slot] * (n - len(full))
                src += [src[0]] * (n - len(full))
                self.cache.gather_scatter(np.asarray(dst), full[0][2],
                                          full[0][3], np.asarray(src))
            for sid, slot, h, c, _ in (e for e in ents if e[4] is None):
                self.cache.write_slots(np.asarray([slot]),
                                       h[:, None, :], c[:, None, :])
        end_mem = time.perf_counter()
        # phase 3: disk reads + sha256 verify OUTSIDE the lock, then the
        # disk states' own stacked scatter — disk-class latency samples
        # cover the read+verify, memory-class ones (above) do not
        disk_states: list[tuple[str, int, DetachedState]] = []
        for sid, slot in disk_cands:
            state = None
            try:
                state = self._disk.get(sid, self.cache.num_layers,
                                       self.cache.hidden_size)
            except CorruptCheckpointError as e:
                print(f"serve tiers: QUARANTINED corrupt session file "
                      f"for {sid!r}: {e}", flush=True)
                with self._lock:
                    self.corrupt += 1
                self._m_lost["corrupt"].inc()
            if state is None:
                misses += 1
            else:
                disk_states.append((sid, slot, state))
        if disk_states:
            idx = [slot for _, slot, _ in disk_states]
            hs = [st.h for _, _, st in disk_states]
            cs = [st.c for _, _, st in disk_states]
            n = _pad_pow2(len(disk_states))
            idx += [self.cache.scratch_slot] * (n - len(disk_states))
            hs += [hs[0]] * (n - len(disk_states))
            cs += [cs[0]] * (n - len(disk_states))
            self.cache.write_slots(np.asarray(idx), np.stack(hs, axis=1),
                                   np.stack(cs, axis=1))
        end_disk = time.perf_counter()
        n_host = len(host_fills) + len(dev_fills)
        n_disk = len(disk_states)
        with self._lock:
            self.fills["host"] += n_host
            self.fills["disk"] += n_disk
            self.misses += misses
        if n_host:
            self._m_fill["host"].inc(n_host)
        if n_disk:
            self._m_fill["disk"].inc(n_disk)
        if misses:
            self._m_lost["miss"].inc(misses)
        for sid, _, *_rest in (*host_fills, *dev_fills):
            results[sid] = True
            self._m_fill_lat.observe(end_mem - t0)
        for sid, _, _ in disk_states:
            results[sid] = True
            self._m_fill_lat.observe(end_disk - t0)
        return results

    def fill_memory(self, sid: str, slot: int) -> bool:
        """Memory-tiers-only :meth:`fill` (pending capture / host RAM /
        evacuating overflow — no disk leg). Safe to call with the shared
        cache lock already held: PrefixCache._promote_locked restores
        spilled prefix entries through this under the reentrant RLock,
        where fill()'s out-of-lock disk read would stall the scheduler
        behind the filesystem. Prefix states never reach the disk tier,
        so for them this is the whole fill."""
        t0 = time.perf_counter()
        with self._lock:
            if self._fill_memory_locked(sid, np.asarray([slot]), t0):
                return True
            self.misses += 1
            self._m_lost["miss"].inc()
            return False

    def discard_memory(self, sid: str) -> None:
        """Memory-tiers-only :meth:`discard` — drops pending/host/
        evacuating copies but never touches the disk tier (no file IO,
        safe under the shared cache lock). For sids that cannot have a
        disk file (prefix/ namespace) this is the whole discard."""
        with self._lock:
            job = self._pending.get(sid)
            if job is not None:
                job.to_host = job.to_disk = False
                if not job.in_queue:
                    del self._pending[sid]
            self._host.pop(sid, None)
            self._evacuating.pop(sid, None)

    def warmup_fills(self, max_batch: int) -> None:
        """Pre-compile the fill-path scatter lattice: one
        ``_scatter_slots`` + ``_gather_scatter_slots`` program per
        power-of-two batch size up to ``max_batch`` (fill batches are
        padded onto exactly these shapes). Called from
        ``ServeEngine.warmup`` so the first real continuation burst is
        never charged a mid-traffic XLA compile — the same discipline as
        the engine's program lattice. All
        writes target the scratch slot: harmless by definition."""
        L, H = self.cache.num_layers, self.cache.hidden_size
        scratch = self.cache.scratch_slot
        n = 1
        while True:
            idx = np.full((n,), scratch)
            z = np.zeros((L, n, H), np.float32)
            self.cache.write_slots(idx, z, z)
            with self._lock:
                h, c = self.cache.h, self.cache.c
            self.cache.gather_scatter(idx, h, c, idx)
            if n >= max(1, max_batch):
                break
            n *= 2

    def fill_ahead(self, sid: str) -> bool:
        """Router fill-ahead: on an affinity-probe tier hit, promote the
        session into a device slot NOW so the continuation's admission
        finds it resident (the device copy dispatches async — by the
        time the scheduler prefills, it is data-ordered anyway).
        MEMORY tiers only: this runs under the router's global lock, so
        a disk-resident session just routes home and admission does the
        (out-of-lock) disk fill."""
        with self._lock:
            if sid in self.cache:
                return True
            if not self._has_locked(sid):
                return False
            job = self._pending.get(sid)
            in_memory = ((job is not None and (job.to_host or job.to_disk))
                         or sid in self._host or sid in self._evacuating)
            if not in_memory:
                return True  # disk-resident: admission fills on arrival
            try:
                slot, fresh = self.cache.acquire(sid)
            except CacheFullError:
                return False  # every slot pinned: admission will retry
            if not fresh:
                return True
            if self._fill_memory_locked(sid, np.asarray([slot]),
                                        time.perf_counter()):
                return True
            self.cache.release(sid)
            return False

    def discard(self, sid: str) -> None:
        """Drop every tier's copy of ``sid`` (un-kept completion /
        prefix-entry eviction: the owner is gone, a stale copy must not
        resurrect it)."""
        with self._lock:
            job = self._pending.get(sid)
            if job is not None:
                job.to_host = job.to_disk = False
                if not job.in_queue:
                    del self._pending[sid]
            self._host.pop(sid, None)
            self._evacuating.pop(sid, None)
            if self._flushing:
                # a disk write for this sid may be mid-flight: tombstone
                # it so the flusher deletes whatever it lands
                self._dropped.add(sid)
        if self._disk is not None:
            self._disk.discard(sid)

    # ---- replica retirement (router-driven) ----------------------------

    def evacuate(self) -> tuple[int, list[tuple[str, DetachedState]]]:
        """Move every tier-held session off this (retired) replica:
        pending spills are fetched synchronously, then everything is
        persisted to the SHARED disk tier when one exists (any live
        replica can fill from it) or returned for the router to adopt
        into a live replica's host tier. Returns ``(persisted_count,
        homeless_entries)``. Prefix states are dropped — their entries
        die with the replica."""
        with self._lock:
            jobs = [(sid, job) for sid, job in self._pending.items()
                    if job.to_host or job.to_disk]
            self._pending.clear()
            self._queue.clear()
            host = list(self._host.items()) + list(self._evacuating.items())
            self._host.clear()
            self._evacuating.clear()
            self._work.notify_all()
        states: dict[str, DetachedState] = {}
        if jobs:
            fetched = self.cache.fetch_detached_batch(
                [(job.h, job.c, None if job.sliced else job.slot)
                 for _, job in jobs])
            states.update(
                (sid, st) for (sid, _), st in zip(jobs, fetched))
        states.update(host)  # same boundary where both exist
        persisted = 0
        homeless: list[tuple[str, DetachedState]] = []
        for sid, state in states.items():
            if sid.startswith(PREFIX_SID_NAMESPACE):
                continue
            if self._disk is not None:
                try:
                    self._write_disk(sid, state)
                    persisted += 1
                    continue
                except OSError as e:
                    # disk trouble mid-retirement must not abort the
                    # router's requeue of the dead replica's work: the
                    # session becomes HOMELESS (adopted into a live
                    # replica's host tier) instead of crashing _retire
                    print(f"serve tiers: evacuate disk write failed for "
                          f"{sid!r}: {e}", flush=True)
                    with self._lock:
                        self.disk_errors += 1
                    self._m_lost["disk_error"].inc()
            homeless.append((sid, state))
        return persisted, homeless

    def adopt(self, sid: str, state: DetachedState) -> None:
        """Insert a migrated session's state into this replica's host
        tier (router retirement of a diskless peer)."""
        disk_writes: list[tuple[str, DetachedState]] = []
        dropped = 0
        with self._lock:
            self._host[sid] = state
            self._host.move_to_end(sid)
            self.spills["host"] += 1
            dropped += self._cascade_overflow_locked(disk_writes)
        self._m_spill["host"].inc()
        if dropped:
            self._m_lost["overflow"].inc(dropped)
        self._flush_disk_writes(disk_writes)

    # ---- views ---------------------------------------------------------

    def session_ids(self) -> list[str]:
        """Sids with restorable tier state (host + pending + disk)."""
        with self._lock:
            out = {sid for sid, j in self._pending.items()
                   if j.to_host or j.to_disk}
            out.update(self._host)
            out.update(self._evacuating)
            if self._disk is not None:
                out.update(self._disk.sids())
            return sorted(out)

    def stats(self) -> dict:
        with self._lock:
            return {
                "host_entries_max": self.host_entries,
                "entries": {
                    "pending": sum(1 for j in self._pending.values()
                                   if j.to_host or j.to_disk),
                    # evacuating overflow victims are still RAM-resident
                    "host": len(self._host) + len(self._evacuating),
                    "disk": 0 if self._disk is None else len(self._disk),
                },
                "disk_dir": None if self._disk is None
                else self._disk.directory,
                "spills": dict(self.spills),
                "fills": dict(self.fills),
                "misses": self.misses,
                "corrupt": self.corrupt,
                "lost": self.lost,
                "disk_errors": self.disk_errors,
            }


class PagedCache(SessionTable):
    """The second kind of session state: a cache that GROWS with the
    session. A decoder's state is one row per token and layer
    (`ops/paged_attention.py`: a latent row, or keys and values per head),
    kept in pages of ``page`` rows; the device holds one pool ``[num_pages +
    1, page, width]`` per layer (the last page is scratch: dead rows write
    there) and the host holds the bookkeeping: which session has which
    slot, which pages a slot owns in order, how many tokens it holds.

    Pages come in KINDS (`models.decoder.PageKind`; one for a latent cache,
    two for a K/V cache whose layers mix full and window attention): one
    page id of a kind indexes the same page of every pool of that kind's
    layers, each kind has its own free list, and a session owns pages of
    every kind side by side. A kind with a ``window`` keeps only the pages
    that hold a key the session's next query can see: as the session grows,
    pages wholly behind ``length - (window - 1)`` go back to the free list
    WHILE IT RUNS (`ensure`, on the scheduler's thread) and when it goes
    idle (`unpin`), so a session of any length holds a bounded number of
    them; its page list then starts at page index `held`'s ``base``. A
    returned page is never read again: the item lists name only pages a
    session holds.

    The session table IS `StateCache`'s (`SessionTable`: ``acquire_pinned``,
    ``unpin``, ``release``, ``in``, ``session_ids``, ``stats``), so the
    batcher, the router and the server drive it unchanged. What differs:

    - nothing is evicted: a session's pages are its conversation, and
      there is no spill tier for them yet (ROADMAP: preemption by
      snapshot). A kept session holds its pages until it is released;
      when slots or pages run out, `acquire`/`commit` raise
      `CacheFullError` and admission waits or fails loudly;
    - admission is by PAGES, of every kind: `commit` promises a session the
      pages its request can grow into (prompt + new tokens; of a window
      kind at most `window_cap` at a time) before any are taken, `ensure`
      takes them as the session grows, `unpin`/`release` return what was
      promised and not used; ``free - promised`` of a kind is what a new
      request may count on (`can_commit`);
    - the pools are updated IN PLACE: every program that writes them takes
      them donated and hands them back (`swap`); a copy of a multi-GiB
      pool per dispatch would not fit beside the weights.

    Spans ``cache:pages_alloc`` / ``cache:pages_free`` [``pages``, ``kind``]
    and ``cache:window_pages_recycle`` [``pages``] (on the caller's thread:
    the scheduler's) carry the number of pages moved. ``grow_step`` is the
    most tokens one `ensure` may take a session beyond the length the host
    knows (a prefill chunk; a decode window run ahead is shorter)."""

    def __init__(self, num_slots: int, page: int, kinds, dtype=jnp.bfloat16,
                 device=None, grow_step: int = 512):
        self._lock = threading.RLock()
        super().__init__(num_slots, self._lock)
        self.kinds = tuple(kinds)
        if page < 1 or any(k.num_pages < 1 for k in self.kinds):
            raise ValueError("page and every kind's num_pages must be >= 1")
        if self.kinds[0].window is not None:
            raise ValueError("the first kind of page keeps every token")
        self.page, self.grow_step = page, int(grow_step)
        self._has_window = any(k.window is not None for k in self.kinds)
        kind_of = {i: k for k in self.kinds for i in k.layers}
        self.num_layers = len(kind_of)

        def pool(k):
            return jax.jit(lambda: jnp.zeros((k.num_pages + 1, page, k.width),
                                             dtype))()

        self.pools = tuple(pool(kind_of[i]) for i in range(self.num_layers))
        if device is not None:
            self.pools = jax.device_put(self.pools, device)
        n = len(self.kinds)
        self._free_pages = [list(range(k.num_pages - 1, -1, -1))
                            for k in self.kinds]
        self._pages = [[[] for _ in range(num_slots + 1)] for _ in range(n)]
        #: page index (of the session) of a slot's first page, per kind
        self._base = np.zeros((n, num_slots + 1), np.int64)
        #: pages a slot may hold while its admitted request runs, and what
        #: of that it does not hold yet: the promise
        self._target = np.zeros((n, num_slots + 1), np.int64)
        self._promised = np.zeros((n, num_slots + 1), np.int64)
        #: tokens each slot holds (the scratch slot stays 0), and the most
        #: its admitted request may bring it to (`commit`)
        self.length = np.zeros((num_slots + 1,), np.int64)
        self.limit = np.zeros((num_slots + 1,), np.int64)
        self.generation = 0
        self.pages_allocated = 0   # running totals, for the counters
        self.pages_freed = 0
        self.window_pages_recycled = 0

    @property
    def scratch_pages(self) -> tuple[int, ...]:
        return tuple(k.num_pages for k in self.kinds)

    def pages_for(self, tokens: int) -> int:
        return -(-int(tokens) // self.page)

    def window_cap(self, k: int) -> int:
        """The most pages of kind ``k`` one session holds at a time."""
        w = self.kinds[k].window
        return self.pages_for(w - 1 + self.grow_step) + 1

    def _first_index(self, k: int, length: int) -> int:
        """Page index of the first key a query at ``length`` still sees."""
        w = self.kinds[k].window
        return 0 if w is None else max(int(length) - (w - 1), 0) // self.page

    # ---- session table: `SessionTable`'s, plus the pages a slot owns -----

    def _no_free_slot_locked(self) -> int:
        raise CacheFullError(
            f"all {self.num_slots} session slots hold a session "
            "(a decoder's sessions are not evicted)")

    def acquire(self, session_id: str) -> tuple[int, bool]:
        with self._lock:
            slot, fresh = super().acquire(session_id)
            if fresh:
                self.length[slot] = 0
            return slot, fresh

    def unpin(self, session_id: str) -> None:
        """The session goes idle and keeps its pages (of a window kind:
        those its length implies); what it was promised and did not grow
        into is returned."""
        with self._lock:
            super().unpin(session_id)
            slot = self._slots.get(session_id)
            if slot is not None:
                self._target[:, slot] = 0
                self._promised[:, slot] = 0
                self._recycle_locked(slot)

    def release(self, session_id: str) -> int | None:
        """Drop the session: its pages and its slot are free again."""
        with self._lock:
            slot = super().release(session_id)
            if slot is None:
                return None
            self._target[:, slot] = 0
            self._promised[:, slot] = 0
            self.length[slot] = 0
            for k, kind in enumerate(self.kinds):
                pages, self._pages[k][slot] = self._pages[k][slot], []
                self._base[k, slot] = 0
                if pages:
                    with _span("cache:pages_free", pages=len(pages),
                               kind=kind.name):
                        self._free_pages[k].extend(reversed(pages))
                        self.pages_freed += len(pages)
            return slot

    # ---- pages ----------------------------------------------------------

    def _uncommitted_locked(self, k: int) -> int:
        return len(self._free_pages[k]) - int(self._promised[k].sum())

    def _target_locked(self, k: int, slot, tokens: int) -> int:
        """Pages of kind ``k`` a session may hold while it grows by
        ``tokens`` (``slot`` None: a session not opened yet)."""
        length = 0 if slot is None else int(self.length[slot])
        total = self.pages_for(length + int(tokens))
        if self.kinds[k].window is None:
            return total
        return min(total - self._first_index(k, length), self.window_cap(k))

    def _need_locked(self, k: int, slot, tokens: int) -> int:
        target = self._target_locked(k, slot, tokens)
        if slot is None:
            return target
        return max(target - len(self._pages[k][slot])
                   - int(self._promised[k, slot]), 0)

    def can_commit(self, slot_tokens) -> bool:
        """Could sessions growing to these ``(slot or None, tokens)`` totals
        all be promised their pages now, of every kind? (``tokens`` is what
        the request adds; a slot's present length and pages count for it.)"""
        with self._lock:
            asked = list(slot_tokens)
            return all(
                sum(self._need_locked(k, slot, tokens)
                    for slot, tokens in asked) <= self._uncommitted_locked(k)
                for k in range(len(self.kinds)))

    def commit(self, slot: int, tokens: int) -> None:
        """Promise ``slot`` the pages, of every kind, to grow by ``tokens``
        more tokens."""
        with self._lock:
            needs = [self._need_locked(k, slot, tokens)
                     for k in range(len(self.kinds))]
            for k, need in enumerate(needs):
                if need > self._uncommitted_locked(k):
                    raise CacheFullError(
                        f"{need} {self.kinds[k].name} pages needed, "
                        f"{self._uncommitted_locked(k)} of "
                        f"{self.kinds[k].num_pages} neither held nor promised")
            for k, need in enumerate(needs):
                self._promised[k, slot] += need
                self._target[k, slot] = (len(self._pages[k][slot])
                                         + int(self._promised[k, slot]))
            self.limit[slot] = int(self.length[slot]) + int(tokens)

    def _recycle_locked(self, slot: int) -> None:
        """Return the window pages wholly behind what ``slot``'s next query
        sees; the promise follows (a running session may take as many
        again)."""
        for k, kind in enumerate(self.kinds):
            pages = self._pages[k][slot]
            drop = min(self._first_index(k, self.length[slot])
                       - int(self._base[k, slot]), len(pages))
            if kind.window is None or drop <= 0:
                continue
            with _span("cache:window_pages_recycle", pages=drop):
                self._free_pages[k].extend(reversed(pages[:drop]))
                del pages[:drop]
                self._base[k, slot] += drop
                self.window_pages_recycled += drop
                self.pages_freed += drop
            self._promised[k, slot] = max(
                int(self._target[k, slot]) - len(pages), 0)

    def ensure(self, slot: int, tokens: int) -> list[tuple[int, list[int]]]:
        """Give ``slot`` pages for ``tokens`` tokens in all (first out of its
        promise, then out of the free pages), of every kind, after
        returning the window pages it has outgrown; per kind, ``(base,
        pages)``: the session's page index of the first page, and the page
        list."""
        with self._lock:
            if self._has_window and \
                    tokens - int(self.length[slot]) > self.grow_step:
                raise ValueError(
                    f"slot {slot} asked to grow by "
                    f"{tokens - int(self.length[slot])} tokens at once; "
                    f"window pages are promised for {self.grow_step}")
            self._recycle_locked(slot)
            for k, kind in enumerate(self.kinds):
                pages = self._pages[k][slot]
                if not pages:       # a window kind's list may start late
                    self._base[k, slot] = self._first_index(
                        k, self.length[slot])
                need = (self.pages_for(tokens) - int(self._base[k, slot])
                        - len(pages))
                if need <= 0:
                    continue
                spare = (self._uncommitted_locked(k)
                         + int(self._promised[k, slot]))
                if need > spare:
                    raise CacheFullError(
                        f"slot {slot} needs {need} more {kind.name} pages, "
                        f"{spare} free")
                with _span("cache:pages_alloc", pages=need, kind=kind.name):
                    pages.extend(self._free_pages[k].pop()
                                 for _ in range(need))
                    self._promised[k, slot] = max(
                        int(self._promised[k, slot]) - need, 0)
                    self.pages_allocated += need
            return [self.held(slot, k) for k in range(len(self.kinds))]

    def held(self, slot: int, k: int = 0) -> tuple[int, list[int]]:
        """``(base, pages)`` of kind ``k``: ``pages[i]`` holds the session's
        page index ``base + i``."""
        with self._lock:
            return int(self._base[k, slot]), self._pages[k][slot]

    def pages_of(self, slot: int) -> list[int]:
        """The pages of the first kind (which keeps every token)."""
        return self.held(slot)[1]

    def free_page_ids(self, k: int) -> list[int]:
        with self._lock:
            return list(self._free_pages[k])

    @property
    def pages_in_use(self) -> int:
        with self._lock:
            return sum(kind.num_pages - len(free) for kind, free
                       in zip(self.kinds, self._free_pages))

    # ---- device state ---------------------------------------------------

    def swap(self, pools) -> None:
        """Install the pools a program handed back (the ones it was given
        are donated: they no longer exist)."""
        with self._lock:
            self.pools = tuple(pools)
            self.generation += 1

    def stats(self) -> dict:
        with self._lock:
            out = {**super().stats(), "generation": self.generation}
            for k, kind in enumerate(self.kinds):
                out.update({
                    f"{kind.name}_pages_total": kind.num_pages,
                    f"{kind.name}_pages_in_use":
                        kind.num_pages - len(self._free_pages[k]),
                    f"{kind.name}_pages_promised":
                        int(self._promised[k].sum())})
            if self._has_window:
                out["window_pages_recycled"] = self.window_pages_recycled
            out.update({
                f"{self.kinds[0].name}_tokens": int(self.length.sum()),
                "page": self.page,
                "pages_allocated": self.pages_allocated,
                "pages_freed": self.pages_freed})
            return out
