"""The serve engine of the decoder family: the programs `Batcher` dispatches,
over a paged cache (latent rows, or keys and values per head: what differs
between the two decoders sits behind `models.decoder`'s block and the
cache's page kinds).

It answers the calls `ServeEngine` answers (`prefill`, `prefill_chunk`,
`decode`, `decode_window`, `decode_window_next`, `fetch_window_summary`,
`warmup`, `stats`, the session calls), so `ServeServer`, the router and the
batcher drive either family; `serve.engine.build_engine` picks by the
configuration's family. What differs from the LSTM engine:

- **State**: `state_cache.PagedCache`. A slot is a session's row in the
  page bookkeeping, not a carry. Every program takes the pools DONATED and
  hands them back: they are updated in place, never copied. A cache with
  two kinds of page (full and window layers) gives every dispatch two page
  tables and two item lists; a window layer's names only the pages that
  hold a key inside some query's window.
- **Prefill** packs the rows of one dispatch onto ONE flat token axis
  (each row's new tokens at a multiple of the q-tile), padded to a token
  bucket; a row continues at the length its slot holds. Pages are taken as
  the row grows (`cache.ensure`).
- **Decode windows** carry each row's position on the device, so a window
  can be dispatched from the handles of the one before it; the host knows
  an upper bound of every length (it takes pages for ``length + window``
  ahead) and learns the true one when it fetches the tokens.
- **The lattice has no context axis**: attention's grid is as long as the
  (q-tile, page) pairs the host lists for the dispatch (`plan_items`), read
  on the device. Programs: (token bucket) x {final, chunk} for prefill,
  (batch bucket) x (window) for decode.
- Greedy only; one resident model; no prefix cache, no tiers, no draft:
  the calls that would need them raise with a message.
- Every program hands back, per token, its logit and the step's largest:
  `prefill`, `decode` and `fetch_window_summary` return them WITH the
  tokens (the LSTM engine returns None there), and the batcher files them
  under `Request.token_logits`: what the benchmark's judge compares.
- Counters (``moe_pairs_total``, ``moe_pairs_here``, ``experts_touched``)
  are summed on the device in an accumulator every program threads through,
  and reach the host with the tokens of the next fetch. The host counts, per
  kind of page, the (query, key) pairs inside the mask that it dispatched:
  ``decode_<kind>_keys_read`` and ``prefill_<kind>_pairs`` (one layer's; a
  reader multiplies by the layers of the kind).
"""

from __future__ import annotations

import dataclasses
import threading
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from .. import obs
from ..models import decoder
from ..ops import paged_attention
from ..utils.tracing import span
from .engine import (GREEDY, PAD_TOKEN, DecodeWindow, SamplingParams,
                     UnknownModelError, _bucket_for)
from .state_cache import PagedCache

COUNTERS = ("moe_pairs_total", "moe_pairs_here", "experts_touched")

_LSTM_ONLY = ("this engine serves a decoder: the prefix cache, the tiered "
              "session cache, speculation, mesh sharding and model swaps are "
              "LSTM-only for now")


@dataclasses.dataclass(frozen=True)
class DecoderWindow(DecodeWindow):
    """A decode window of this family: `DecodeWindow`'s handles, plus what
    this engine's programs carry from one window to the next and hand back
    with the tokens."""

    pos: jax.Array | None = None     # [batch_b] each row's position after it
    logits: jax.Array | None = None  # [batch_b, window, 2]: chosen, largest
    acc: jax.Array | None = None     # the counters' accumulator after it
    #: the host's estimate of each row's position at its dispatch (-1: a
    #: row dead then); rows that end inside a window are still counted
    host_pos: np.ndarray | None = None
    # set once the window's tokens were fetched and the rows' lengths
    # advanced (`fetch_window_summary`)
    fetched: threading.Event = dataclasses.field(
        default_factory=threading.Event, compare=False)


class DecoderEngine:
    family = "decoder"

    def __init__(self, params, cfg: decoder.DecoderConfig, *,
                 num_slots: int = 64, num_pages: int | tuple = 64,
                 page: int = 256,
                 max_context: int = 4096,
                 prefill_buckets: tuple[int, ...] = (128, 512),
                 batch_buckets: tuple[int, ...] = (8, 32),
                 max_prefill_rows: int = 4,
                 registry=None, device=None, model_id: str = "default",
                 model_version: int = 0, interpret: bool = False):
        tq = paged_attention.PREFILL_TQ
        if any(b % tq for b in prefill_buckets):
            raise ValueError(f"prefill buckets must be multiples of {tq}")
        self.cfg = cfg
        self.device = device
        self.params = (jax.device_put(params, device) if device is not None
                       else params)
        self.absorbed = decoder.absorb(self.params, cfg)
        self.model_id, self.model_version = str(model_id), model_version
        self.prefill_buckets = tuple(sorted(prefill_buckets))
        self.batch_buckets = tuple(sorted(batch_buckets))
        self.max_prefill_rows = int(max_prefill_rows)
        #: token buckets of the flat prefill axis: a row's bucket, and that
        #: of as many full rows as one dispatch may pack
        self.token_buckets = tuple(sorted(
            {*self.prefill_buckets,
             self.prefill_buckets[-1] * self.max_prefill_rows}))
        self.max_context = int(max_context)
        self.pages_per_row = -(-self.max_context // page)
        self.metrics = obs.REGISTRY if registry is None else registry
        #: ``num_pages``: one count per kind of page (`decoder.cache_kinds`)
        self.cache = PagedCache(
            num_slots, page, decoder.cache_kinds(cfg, num_pages),
            self.params["embedding"].dtype, device=device,
            grow_step=self.prefill_buckets[-1])
        self.kinds = self.cache.kinds
        self.prefix = None
        self.tiers = None
        self.has_draft = False
        self.mesh_shards = 1
        self.decode_kernel = "gqa" if cfg.grouped else "mla"
        self._interpret = interpret
        self._lock = threading.RLock()
        self._counts_lock = threading.Lock()
        self.compile_counts: dict[tuple, int] = defaultdict(int)
        self._fns: dict[tuple, callable] = {}
        # row 0: the prefill programs' sums, row 1: the decode programs'
        self._acc = jnp.zeros((2, len(COUNTERS)), jnp.int32)
        self.counters = self._split_counters(np.zeros((2, len(COUNTERS))))
        self.decode_steps = 0          # steps dispatched (window sizes summed)
        self.decode_row_steps = 0      # live rows x steps dispatched
        self.decode_context_tokens = 0  # sum over dispatched steps of contexts
        self.prefill_tokens = 0
        self.prefill_attended = 0      # (query, key) pairs of prefilled tokens
        self.prefill_context_tokens = 0  # sum over prefill rows of their ends
        # (query, key) pairs inside the mask, per kind of page (one layer's)
        self.decode_keys_read = [0] * len(self.kinds)
        self.prefill_pairs = [0] * len(self.kinds)
        self._warming = False

    # ---- limits and residency (the batcher's and router's questions) ----

    @property
    def max_prompt_len(self) -> int:
        return self.prefill_buckets[-1]

    @property
    def max_batch(self) -> int:
        return self.batch_buckets[-1]

    @property
    def max_prefill_batch(self) -> int:
        return self.max_prefill_rows

    def has_model(self, model_id: str | None) -> bool:
        return model_id is None or model_id == self.model_id

    def resident_models(self) -> dict:
        return {self.model_id: self.model_version}

    def _check_model(self, model):
        if not self.has_model(model):
            raise UnknownModelError(f"model {model!r} is not resident "
                                    f"(resident: {self.model_id})")

    def add_model(self, *a, **k):
        raise NotImplementedError(_LSTM_ONLY)

    swap_model = remove_model = resize_slots = attach_draft = add_model
    detach_session = restore_session = add_model

    def has_session(self, session_id: str) -> bool:
        return session_id in self.cache

    # ---- admission by pages ---------------------------------------------

    def _grows_by(self, req) -> int:
        return int(req.prompt.size) + int(req.max_new_tokens)

    def admits(self, req, ahead=()) -> bool:
        """Can ``req`` be promised its pages once the requests ``ahead`` of
        it in this admission round have theirs? (It waits in the queue
        otherwise: pages come back when sessions end.)"""
        asked = [(None if r.session_id is None
                  else self.cache.lookup(r.session_id), self._grows_by(r))
                 for r in (*ahead, req)]
        return self.cache.can_commit(asked)

    def admit_session(self, sid: str, req) -> tuple[int, bool]:
        """Slot and pages for an admitted request: ``(slot, fresh)``."""
        known = sid in self.cache
        slot, fresh = self.cache.acquire_pinned(sid)
        try:
            if int(self.cache.length[slot]) + self._grows_by(req) \
                    > self.max_context:
                raise ValueError(
                    f"session {sid!r} would reach "
                    f"{int(self.cache.length[slot]) + self._grows_by(req)} "
                    f"tokens; this engine's programs hold {self.max_context}")
            self.cache.commit(slot, self._grows_by(req))
        except Exception:
            if known:
                self.cache.unpin(sid)
            else:
                self.cache.release(sid)
            raise
        return slot, fresh

    # ---- programs --------------------------------------------------------

    def _count(self, key: tuple) -> None:
        with self._counts_lock:
            self.compile_counts[key] += 1

    def _items_capacity(self, tiles: int, k: int) -> int:
        """Items a program of ``tiles`` q-tiles may list for kind ``k``:
        every page of a row, or the most a session holds of a window kind."""
        if self.kinds[k].window is None:
            return tiles * self.pages_per_row
        return tiles * min(self.pages_per_row, self.cache.window_cap(k))

    def _scratch_filled(self, *shape) -> np.ndarray:
        """``[kinds, *shape]`` int32, each kind's plane its scratch page."""
        out = np.empty((len(self.kinds), *shape), np.int32)
        out[:] = np.asarray(self.cache.scratch_pages).reshape(
            -1, *(1,) * len(shape))
        return out

    def _keys_seen(self, k: int, positions) -> int:
        """(query, key) pairs inside kind ``k``'s mask of queries at
        ``positions``: each sees itself and what precedes it, a window kind
        at most ``window`` keys."""
        seen = np.asarray(positions, np.int64) + 1
        w = self.kinds[k].window
        return int((seen if w is None else np.minimum(seen, w)).sum())

    def _prefill_fn(self, tokens_b: int, final: bool):
        key = ("decoder_prefill" if final else "decoder_prefill_chunk",
               tokens_b)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        cfg, interpret = self.cfg, self._interpret

        def run(params, absorbed, pools, acc, tokens, pos, live, write_page,
                write_off, items, last_idx):
            self._count(key)
            hidden, pools, counts = decoder.forward_tokens(
                params, absorbed, cfg, pools, tokens, pos, live, write_page,
                write_off, items, tq=paged_attention.PREFILL_TQ,
                interpret=interpret)
            acc = acc.at[0].add(jnp.stack([counts[k] for k in COUNTERS]))
            if not final:
                return pools, acc
            logits = decoder.head_logits(params, hidden[last_idx])
            tok, chosen, top = decoder.pick_greedy(logits)
            return pools, acc, tok, jnp.stack([chosen, top], axis=-1)

        run.__name__ = "decoder_prefill_fn" if final else "decoder_chunk_fn"
        fn = jax.jit(run, donate_argnums=(2,))
        self._fns[key] = fn
        return fn

    def _window_fn(self, batch_b: int, window: int):
        key = ("decoder_window", batch_b, window)
        fn = self._fns.get(key)
        if fn is not None:
            return fn
        cfg, interpret, page = self.cfg, self._interpret, self.cache.page
        scratch = jnp.asarray(self.cache.scratch_pages, jnp.int32)[:, None]

        def decoder_window_fn(params, absorbed, pools, acc, tokens, pos,
                              alive, remaining, eos_ids, page_table, items):
            self._count(key)
            rows = jnp.arange(batch_b)

            def step(carry, _):
                pools, acc, token, pos, alive, remaining = carry
                write_page = jnp.where(
                    alive, page_table[:, rows, pos // page], scratch)
                klen = jnp.where(alive, pos + 1, 0)
                step_items = [dict(it, qpos=pos, klen=klen) for it in items]
                hidden, pools, counts = decoder.forward_tokens(
                    params, absorbed, cfg, pools, token, pos, alive,
                    write_page, jnp.where(alive, pos % page, 0), step_items,
                    tq=paged_attention.DECODE_TQ, interpret=interpret)
                nxt, chosen, top = decoder.pick_greedy(
                    decoder.head_logits(params, hidden))
                # a row alive at the step's start consumed its token (its
                # latent is in the cache) and emits the next one
                out_tok = jnp.where(alive, nxt, PAD_TOKEN).astype(jnp.int32)
                new_remaining = remaining - alive.astype(remaining.dtype)
                hit_eos = alive & (eos_ids >= 0) & (nxt == eos_ids)
                new_alive = alive & ~hit_eos & (new_remaining > 0)
                carry = (pools,
                         acc.at[1].add(jnp.stack([counts[k] for k in COUNTERS])),
                         jnp.where(new_alive, nxt, 0).astype(jnp.int32),
                         pos + alive.astype(pos.dtype), new_alive,
                         new_remaining)
                return carry, (out_tok, jnp.stack([chosen, top], axis=-1))

            (pools, acc, next_tok, pos, alive, remaining), (toks, logits) = \
                jax.lax.scan(step, (pools, acc, tokens, pos, alive,
                                    remaining), None, length=window)
            return (pools, acc, jnp.moveaxis(toks, 0, 1),
                    jnp.moveaxis(logits, 0, 1), next_tok, pos, alive,
                    remaining)

        fn = jax.jit(decoder_window_fn, donate_argnums=(2,))
        self._fns[key] = fn
        return fn

    def _admit_sampling(self, sampling: SamplingParams) -> None:
        if not sampling.greedy:
            raise ValueError("the decoder engine decodes greedily: its "
                             "programs hand the judge the logit of the "
                             "argmax (sampled decoding is LSTM-only for now)")

    # ---- prefill ----------------------------------------------------------

    def _pack_prefill(self, items):
        """Lay the rows' new tokens on the flat token axis and plan the
        attention items, per kind of page. Takes pages as rows grow (and
        returns the window pages they have outgrown)."""
        cache, tq = self.cache, paged_attention.PREFILL_TQ
        if len(items) > self.max_prefill_rows:
            raise ValueError(f"{len(items)} rows in a prefill dispatch of "
                             f"at most {self.max_prefill_rows}")
        rows, at = [], 0
        for it in items:
            slot, prompt = it[0], np.asarray(it[-1], np.int32).reshape(-1)
            if len(it) == 4 and it[1] != slot:
                raise ValueError(_LSTM_ONLY)
            if prompt.size < 1:
                raise ValueError("empty prompt")
            n = int(prompt.size)
            if slot == cache.scratch_slot:      # warm-up rows
                start = 0
                held = [(0, [scratch] * cache.pages_for(n))
                        for scratch in cache.scratch_pages]
            else:
                start = int(cache.length[slot])
                held = cache.ensure(slot, start + n)
            rows.append((prompt, slot, start, held, at))
            at += -(-n // tq) * tq
        tokens_b = _bucket_for(at, self.token_buckets, "prefill tokens")
        tokens = np.zeros((tokens_b,), np.int32)
        pos = np.zeros((tokens_b,), np.int32)
        live = np.zeros((tokens_b,), bool)
        write_page = self._scratch_filled(tokens_b)
        write_off = np.zeros((tokens_b,), np.int32)
        last_idx = np.zeros((self.max_prefill_rows,), np.int32)
        for r, (prompt, slot, start, held, at) in enumerate(rows):
            n = prompt.size
            p = start + np.arange(n)
            tokens[at:at + n], pos[at:at + n], live[at:at + n] = prompt, p, True
            for k, (base, pages) in enumerate(held):
                write_page[k, at:at + n] = np.asarray(
                    pages, np.int32)[p // cache.page - base]
            write_off[at:at + n] = p % cache.page
            last_idx[r] = at + n - 1
        tiles = tokens_b // tq
        plans = [paged_attention.plan_items(
            [r[3][k][1] for r in rows], [r[2] for r in rows],
            [r[0].size for r in rows], page=cache.page, tq=tq, tiles=tiles,
            capacity=self._items_capacity(tiles, k),
            scratch_page=cache.scratch_pages[k], window=kind.window,
            bases=[r[3][k][0] for r in rows])
            for k, kind in enumerate(self.kinds)]
        return (tokens_b, rows, (tokens, pos, live, write_page, write_off,
                                 plans, last_idx))

    def _run_prefill(self, items, final: bool):
        with span("engine:pack"):
            tokens_b, rows, host = self._pack_prefill(items)
            args = jax.device_put(host)
        n_new = sum(r[0].size for r in rows)
        with self._lock:
            fn = self._prefill_fn(tokens_b, final)
            with span("serve:prefill_chunk", tokens=n_new,
                      context=max(r[2] for r in rows)), \
                    span("engine:launch",
                         program=("decoder_prefill_fn" if final
                                  else "decoder_chunk_fn"),
                         batch_bucket=tokens_b, len_bucket=tokens_b,
                         context_bucket=sum(int(p["n"][0]) for p in host[5])):
                out = fn(self.params, self.absorbed, self.cache.pools,
                         self._acc, *args)
            self.cache.swap(out[0])
            self._acc = out[1]
        # the host's own counts: the scheduler's thread alone writes them
        for prompt, slot, start, _, _ in rows:
            n = int(prompt.size)
            if slot != self.cache.scratch_slot:
                self.cache.length[slot] += n
            if not self._warming:
                self.prefill_tokens += n
                self.prefill_attended += n * start + n * (n + 1) // 2
                self.prefill_context_tokens += start + n
                for k in range(len(self.kinds)):
                    self.prefill_pairs[k] += self._keys_seen(
                        k, start + np.arange(n))
        return out[1:]

    def prefill(self, items, sampling: SamplingParams = GREEDY, *,
                model: str | None = None) -> tuple[np.ndarray, np.ndarray]:
        """The FINAL chunk of each row's prompt: ``items`` are ``(slot,
        fresh, tokens)`` triples (or the LSTM engine's quads with src ==
        dst); a row continues at the length its slot holds. Returns
        ``(tokens [n], logits [n, 2])``: the first generated token of each
        row, with its logit and the step's largest."""
        if len(items) == 0:
            return np.zeros((0,), np.int32), np.zeros((0, 2), np.float32)
        self._admit_sampling(sampling)
        self._check_model(model)
        acc, tok, logits = self._run_prefill(items, final=True)
        with span("engine:fetch", program="decoder_prefill_fn",
                  rows=len(items), k=1):
            tok, logits, acc = jax.device_get((tok, logits, acc))
        self._note_counters(acc)
        return np.asarray(tok)[:len(items)], np.asarray(logits)[:len(items)]

    def prefill_chunk(self, items, *, model: str | None = None) -> None:
        """An INTERMEDIATE chunk: the rows' latents are written, nothing is
        sampled, nothing is fetched."""
        if len(items) == 0:
            return
        self._check_model(model)
        self._run_prefill(items, final=False)

    def draft_prefill(self, items) -> None:
        raise NotImplementedError(_LSTM_ONLY)

    # ---- decode -----------------------------------------------------------

    def _plan_window(self, slots, ahead: int, batch_b: int):
        """Page tables and attention items, per kind of page, for rows that
        may grow by ``ahead`` tokens from the lengths the host knows."""
        cache = self.cache
        tables = self._scratch_filled(batch_b, self.pages_per_row)
        held_rows = []
        for i, slot in enumerate(slots):
            if slot == cache.scratch_slot:
                held = [(0, [scratch]) for scratch in cache.scratch_pages]
            else:
                length = int(cache.length[slot])
                held = cache.ensure(
                    slot, min(length + ahead, int(cache.limit[slot])))
            for k, (base, pages) in enumerate(held):
                tables[k, i, base:base + len(pages)] = pages
            held_rows.append(held)
        # each row is one q-tile that may see every page it holds (a window
        # layer's rows hold only the pages their windows reach)
        plans = [paged_attention.plan_items(
            [h[k][1] for h in held_rows],
            [max((h[k][0] + len(h[k][1])) * cache.page - 1, 0)
             for h in held_rows],
            [1] * len(slots), page=cache.page, tq=1, tiles=batch_b,
            capacity=self._items_capacity(batch_b, k),
            scratch_page=cache.scratch_pages[k],
            bases=[h[k][0] for h in held_rows])
            for k in range(len(self.kinds))]
        return tables, plans

    def _launch_window(self, fn, batch_b, window, tokens, pos, alive,
                       remaining, eos, table, plans, *, host_pos):
        with self._lock, span("engine:launch", program="decoder_window_fn",
                              batch_bucket=batch_b,
                              context_bucket=sum(int(p["n"][0])
                                                 for p in plans)):
            out = fn(self.params, self.absorbed, self.cache.pools, self._acc,
                     tokens, pos, alive, remaining, eos,
                     *jax.device_put((table, plans)))
            self.cache.swap(out[0])
            self._acc = out[1]
        if not self._warming:
            at = host_pos[host_pos >= 0]
            self.decode_steps += window
            self.decode_row_steps += at.size * window
            self.decode_context_tokens += int(at.sum()) * window
            steps = at[:, None] + np.arange(window)[None, :]
            for k in range(len(self.kinds)):
                self.decode_keys_read[k] += self._keys_seen(k, steps)
        return out[1:]

    def decode_window(self, slots, tokens, remaining, eos_ids=None,
                      sampling: SamplingParams = GREEDY, *, window: int,
                      model: str | None = None) -> DecoderWindow:
        n = len(slots)
        if n == 0 or window < 1:
            raise ValueError(f"decode_window needs rows and window >= 1, "
                             f"got {n} rows, window {window}")
        self._admit_sampling(sampling)
        self._check_model(model)
        cache = self.cache
        with span("engine:pack"):
            batch_b = _bucket_for(n, self.batch_buckets, "decode batch")
            slots_p = np.full((batch_b,), cache.scratch_slot, np.int32)
            slots_p[:n] = np.asarray(slots, np.int32)
            tokens_p = np.zeros((batch_b,), np.int32)
            tokens_p[:n] = np.asarray(tokens, np.int32)
            rem_p = np.zeros((batch_b,), np.int32)
            rem_p[:n] = np.asarray(remaining, np.int32)
            eos_p = np.full((batch_b,), -1, np.int32)
            if eos_ids is not None:
                eos_p[:n] = np.asarray(eos_ids, np.int32)
            alive_p = rem_p > 0
            pos_p = cache.length[slots_p].astype(np.int32)
            table, plans = self._plan_window(slots_p, window, batch_b)
            dev = jax.device_put((tokens_p, pos_p, alive_p, rem_p, eos_p))
        host_pos = np.where(alive_p, pos_p, -1)
        acc, toks, logits, next_tok, pos, alive, rem = self._launch_window(
            self._window_fn(batch_b, window), batch_b, window, *dev,
            table, plans, host_pos=host_pos)
        return DecoderWindow(
            tokens=toks, next_tokens=next_tok, alive=alive, remaining=rem,
            slots=slots_p, eos_ids=dev[4], batch_b=batch_b, window=window,
            n=n, sampling=sampling, t_dispatch=time.perf_counter(),
            model=self.model_id, pos=pos, logits=logits, acc=acc,
            host_pos=host_pos)

    def decode_window_next(self, prev: DecoderWindow, *,
                           window: int | None = None) -> DecoderWindow:
        """The follow-up window from ``prev``'s device handles, before
        ``prev`` is fetched: positions come from the device; the host takes
        pages for the furthest any row can have got."""
        window = prev.window if window is None else window
        # every window before ``prev`` has been fetched (the batcher keeps
        # one in flight); ``prev`` itself may not have been
        ahead = (0 if prev.fetched.is_set() else prev.window) + window
        host_pos = np.where(prev.host_pos >= 0, prev.host_pos + prev.window, -1)
        with span("engine:pack"):
            table, plans = self._plan_window(prev.slots, ahead, prev.batch_b)
        acc, toks, logits, next_tok, pos, alive, rem = self._launch_window(
            self._window_fn(prev.batch_b, window), prev.batch_b, window,
            prev.next_tokens, prev.pos, prev.alive, prev.remaining,
            prev.eos_ids, table, plans, host_pos=host_pos)
        return dataclasses.replace(
            prev, tokens=toks, next_tokens=next_tok, alive=alive,
            remaining=rem, window=window, t_dispatch=time.perf_counter(),
            pos=pos, logits=logits, acc=acc, host_pos=host_pos,
            fetched=threading.Event())

    def fetch_window_summary(self, win: DecoderWindow):
        """One transfer: ``(tokens [n, K], remaining [n], alive [n], logits
        [n, K, 2])`` — the tokens, the latched budgets and liveness, the
        tokens' logits — and the counters. The rows' true lengths are
        learned here: a row consumed one token for each it emitted."""
        with span("engine:fetch", program="decoder_window_fn", rows=win.n,
                  k=win.window):
            toks, rem, alive, logits, acc = jax.device_get(
                (win.tokens, win.remaining, win.alive, win.logits, win.acc))
        toks = np.asarray(toks)
        emitted = (toks != PAD_TOKEN).sum(axis=1)
        if not win.fetched.is_set():        # lengths advance once
            win.fetched.set()
            for slot, m in zip(win.slots[:win.n], emitted[:win.n]):
                if slot != self.cache.scratch_slot:
                    self.cache.length[slot] += int(m)
        self._note_counters(acc)
        n = win.n
        return (toks[:n], np.asarray(rem)[:n], np.asarray(alive)[:n],
                np.asarray(logits)[:n])

    def fetch_window(self, win: DecoderWindow) -> np.ndarray:
        return self.fetch_window_summary(win)[0]

    def decode(self, slots, tokens, sampling: SamplingParams = GREEDY, *,
               model: str | None = None) -> tuple[np.ndarray, np.ndarray]:
        """One token for every row, now (a window of one, fetched):
        ``(tokens [n], logits [n, 2])``."""
        win = self.decode_window(slots, tokens, [1] * len(slots),
                                 sampling=sampling, window=1, model=model)
        toks, _, _, logits = self.fetch_window_summary(win)
        return toks[:, 0], logits[:, 0]

    def spec_window(self, *a, **k):
        raise NotImplementedError(_LSTM_ONLY)

    spec_window_next = spec_window

    @staticmethod
    def _split_counters(acc) -> dict:
        """All programs' sums under the counters' names, the decode
        programs' alone under ``decode_<name>``."""
        out = {k: int(acc[0][i] + acc[1][i]) for i, k in enumerate(COUNTERS)}
        out.update({f"decode_{k}": int(acc[1][i])
                    for i, k in enumerate(COUNTERS)})
        return out

    def _note_counters(self, acc) -> None:
        if not self._warming:
            self.counters = self._split_counters(np.asarray(acc))

    # ---- warm-up, stats -----------------------------------------------------

    def warmup(self, sampling: SamplingParams = GREEDY,
               prompt_lens: tuple[int, ...] = (1,), batch_sizes=None,
               windows: tuple[int, ...] = (), chunk_lens: tuple[int, ...] = (),
               models=None, spec_windows: tuple[int, ...] = ()) -> int:
        """Compile every program the lattice has, against the scratch slot
        and the scratch page: (token bucket) x {final, chunk} and (batch
        bucket) x ({1} | windows). The lengths are the batcher's business
        elsewhere; here the flat token axis makes them moot."""
        self._admit_sampling(sampling)
        scratch = self.cache.scratch_slot
        self._warming = True
        try:
            with self._lock:
                base = jax.device_get(self._acc)
            for tokens_b in self.token_buckets:
                rows = min(self.max_prefill_rows,
                           max(tokens_b // self.prefill_buckets[-1], 1))
                each = tokens_b // rows
                items = [(scratch, True, np.zeros((each,), np.int32))] * rows
                self.prefill(items, sampling)
                self.prefill_chunk(items)
            for b in self.batch_buckets:
                for k in sorted({1, *windows}):
                    self.fetch_window(self.decode_window(
                        [scratch] * b, [0] * b, [k] * b, sampling=sampling,
                        window=k))
            # the scratch rows were dead or padding: nothing was routed,
            # but keep the accumulator exactly where it was
            with self._lock:
                self._acc = jax.device_put(base)
        finally:
            self._warming = False
        return len(self._fns)

    def num_compiles(self, phase: str | None = None) -> int:
        with self._counts_lock:
            items = list(self.compile_counts.items())
        return sum(v for k, v in items if phase is None or k[0] == phase)

    def stats(self) -> dict:
        with self._counts_lock:
            compiles = dict(self.compile_counts)
        # the pools are donated by every dispatch: a reader on another
        # thread must not touch them. They live where the weights live.
        emb = self.params["embedding"]
        dev = min(emb.devices(), key=lambda d: d.id)
        memory = dev.memory_stats() or {}
        ids = lambda x: sorted(d.id for d in x.devices())  # noqa: E731
        return {
            "device": {"platform": dev.platform, "kind": dev.device_kind,
                       "process_devices": jax.device_count(),
                       "cache_on": ids(emb),
                       "params_on": ids(emb),
                       "peak_bytes_in_use": memory.get("peak_bytes_in_use")},
            "family": self.family,
            "decode_kernel": self.decode_kernel,
            "mesh_shards": 1,
            "model_id": self.model_id,
            "models": self.resident_models(),
            "draft": None,
            "decode_window_scan_fallbacks": 0,
            "cache": self.cache.stats(),
            "prefix_cache": None,
            "tiers": None,
            "compiles": {repr(k): v for k, v in compiles.items()},
            "prefill_buckets": self.prefill_buckets,
            "token_buckets": self.token_buckets,
            "batch_buckets": self.batch_buckets,
            "decoder": {**self.counters,
                        "decode_steps": self.decode_steps,
                        "decode_row_steps": self.decode_row_steps,
                        "decode_context_tokens": self.decode_context_tokens,
                        "prefill_tokens": self.prefill_tokens,
                        "prefill_attended": self.prefill_attended,
                        "prefill_context_tokens":
                            self.prefill_context_tokens,
                        **{f"decode_{k.name}_keys_read": v for k, v in
                           zip(self.kinds, self.decode_keys_read)},
                        **{f"prefill_{k.name}_pairs": v for k, v in
                           zip(self.kinds, self.prefill_pairs)}},
        }
