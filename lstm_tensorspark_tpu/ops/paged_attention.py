"""Ragged attention over a paged cache: one kernel, two readings of a page,
two tilings.

A cache holds, per token and layer, ONE row; pages of ``page`` such rows live
in a pool ``[num_pages + 1, page, width]`` (the last page is scratch for dead
rows), and a sequence owns a list of pages. How a row is read as keys and
values is a `Reading`:

- `latent` (MLA, DeepSeek-V2): the row is ``[c_kv (kv_rank) ; k_pe (rope_dim)
  ; zeros]`` shared by every head, padded to a multiple of the 128 lanes (576
  -> 640 at the published widths; the HBM tiling pads the row to that
  anyway). The whole row is the key, its first ``kv_rank`` lanes the value:
  the ABSORBED form of the published attention, with ``W_kvb = [W_uk ;
  W_uv]`` per head,

      score(q, t) = (q_nope W_uk^T) . c_kv[t] + q_pe . k_pe[t]
      out         = (sum_t p_t c_kv[t]) W_uv

  which is the same mathematics as decompressing every cached token to
  ``k_nope``/``v`` (``models.decoder`` applies ``W_uk`` before the kernel and
  ``W_uv`` after it). Prefill uses it too: a chunk of at most 512 new tokens
  attends to thousands of cached ones, and decompressing a page costs ``page
  x kv_rank x 256`` multiply-adds PER HEAD whatever the chunk's length.
- `grouped` (GQA): the row is ``[k of every key/value head ; v of every
  key/value head]`` (4 x 128 + 4 x 128 = 1,024 lanes at Mellum2's widths), so
  one grid step brings ONE page block of 512 KB for all key/value heads (a
  block per head would be 64 KB, less than a grid step's fixed cost is worth)
  and the kernel walks the groups: group ``g``'s query heads against lanes
  ``[g*d, (g+1)*d)`` as keys and ``[G*d + g*d, ...)`` as values.

The kernel's unit of work is an ITEM: one (q-tile, page) pair. The host
lists exactly the pairs that hold work (`plan_items`: the pages a row really
has, causally pruned for prefill, and for a layer with an attention WINDOW
only the pages that hold a key inside some query's window), sorted by
q-tile, and the grid's length is the number of items, read on the device (a
dynamic grid): a batch of short and long contexts costs the sum of their
pages, not rows x the longest. A q-tile is ``tq`` tokens x all query heads
as one ``[groups * tq * heads, width]`` matrix (group-major, so a group's
rows are contiguous): ``tq = 1`` for a decode step, 16 for a prefill chunk.

The mask: key ``u`` of a query at position ``p`` counts when ``u <= p``, ``u
< klen`` and, with a window ``w`` (a static argument), ``p - u < w`` (a
token sees itself and the ``w - 1`` before it).

Scores, softmax and the accumulator are float32; the two matmuls take the
cache's and the query's dtype (bf16 on the chip).
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
DECODE_TQ = 1
PREFILL_TQ = 16
_NEG = -1e30


@dataclasses.dataclass(frozen=True)
class Reading:
    """How a cached row is read: ``groups`` key/value heads side by side,
    ``heads`` query heads on each; group ``g``'s key is lanes ``[g*k_width,
    (g+1)*k_width)`` (the query's width), its value lanes ``[v_at +
    g*v_width, v_at + (g+1)*v_width)``."""
    groups: int
    heads: int
    k_width: int
    v_at: int
    v_width: int

    @property
    def width(self) -> int:
        return max(self.groups * self.k_width,
                   self.v_at + self.groups * self.v_width)


def latent_width(kv_rank: int, rope_dim: int) -> int:
    """Lanes of one cached latent row: ``kv_rank + rope_dim`` padded to 128."""
    return -(-(kv_rank + rope_dim) // LANES) * LANES


def latent(kv_rank: int, rope_dim: int, heads: int) -> Reading:
    return Reading(1, heads, latent_width(kv_rank, rope_dim), 0, kv_rank)


def grouped(kv_heads: int, q_heads: int, head_dim: int) -> Reading:
    return Reading(kv_heads, q_heads // kv_heads, head_dim,
                   kv_heads * head_dim, head_dim)


def _kernel(tile_ref, page_ref, start_ref, n_ref, qpos_ref, klen_ref,
            q_ref, pool_ref, o_ref, m_s, l_s, acc_s, *,
            scale: float, reading: Reading, window: int | None):
    del page_ref  # read by the pool's index map
    i = pl.program_id(0)
    n = n_ref[0]
    r = tile_ref[i]
    first = jnp.logical_or(i == 0, tile_ref[jnp.maximum(i - 1, 0)] != r)
    last = jnp.logical_or(i == n - 1,
                          tile_ref[jnp.minimum(i + 1, n - 1)] != r)

    @pl.when(first)
    def _():
        m_s[...] = jnp.full_like(m_s, _NEG)
        l_s[...] = jnp.zeros_like(l_s)
        acc_s[...] = jnp.zeros_like(acc_s)

    start = start_ref[i]      # position of the page's first row
    klen = klen_ref[r]        # keys [0, klen) exist for this tile's sequence
    q0 = qpos_ref[r]          # position of the tile's first token
    has_keys = start < klen
    if window is not None:    # the page's last key is inside q0's window
        has_keys = jnp.logical_and(
            has_keys, start + pool_ref.shape[1] > q0 - window + 1)

    @pl.when(has_keys)
    def _():
        blk = pool_ref[0]                  # [page, width]
        rd = reading
        rows = q_ref.shape[1] // rd.groups
        for g in range(rd.groups):
            at = slice(g * rows, (g + 1) * rows)
            q = q_ref[0, at, :]            # [tq*heads, k_width]
            s = jax.lax.dot_general(
                q, blk[:, g * rd.k_width:(g + 1) * rd.k_width],
                (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale
            kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
            qpos = q0 + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0) // rd.heads
            ok = jnp.logical_and(kpos < klen, kpos <= qpos)
            if window is not None:
                ok = jnp.logical_and(ok, qpos - kpos < window)
            s = jnp.where(ok, s, _NEG)
            m_prev = m_s[at, :]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            alpha = jnp.exp(m_prev - m_new)
            p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
            l_s[at, :] = alpha * l_s[at, :] + p.sum(axis=1, keepdims=True)
            v0 = rd.v_at + g * rd.v_width
            acc_s[at, :] = alpha * acc_s[at, :] + jnp.dot(
                p.astype(blk.dtype), blk[:, v0:v0 + rd.v_width],
                preferred_element_type=jnp.float32)
            m_s[at, :] = m_new

    @pl.when(last)
    def _():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention(q, pool, items, *, scale: float, reading: Reading,
                    name: str, window: int | None = None,
                    interpret: bool = False):
    """``q [tiles, groups*tq*heads, k_width]`` (group-major rows) against
    ``pool [pages+1, page, width]`` -> ``[tiles, groups*tq*heads, v_width]``
    (the softmax-weighted sum of each group's values), in ``q``'s dtype.
    ``items`` is `plan_items`' dict (device or host arrays): ``tile`` /
    ``page`` / ``start`` ``[N]`` int32 sorted by tile, ``n`` ``[1]`` the
    number that are real, ``qpos``/``klen`` ``[tiles]``. Every tile must
    have at least one item (or its rows are never written)."""
    tiles, rows, k_width = q.shape
    page, width = pool.shape[1:]
    v_width = reading.v_width
    index = lambda f: (lambda i, tile, pg, st, n, qp, kl: f(i, tile, pg))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(items["n"][0],),
        in_specs=[
            pl.BlockSpec((1, rows, k_width),
                         index(lambda i, t, p: (t[i], 0, 0))),
            pl.BlockSpec((1, page, width), index(lambda i, t, p: (p[i], 0, 0))),
        ],
        out_specs=pl.BlockSpec((1, rows, v_width),
                               index(lambda i, t, p: (t[i], 0, 0))),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, v_width), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, reading=reading,
                          window=window),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, rows, v_width), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name=name,
    )(items["tile"], items["page"], items["start"], items["n"],
      items["qpos"], items["klen"], q, pool)


def plan_items(page_rows, starts, lengths, *, page: int, tq: int,
               tiles: int, capacity: int, scratch_page: int,
               window: int | None = None, bases=None) -> dict:
    """The (q-tile, page) pairs of one dispatch, on the host.

    Row ``j`` owns pages ``page_rows[j]`` (ids, in order; the first is page
    index ``bases[j]`` of its sequence, 0 without ``bases``: a cache whose
    layers have a window keeps only a sequence's last pages), holds
    ``starts[j]`` tokens already and adds ``lengths[j]`` new ones at
    positions ``starts[j] ..``; its q-tiles are the next ``ceil(lengths[j] /
    tq)`` of the ``tiles`` the program has (rows are laid out tile-aligned,
    one after another). A tile gets every page that holds a key at or before
    its last query and, with a ``window``, inside its FIRST query's window
    (the later queries' windows begin later). Tiles beyond the rows' get one
    item on the scratch page with no keys, so every tile of the output is
    written. ``klen`` is the row's length AFTER this dispatch (its new tokens
    are in the cache before the kernel runs); a decode window overrides
    ``qpos``/``klen`` from the positions it carries on the device."""
    tile, pg, st = [], [], []
    qpos = np.zeros((tiles,), np.int32)
    klen = np.zeros((tiles,), np.int32)
    t = 0
    for r, (pages_j, s, n) in enumerate(zip(page_rows, starts, lengths)):
        base = 0 if bases is None else bases[r]
        for k in range(-(-n // tq)):
            q_first = s + k * tq
            q_last = min(q_first + tq, s + n) - 1
            qpos[t], klen[t] = q_first, s + n
            lo = 0 if window is None else max(q_first - window + 1, 0) // page
            for j in range(max(lo, base), q_last // page + 1):
                tile.append(t)
                pg.append(pages_j[j - base])
                st.append(j * page)
            t += 1
    if t > tiles:
        raise ValueError(f"{t} q-tiles in a program of {tiles}")
    for dead in range(t, tiles):
        tile.append(dead)
        pg.append(scratch_page)
        st.append(0)
    n = len(tile)
    if n > capacity:
        raise ValueError(f"{n} attention items exceed the program's {capacity}")
    pad = capacity - n

    def arr(x, fill):
        return np.asarray(x + [fill] * pad, np.int32)

    return {"tile": arr(tile, tiles - 1), "page": arr(pg, scratch_page),
            "start": arr(st, 0), "n": np.asarray([n], np.int32),
            "qpos": qpos, "klen": klen}
