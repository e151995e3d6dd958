"""Latent attention (MLA) over a paged latent cache: one ragged kernel, two
tilings.

The cache holds, per token and layer, ONE row shared by every head:
``[c_kv (kv_rank) ; k_pe (rope_dim) ; zeros]``, padded to a multiple of the
128 lanes (576 -> 640 at the published widths; the HBM tiling pads the row
to that anyway). Pages of ``page`` such rows live in a pool
``[num_pages + 1, page, width]`` (the last page is scratch for dead rows),
and a sequence owns a list of pages.

Both phases use the ABSORBED form of the published attention: with
``W_kvb = [W_uk ; W_uv]`` per head,

    score(q, t) = (q_nope W_uk^T) . c_kv[t] + q_pe . k_pe[t]
    out         = (sum_t p_t c_kv[t]) W_uv

which is the same mathematics as decompressing every cached token to
``k_nope``/``v`` (``models.decoder`` applies ``W_uk`` before the kernel and
``W_uv`` after it). Why prefill uses it too: a chunk of at most 512 new
tokens attends to thousands of cached ones, and decompressing a page costs
``page x kv_rank x 256`` multiply-adds PER HEAD whatever the chunk's length,
as much as the absorbed scores of a 512-token chunk against that page; the
absorbed form needs no second copy of the prefix and no second kernel.

The kernel's unit of work is an ITEM: one (q-tile, page) pair. The host
lists exactly the pairs that hold work (`plan_items`: the pages a row really
has, causally pruned for prefill), sorted by q-tile, and the grid's length is
the number of items, read on the device (a dynamic grid): a batch of short
and long contexts costs the sum of their pages, not rows x the longest. A
q-tile is ``tq`` tokens x all heads as one ``[tq*heads, width]`` matrix:
``tq = 1`` for a decode step (`DECODE`), 16 for a prefill chunk (`PREFILL`).

Scores, softmax and the accumulator are float32; the two matmuls take bf16
inputs (the cache's and the query's dtype).
"""

from __future__ import annotations

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


def latent_width(kv_rank: int, rope_dim: int) -> int:
    """Lanes of one cached row: ``kv_rank + rope_dim`` padded to 128."""
    return -(-(kv_rank + rope_dim) // LANES) * LANES


def _kernel(tile_ref, page_ref, start_ref, n_ref, qpos_ref, klen_ref,
            q_ref, pool_ref, o_ref, m_s, l_s, acc_s, *,
            scale: float, heads: int, kv_rank: int):
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

    @pl.when(start < klen)
    def _():
        q = q_ref[0]                       # [tq*heads, width]
        blk = pool_ref[0]                  # [page, width]
        s = jax.lax.dot_general(
            q, blk, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale
        kpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        qpos = q0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0) // heads
        ok = jnp.logical_and(kpos < klen, kpos <= qpos)
        s = jnp.where(ok, s, _NEG)
        m_prev = m_s[...]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(ok, jnp.exp(s - m_new), 0.0)
        l_s[...] = alpha * l_s[...] + p.sum(axis=1, keepdims=True)
        acc_s[...] = alpha * acc_s[...] + jnp.dot(
            p.astype(blk.dtype), blk[:, :kv_rank],
            preferred_element_type=jnp.float32)
        m_s[...] = m_new

    @pl.when(last)
    def _():
        o_ref[0] = (acc_s[...] / jnp.maximum(l_s[...], 1e-30)
                    ).astype(o_ref.dtype)


def paged_attention(q, pool, items, *, scale: float, heads: int,
                    kv_rank: int, name: str, interpret: bool = False):
    """``q [tiles, tq*heads, width]`` against ``pool [pages+1, page, width]``
    -> ``[tiles, tq*heads, kv_rank]`` (the softmax-weighted sum of ``c_kv``
    rows), in ``q``'s dtype. ``items`` is `plan_items`' dict (device or host
    arrays): ``tile``/``page``/``start`` ``[N]`` int32 sorted by tile, ``n``
    ``[1]`` the number that are real, ``qpos``/``klen`` ``[tiles]``. Every
    tile must have at least one item (or its rows are never written)."""
    tiles, rows, width = q.shape
    page = pool.shape[1]
    index = lambda f: (lambda i, tile, pg, st, n, qp, kl: f(i, tile, pg))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=6, grid=(items["n"][0],),
        in_specs=[
            pl.BlockSpec((1, rows, width), index(lambda i, t, p: (t[i], 0, 0))),
            pl.BlockSpec((1, page, width), index(lambda i, t, p: (p[i], 0, 0))),
        ],
        out_specs=pl.BlockSpec((1, rows, kv_rank),
                               index(lambda i, t, p: (t[i], 0, 0))),
        scratch_shapes=[pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, 1), jnp.float32),
                        pltpu.VMEM((rows, kv_rank), jnp.float32)])
    return pl.pallas_call(
        functools.partial(_kernel, scale=scale, heads=heads, kv_rank=kv_rank),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((tiles, rows, kv_rank), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name=name,
    )(items["tile"], items["page"], items["start"], items["n"],
      items["qpos"], items["klen"], q, pool)


def plan_items(page_rows, starts, lengths, *, page: int, tq: int,
               tiles: int, capacity: int, scratch_page: int) -> dict:
    """The (q-tile, page) pairs of one dispatch, on the host.

    Row ``j`` owns pages ``page_rows[j]`` (ids, in order), holds ``starts[j]``
    tokens already and adds ``lengths[j]`` new ones at positions
    ``starts[j] ..``; its q-tiles are the next ``ceil(lengths[j] / tq)`` of
    the ``tiles`` the program has (rows are laid out tile-aligned, one after
    another). A tile gets every page that holds a key at or before its last
    query. Tiles beyond the rows' get one item on the scratch page with no
    keys, so every tile of the output is written. ``klen`` is the row's
    length AFTER this dispatch (its new tokens are in the cache before the
    kernel runs); a decode window overrides ``qpos``/``klen`` from the
    positions it carries on the device."""
    tile, pg, st = [], [], []
    qpos = np.zeros((tiles,), np.int32)
    klen = np.zeros((tiles,), np.int32)
    t = 0
    for pages_j, s, n in zip(page_rows, starts, lengths):
        for k in range(-(-n // tq)):
            q_first = s + k * tq
            q_last = min(q_first + tq, s + n) - 1
            qpos[t], klen[t] = q_first, s + n
            for j in range(q_last // page + 1):
                tile.append(t)
                pg.append(pages_j[j])
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

