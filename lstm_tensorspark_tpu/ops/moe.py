"""Routed experts for one chip's share of an expert-parallel layer.

`route` is the published greedy router over ALL experts: softmax scores,
the best ``topk_group`` of ``n_group`` groups by their largest score (one
group: no limit), top-``k`` of what stays, weights ``scale * p``, divided by
their sum over the ``k`` picks where the model says so (``renormalise``:
the published ``norm_topk_prob``; DeepSeek-V2 does not, Mellum2 does).
`routed_experts` computes the terms of the experts THIS chip holds (a
contiguous range ``first .. first + held``, which may be all of them) and
nothing that stands in for the others: pairs routed elsewhere are dropped
before any work is done for them.

The pairs that land here are laid out expert by expert in tiles of ``tm``
rows (`plan_tiles`, a few gathers on the device, no scatter), and one
grouped matrix product (`grouped_matmul`, a Pallas kernel whose weight block
is picked by the tile's expert through scalar prefetch, and whose grid is as
long as the tiles in use, read on the device) runs gate/up and down over
them; the weighted combine is a gather back. Experts with no pair are never
read, which is what a decode step lives on: its cost is the weights of the
experts touched.

Counters (``counts``): pairs routed in all, pairs that landed here, experts
touched; summed by the caller, fetched with the tokens.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def route(x, w_router, *, n_group: int, topk_group: int, top_k: int,
          scale: float, renormalise: bool):
    """``x [T, D]``, ``w_router [D, E]`` -> ``(experts [T, k] int32,
    weights [T, k] float32)``. Scores and softmax in float32 (a bf16 router
    flips near-tied picks, and a flipped pick is a different expert's
    output, not a rounding)."""
    logits = jnp.dot(x.astype(jnp.float32), w_router.astype(jnp.float32),
                     precision=jax.lax.Precision.HIGHEST)
    p = jax.nn.softmax(logits, axis=-1)
    t, e = p.shape
    group_best = p.reshape(t, n_group, e // n_group).max(axis=-1)
    _, keep = jax.lax.top_k(group_best, topk_group)
    group_ok = jnp.zeros((t, n_group), bool).at[
        jnp.arange(t)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(group_ok, e // n_group, axis=1), p, 0.0)
    w, experts = jax.lax.top_k(masked, top_k)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return experts.astype(jnp.int32), scale * w


def plan_tiles(experts, live, *, first: int, held: int, tm: int):
    """Lay the pairs that land on experts ``first .. first+held`` out in
    tiles of ``tm`` rows, each tile one expert's.

    ``experts [T, k]``; ``live [T]`` bool (padding rows route nowhere).
    Returns a dict: ``slot_token [M]`` (the token each row of the laid-out
    buffer reads; M = tiles_max * tm), ``slot_ok [M]``, ``pair_slot [T, k]``
    (where each pair's output lies, or -1), ``tile_expert [tiles_max]``
    (local ids), ``n_tiles [1]``, and the counters."""
    t, k = experts.shape
    pairs = t * k
    tiles_max = held + -(-pairs // tm)
    local = experts - first
    here = (local >= 0) & (local < held) & live[:, None]
    key = jnp.where(here, local, held).reshape(-1)          # sentinel last
    order = jnp.argsort(key, stable=True)                    # sorted -> pair
    counts = jnp.sum(key[:, None] == jnp.arange(held)[None, :], axis=0)
    group_start = jnp.cumsum(counts) - counts
    tiles_of = -(-counts // tm)
    tile_end = jnp.cumsum(tiles_of)
    tile_start = tile_end - tiles_of
    n_tiles = tile_end[-1]
    tile_expert = jnp.minimum(
        jnp.searchsorted(tile_end, jnp.arange(tiles_max), side="right"),
        held - 1).astype(jnp.int32)
    slot = jnp.arange(tiles_max * tm)
    e_of_slot = tile_expert[slot // tm]
    pos = slot - tile_start[e_of_slot] * tm
    slot_ok = (slot // tm < n_tiles) & (pos < counts[e_of_slot])
    sorted_idx = jnp.clip(group_start[e_of_slot] + pos, 0, pairs - 1)
    slot_token = jnp.where(slot_ok, order[sorted_idx] // k, 0)
    rank = jnp.argsort(order)                                # pair -> sorted
    key_c = jnp.minimum(key, held - 1)
    pair_slot = jnp.where(
        key < held, tile_start[key_c] * tm + rank - group_start[key_c], -1)
    return {
        "slot_token": slot_token.astype(jnp.int32), "slot_ok": slot_ok,
        "pair_slot": pair_slot.reshape(t, k).astype(jnp.int32),
        "tile_expert": tile_expert,
        "n_tiles": n_tiles.astype(jnp.int32).reshape(1),
        "counts": {"moe_pairs_total": jnp.sum(live).astype(jnp.int32) * k,
                   "moe_pairs_here": jnp.sum(counts).astype(jnp.int32),
                   "experts_touched": jnp.sum(counts > 0).astype(jnp.int32)},
    }


def _gmm_kernel(te_ref, n_ref, x_ref, w_ref, o_ref, acc):
    del te_ref, n_ref
    kk = pl.program_id(2)

    @pl.when(kk == 0)
    def _():
        acc[...] = jnp.zeros_like(acc)

    acc[...] += jnp.dot(x_ref[...], w_ref[0],
                        preferred_element_type=jnp.float32)

    @pl.when(kk == pl.num_programs(2) - 1)
    def _():
        o_ref[...] = acc[...].astype(o_ref.dtype)


def _block(dim: int, want: int) -> int:
    """The largest divisor of ``dim`` that is at most ``want`` and a
    multiple of 128, else the whole dimension."""
    for b in range(min(want, dim), 127, -128):
        if dim % b == 0 and b % 128 == 0:
            return b
    return dim


def grouped_matmul(x, w, tile_expert, n_tiles, *, tm: int,
                   interpret: bool = False,
                   name: str = "expert_gmm"):
    """``x [M, K]`` in tiles of ``tm`` rows, tile ``i`` multiplied by
    ``w[tile_expert[i]]`` (``w [E, K, N]``) -> ``[M, N]`` in ``x``'s dtype,
    float32 accumulation. Only the first ``n_tiles[0]`` tiles are computed;
    the rows of the others are never written."""
    m, k = x.shape
    _, _, n = w.shape
    tk, tn = _block(k, 1024), _block(n, 1536)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(n_tiles[0], n // tn, k // tk),
        in_specs=[
            pl.BlockSpec((tm, tk), lambda i, j, kk, te, nt: (i, kk)),
            pl.BlockSpec((1, tk, tn), lambda i, j, kk, te, nt: (te[i], kk, j)),
        ],
        out_specs=pl.BlockSpec((tm, tn), lambda i, j, kk, te, nt: (i, j)),
        scratch_shapes=[pltpu.VMEM((tm, tn), jnp.float32)])
    return pl.pallas_call(
        _gmm_kernel, grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), x.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary"),
            vmem_limit_bytes=64 * 2 ** 20),
        interpret=interpret, name=name,
    )(tile_expert, n_tiles, x, w)


def routed_experts(x, live, w_router, w_gate_up, w_down, *, first: int,
                   n_group: int, topk_group: int, top_k: int, scale: float,
                   renormalise: bool, tm: int, interpret: bool = False):
    """The routed part of one expert layer on this chip: ``x [T, D]`` ->
    ``(sum over the experts held of weight * Expert(x) [T, D] float32,
    counts)``. ``w_gate_up [held, D, 2I]`` is ``[W_gate | W_up]``,
    ``w_down [held, I, D]``."""
    held, _, two_i = w_gate_up.shape
    experts, weights = route(x, w_router, n_group=n_group,
                             topk_group=topk_group, top_k=top_k, scale=scale,
                             renormalise=renormalise)
    plan = plan_tiles(experts, live, first=first, held=held, tm=tm)
    gmm = functools.partial(grouped_matmul, tile_expert=plan["tile_expert"],
                            n_tiles=plan["n_tiles"], tm=tm,
                            interpret=interpret)
    # the router read ``x`` as it came (float32); the experts' matmuls
    # take it in the weights' dtype
    xs = jnp.take(x.astype(w_gate_up.dtype), plan["slot_token"], axis=0)
    gu = gmm(xs, w_gate_up, name="expert_gmm_gate_up")
    h = (jax.nn.silu(gu[:, : two_i // 2].astype(jnp.float32))
         * gu[:, two_i // 2:].astype(jnp.float32)).astype(xs.dtype)
    out = gmm(h, w_down, name="expert_gmm_down")
    # rows of tiles that never ran hold whatever was there: select, do not
    # multiply by zero
    out = jnp.where(plan["slot_ok"][:, None], out, 0).astype(jnp.float32)
    picked = jnp.take(out, jnp.maximum(plan["pair_slot"], 0), axis=0)
    w = jnp.where(plan["pair_slot"] >= 0, weights, 0.0)
    return jnp.einsum("tk,tkd->td", w, picked), plan["counts"]

