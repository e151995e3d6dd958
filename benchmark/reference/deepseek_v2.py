"""The plain reference: DeepSeek-V2's forward pass in `jax.numpy`.

Float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernel, no cache, no batching; attention in the NON-absorbed form (every
position's latent is decompressed to ``k_nope`` and ``v``); the expert layer
as a dense sum over the experts it is told this chip holds. Nothing here
imports the program. It follows the published `modeling_deepseek.py`
(https://huggingface.co/deepseek-ai/DeepSeek-V2, arXiv:2405.04434); every
departure is listed here:

1. *The share.* `forward` takes ``experts_held`` (the ids of the routed
   experts on this chip) and adds only their terms; the router still scores
   all ``n_routed_experts`` and keeps its ``num_experts_per_tok``. Terms of
   experts held elsewhere are left out and the partial sum goes on to the
   next layer, as in the program. With all experts held it is the published
   layer (`tests`: the shares add up).
2. *Parameter layout.* It reads the program's tree: gate and up projections
   side by side (``w_gate_up [D, 2I]`` = ``[W_gate | W_up]``), experts
   stacked on a leading axis in the order of ``experts_held``; ``w_kvb
   [kv_rank, H * (nope + v)]`` is the published ``kv_b_proj`` transposed,
   viewed ``[kv_rank, H, nope + v]``. Weights are upcast to float32 where
   they are used (at the published widths a float32 copy of all of them does
   not fit a chip).
3. *Rotary pairing.* The published code de-interleaves pairs ``(2i, 2i+1)``
   into halves and applies ``rotate_half``; here the pairs are rotated in
   place. q and k take the same permutation, so every score is the same.
4. *Blocks.* Queries go through in blocks of positions and heads in groups,
   so that a 20,000-token sequence fits beside the program: a block's rows
   are independent of the other blocks', so this changes nothing.
5. bias-free, no dropout, no auxiliary loss (evaluation).

``model`` is the ``model`` group of the configuration file (the published
keys); ``params`` the tree of `models/decoder.init_decoder`.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

F32 = jnp.float32


def _f32(x):
    return jnp.asarray(x, F32)


def rmsnorm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * _f32(w)


def yarn_mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(model: dict) -> np.ndarray:
    """`DeepseekV2YarnRotaryEmbedding`: per frequency a blend of the
    unscaled and the interpolated inverse frequency by the linear ramp
    between the two correction dimensions."""
    r, dim, base = (model["rope_scaling"], model["qk_rope_head_dim"],
                    model["rope_theta"])
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / r["factor"]

    def correction_dim(rot):
        return (dim * math.log(r["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    mask = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return (inter * (1 - mask) + extra * mask).astype(np.float32)


def rope(x, pos, inv_freq):
    """Rotate pairs (2i, 2i+1) of ``x [T, ..., dim]`` (departure 3). The
    cos/sin scale is mscale(factor, mscale) / mscale(factor, mscale_all_dim)
    = 1 at the published values, and is applied."""
    ang = _f32(pos)[:, None] * _f32(inv_freq)[None, :]
    shape = x.shape
    x = x.reshape(*shape[:-1], shape[-1] // 2, 2)
    cos = jnp.cos(ang).reshape(shape[0], *(1,) * (len(shape) - 2), -1)
    sin = jnp.sin(ang).reshape(shape[0], *(1,) * (len(shape) - 2), -1)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(shape)


def softmax_scale(model: dict) -> float:
    r = model["rope_scaling"]
    m = yarn_mscale(r["factor"], r["mscale_all_dim"])
    return (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]) ** -0.5 * m * m


def swiglu(x, w_gate_up, w_down):
    gu = x @ _f32(w_gate_up)
    inter = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :inter]) * gu[..., inter:]) @ _f32(w_down)


def _attend_block(q, q_pos, k, v, scale):
    """``q [b, g, dq]`` at positions ``q_pos [b]`` against keys ``k [T, g,
    dq]``, values ``v [T, g, dv]`` at positions 0..T-1, causal."""
    s = jnp.einsum("bgd,tgd->gbt", q, k) * scale
    mask = jnp.arange(k.shape[0])[None, :] <= q_pos[:, None]
    p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), axis=-1)
    return jnp.einsum("gbt,tgd->bgd", p, v)


@functools.partial(jax.jit, static_argnames=("nope", "scale", "block"))
def _head_group(c_q, c_kv, k_pe, pos, inv_freq, w_qb, w_kvb, w_o, *, nope,
                scale, block):
    """A group of ``g`` heads over the whole sequence: ``w_qb [q_rank, g,
    nope + rope]``, ``w_kvb [kv_rank, g, nope + v]``, ``w_o [g, v, D]`` ->
    their part of ``concat_h(P v) W_o``, ``[T, D]``. Queries in blocks of
    ``block`` positions (departure 4)."""
    t, g = c_q.shape[0], w_qb.shape[1]
    q = jnp.einsum("tc,cgd->tgd", c_q, _f32(w_qb))
    q = jnp.concatenate([q[..., :nope], rope(q[..., nope:], pos, inv_freq)], -1)
    kvb = jnp.einsum("tc,cgd->tgd", c_kv, _f32(w_kvb))
    k = jnp.concatenate(
        [kvb[..., :nope],
         jnp.broadcast_to(k_pe[:, None, :], (t, g, k_pe.shape[-1]))], -1)
    v = kvb[..., nope:]
    o = jax.lax.map(
        lambda qp: _attend_block(qp[0], qp[1], k, v, scale),
        (q.reshape(t // block, block, g, -1), pos.reshape(t // block, block)))
    return jnp.einsum("tgd,gdm->tm", o.reshape(t, g, -1), _f32(w_o))


def attention(layer, model: dict, x, pos, inv_freq, *, block: int,
              head_group: int):
    """``x [T, D]`` (one sequence from position 0, ``T`` a multiple of
    ``block``) -> ``Attn(RMSNorm(x))``."""
    h, nope, rope_d, vd, kv = (
        model["num_attention_heads"], model["qk_nope_head_dim"],
        model["qk_rope_head_dim"], model["v_head_dim"], model["kv_lora_rank"])
    eps = model["rms_norm_eps"]
    xn = rmsnorm(x, layer["attn_norm"], eps)
    c_q = rmsnorm(xn @ _f32(layer["w_qa"]), layer["q_norm"], eps)
    kva = xn @ _f32(layer["w_kva"])
    c_kv = rmsnorm(kva[:, :kv], layer["kv_norm"], eps)
    k_pe = rope(kva[:, kv:], pos, inv_freq)              # shared by the heads
    w_qb = layer["w_qb"].reshape(-1, h, nope + rope_d)
    w_kvb = layer["w_kvb"].reshape(kv, h, nope + vd)
    w_o = layer["w_o"].reshape(h, vd, -1)
    out = jnp.zeros_like(x)
    for g0 in range(0, h, head_group):
        g1 = min(g0 + head_group, h)
        out = out + _head_group(
            c_q, c_kv, k_pe, pos, _f32(inv_freq), w_qb[:, g0:g1],
            w_kvb[:, g0:g1], w_o[g0:g1], nope=nope,
            scale=softmax_scale(model), block=block)
    return out


def route(xn, layer, model: dict):
    """``(experts [T, k], weights [T, k])``: softmax over all experts, a
    group's score its largest, the best ``topk_group`` groups stay, top-k of
    what stays, weights ``routed_scaling_factor * p`` (not renormalised:
    ``norm_topk_prob`` is false)."""
    p = jax.nn.softmax(xn @ _f32(layer["w_router"]), axis=-1)
    t, e = p.shape
    g = model["n_group"]
    group = p.reshape(t, g, e // g).max(-1)
    keep = jax.lax.top_k(group, model["topk_group"])[1]
    ok = jnp.zeros((t, g), bool).at[jnp.arange(t)[:, None], keep].set(True)
    masked = jnp.where(jnp.repeat(ok, e // g, axis=1), p, 0.0)
    w, idx = jax.lax.top_k(masked, model["num_experts_per_tok"])
    return idx, model["routed_scaling_factor"] * w


@functools.partial(jax.jit, static_argnames=("block",))
def _routed(xn, idx, w, w_gate_up, w_down, held, *, block):
    """``sum over the experts held of weight_e(x) * Expert_e(x)``: every
    held expert over every position (dense: no sort, no gather), positions
    in blocks. ``w_gate_up [E, D, 2I]``, ``w_down [E, I, D]``, ``held [E]``
    the experts' ids."""
    t, d = xn.shape

    def one_block(args):
        xb, ib, wb = args

        def add_expert(y, expert):
            gate_up, down, e = expert
            weight = jnp.sum(jnp.where(ib == e, wb, 0.0), axis=-1)
            return y + weight[:, None] * swiglu(xb, gate_up, down), None

        return jax.lax.scan(add_expert, jnp.zeros_like(xb),
                            (w_gate_up, w_down, held))[0]

    k = idx.shape[-1]
    return jax.lax.map(one_block, (
        xn.reshape(t // block, block, d), idx.reshape(t // block, block, k),
        w.reshape(t // block, block, k))).reshape(t, d)


def mlp(layer, model: dict, x, experts_held, *, block: int):
    """``MLP(RMSNorm(x))`` of one layer: dense SwiGLU, or the shared experts
    plus the terms of the routed experts in ``experts_held`` (``x``'s length
    a multiple of ``block``)."""
    xn = rmsnorm(x, layer["mlp_norm"], model["rms_norm_eps"])
    if "w_router" not in layer:
        return swiglu(xn, layer["w_gate_up"], layer["w_down"])
    idx, w = route(xn, layer, model)
    return (swiglu(xn, layer["shared_gate_up"], layer["shared_down"])
            + _routed(xn, idx, w, layer["w_gate_up"], layer["w_down"],
                      jnp.asarray(experts_held, jnp.int32), block=block))


def forward(params, model: dict, tokens, experts_held, *, want=None,
            block: int = 256, head_group: int = 16):
    """``tokens [T]`` (one sequence from position 0) -> float32 logits
    ``[hi - lo, V]`` of positions ``want = (lo, hi)`` (default: the last).
    The sequence is padded to a multiple of ``block`` (attention is causal:
    what follows a position changes nothing at it)."""
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        lo, hi = want or (n - 1, n)
        tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, -n % block))
        pos = jnp.arange(tokens.shape[0])
        inv_freq = yarn_inv_freq(model)
        x = _f32(params["embedding"][tokens])
        for layer in params["layers"]:
            h = x + attention(layer, model, x, pos, inv_freq, block=block,
                              head_group=head_group)
            x = h + mlp(layer, model, h, experts_held, block=block)
        x = rmsnorm(x[lo:hi], params["final_norm"], model["rms_norm_eps"])
        return x @ _f32(params["head"])
