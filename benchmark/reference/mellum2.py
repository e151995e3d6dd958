"""The plain reference: Mellum2's forward pass in `jax.numpy`.

Float32 throughout under ``jax.default_matmul_precision("highest")``; no
kernel, no cache, no batching: full causal attention over one sequence from
position 0 with a layer's window as a MASK, each key/value head serving its
eight query heads, the router's softmax over all experts, then the top-k,
then the division by their sum. Nothing here imports the program. It follows
the published `config.json` (https://huggingface.co/JetBrains/
Mellum2-12B-A2.5B-Instruct; ``model_type: "mellum"``), whose keys fix every
equation used here (ISSUE 34); `modeling_mellum` was not available, so what
the keys do not name is ASSUMED and listed:

1. *No per-head RMSNorm of q and k* (no key names one) and *no
   multi-token-prediction head* (it has no key; serving the main model does
   not run it).
2. *Parameter layout.* It reads the program's tree: ``w_q [D, H d]``, ``w_kv
   [D, 2 G d]`` = ``[W_k | W_v]`` side by side, ``w_o [H d, D]``; gate and
   up projections side by side (``w_gate_up [E, D, 2I]`` = ``[W_gate |
   W_up]``), experts stacked on a leading axis. Weights are upcast to float32
   where they are used.
3. *Rotary pairing.* Published codes de-interleave pairs ``(2i, 2i+1)`` into
   halves and apply ``rotate_half``; here the pairs are rotated in place. q
   and k take the same permutation, so every score is the same.
4. *Blocks.* Queries go through in blocks of positions, so that a
   40,000-token sequence fits beside the program. A window layer's block
   reads only the keys its mask can pass (the block's own positions and the
   ``window - 1`` before its first, rounded up to blocks): the mask is still
   applied, the keys left out are ones it refuses.
5. *Experts only on the tokens that chose them.* Per expert, the rows routed
   to it are gathered, go through its SwiGLU, and are added back times their
   weight (a dense sum over 64 experts of 40,000 tokens would be eight times
   the work, all of it multiplied by zero). The rows are found on the host.
6. bias-free, no dropout, no auxiliary loss (evaluation).

``model`` is the configuration file (the published keys at its top level);
``params`` the tree of `models/decoder.init_decoder`.
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


def inv_freq(model: dict, layer_type: str) -> tuple[np.ndarray, float]:
    """``(inverse frequencies [d / 2], the factor on cos and sin)`` of a
    layer: ``rope_type: default`` for the sliding layers; YaRN for the full
    ones (per frequency a blend of the unscaled and the interpolated inverse
    frequency by the linear ramp between the two correction dimensions, and
    ``attention_factor`` on cos and sin of q and k alike)."""
    r, dim = model["rope_parameters"][layer_type], model["head_dim"]
    base = r["rope_theta"]
    plain = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    if r["rope_type"] == "default":
        return plain.astype(np.float32), 1.0
    inter = plain / r["factor"]

    def correction_dim(rot):
        return (dim * math.log(r["original_max_position_embeddings"]
                               / (rot * 2 * math.pi)) / (2 * math.log(base)))

    low = max(math.floor(correction_dim(r["beta_fast"])), 0)
    high = min(math.ceil(correction_dim(r["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    keep = 1.0 - np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    return ((inter * (1 - keep) + plain * keep).astype(np.float32),
            float(r["attention_factor"]))


def rope(x, pos, freq, factor):
    """Rotate pairs (2i, 2i+1) of ``x [T, heads, d]`` (departure 3)."""
    ang = _f32(pos)[:, None] * _f32(freq)[None, :]
    cos = (factor * jnp.cos(ang))[:, None, :]
    sin = (factor * jnp.sin(ang))[:, None, :]
    shape = x.shape
    x = x.reshape(*shape[:-1], shape[-1] // 2, 2)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos], -1).reshape(shape)


def _attend_block(q, q_pos, k, v, k_pos, window, scale):
    """``q [b, G, H/G, d]`` at ``q_pos [b]`` against ``k``/``v [n, G, d]`` at
    ``k_pos [n]`` (each key/value head serves its H/G query heads): causal,
    and within ``window`` keys where one is given."""
    s = jnp.einsum("bghd,ngd->ghbn", q, k) * scale
    seen = (k_pos[None, :] <= q_pos[:, None]) & (k_pos[None, :] >= 0)
    if window:
        seen &= q_pos[:, None] - k_pos[None, :] < window
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum("ghbn,ngd->bghd", p, v)


@functools.partial(jax.jit, static_argnames=("heads", "groups", "window",
                                              "block", "factor", "eps"))
def _attention(x, norm, pos, freq, w_q, w_kv, w_o, *, heads, groups, window,
               block, factor, eps):
    xn = rmsnorm(x, norm, eps)
    t = xn.shape[0]
    d = w_q.shape[1] // heads
    q = rope((xn @ _f32(w_q)).reshape(t, heads, d), pos, freq, factor)
    kv = xn @ _f32(w_kv)
    k = rope(kv[:, :groups * d].reshape(t, groups, d), pos, freq, factor)
    v = kv[:, groups * d:].reshape(t, groups, d)
    # query head j reads key/value head j // (heads // groups)
    q = q.reshape(t, groups, heads // groups, d)
    # a block sees the keys of its own positions and of `back` before them
    back = -(-(window - 1) // block) * block if window else None

    if back is not None:
        k, v = (jnp.pad(a, ((back, 0), (0, 0), (0, 0))) for a in (k, v))

    def one_block(args):
        qb, pb = args
        if back is None:
            kb, vb, kp = k, v, pos
        else:       # the padded arrays' row i is position i - back
            kb = jax.lax.dynamic_slice_in_dim(k, pb[0], back + block)
            vb = jax.lax.dynamic_slice_in_dim(v, pb[0], back + block)
            kp = pb[0] - back + jnp.arange(back + block)  # < 0: the padding
        return _attend_block(qb, pb, kb, vb, kp, window, d ** -0.5)

    o = jax.lax.map(one_block, (
        q.reshape(t // block, block, groups, heads // groups, d),
        pos.reshape(t // block, block)))
    return x + o.reshape(t, heads * d) @ _f32(w_o)


def attention(layer, model: dict, x, pos, layer_type: str, *, block: int):
    """``x [T, D]`` (one sequence from position 0, ``T`` a multiple of
    ``block``) -> ``x + Attn(RMSNorm(x))`` of a layer of ``layer_type``."""
    freq, factor = inv_freq(model, layer_type)
    window = (model["sliding_window"] if layer_type == "sliding_attention"
              else 0)
    return _attention(
        x, layer["attn_norm"], pos, _f32(freq), layer["w_q"], layer["w_kv"],
        layer["w_o"], heads=model["num_attention_heads"],
        groups=model["num_key_value_heads"], window=window, block=block,
        factor=factor, eps=model["rms_norm_eps"])


def route(xn, w_router, k: int, renormalise: bool):
    """``(experts [T, k], weights [T, k])``: softmax over all experts, the
    ``k`` largest, their weights divided by their sum (``norm_topk_prob``)."""
    p = jax.nn.softmax(xn @ _f32(w_router), axis=-1)
    w, idx = jax.lax.top_k(p, k)
    if renormalise:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    return idx, w


def swiglu(x, w_gate_up, w_down):
    gu = x @ _f32(w_gate_up)
    inter = gu.shape[-1] // 2
    return (jax.nn.silu(gu[..., :inter]) * gu[..., inter:]) @ _f32(w_down)


def _ladder(n: int, least: int = 64) -> int:
    """``n`` rounded up to 2^k or 1.5 x 2^k: few shapes to compile."""
    size = least
    while size < n:
        size = size * 3 // 2 if size & (size - 1) == 0 else size // 3 * 4
    return size


@jax.jit
def _experts(h, xn, rows, which, w, w_gate_up, w_down):
    """``h`` plus expert ``e`` over the rows ``rows[e]`` of ``xn`` times
    their weights ``w[rows[e], which[e]]`` (``which`` -1: padding)."""
    def add(y, expert):
        r, k, gate_up, down = expert
        weight = jnp.where(k >= 0, w[r, jnp.maximum(k, 0)], 0.0)
        return y.at[r].add(weight[:, None] * swiglu(xn[r], gate_up, down)), None

    return jax.lax.scan(add, h, (rows, which, w_gate_up, w_down))[0]


def mlp(layer, model: dict, h):
    """``h + sum over the k picks of weight_e * Expert_e(RMSNorm(h))``,
    expert by expert over the rows that picked it (departure 5): the rows
    are sorted by expert on the host and padded to one length for all
    experts (at least half as much again as an even router would give each,
    so that a sequence length compiles one shape)."""
    xn, idx, w = _routed(h, layer["mlp_norm"], layer["w_router"],
                         eps=model["rms_norm_eps"],
                         k=model["num_experts_per_tok"],
                         renormalise=bool(model["norm_topk_prob"]))
    picks = np.asarray(idx)
    t, k = picks.shape
    experts = layer["w_gate_up"].shape[0]
    counts = np.bincount(picks.ravel(), minlength=experts)
    order = np.argsort(picks.ravel(), kind="stable")
    cap = _ladder(max(int(counts.max()), 3 * t * k // (2 * experts)))
    rows = np.zeros((experts, cap), np.int32)
    which = np.full((experts, cap), -1, np.int32)
    at = 0
    for e, n in enumerate(counts):
        rows[e, :n], which[e, :n] = order[at:at + n] // k, order[at:at + n] % k
        at += n
    return _experts(h, xn, rows, which, w, layer["w_gate_up"], layer["w_down"])


@functools.partial(jax.jit, static_argnames=("eps", "k", "renormalise"))
def _routed(h, norm, w_router, *, eps, k, renormalise):
    xn = rmsnorm(h, norm, eps)
    return (xn, *route(xn, w_router, k, renormalise))


def forward(params, model: dict, tokens, *, want=None, block: int = 256):
    """``tokens [T]`` (one sequence from position 0) -> float32 logits
    ``[hi - lo, V]`` of positions ``want = (lo, hi)`` (default: the last).
    The sequence is padded to a multiple of ``block`` (attention is causal:
    what follows a position changes nothing at it)."""
    with jax.default_matmul_precision("highest"):
        n = len(tokens)
        lo, hi = want or (n - 1, n)
        tokens = jnp.pad(jnp.asarray(tokens, jnp.int32), (0, -n % block))
        pos = jnp.arange(tokens.shape[0])
        x = _f32(params["embedding"][tokens])
        for layer, layer_type in zip(params["layers"], model["layer_types"]):
            h = attention(layer, model, x, pos, layer_type, block=block)
            x = mlp(layer, model, h)
        return _logits(x, params["final_norm"], params["head"], lo,
                       size=_ladder(hi - lo),
                       eps=model["rms_norm_eps"])[:hi - lo]


@functools.partial(jax.jit, static_argnames=("size", "eps"))
def _logits(x, norm, head, lo, *, size, eps):
    """Logits of positions ``lo .. lo + size`` (``size`` from `_ladder`: few
    shapes; rows past the sequence's end are padding)."""
    rows = jax.lax.dynamic_slice_in_dim(
        jnp.pad(x, ((0, size), (0, 0))), lo, size)
    return rmsnorm(rows, norm, eps) @ _f32(head)
