"""Decoders for the serving path, two blocks behind one `DecoderConfig`; a
model file's ``model_type`` picks the block.

**``deepseek_v2``**: latent attention (MLA) over a paged latent cache, a
leading dense SwiGLU layer, then expert layers (group-limited greedy
routing, weights not renormalised, shared experts), of which THIS chip holds
a contiguous range of the routed experts and a slice of the vocabulary.

**``mellum``** (Mellum2): grouped-query attention, 8 query heads on each of 4
key/value heads of 128, over a paged K/V cache; ``layer_types`` says per
layer whether a query sees every earlier key (``full_attention``: YaRN rope,
cos and sin times ``attention_factor``) or only the last ``sliding_window``
(``sliding_attention``: plain rope, a token sees itself and the 1,023 before
it); every layer is an expert layer (top-8 of 64 by softmax, weights divided
by their sum, no shared expert), every expert held. No per-head norm of q
and k and no multi-token-prediction head: the published config names neither
(the model file's ``assumed``).

One forward pass over a flat token axis (`forward_tokens`) serves both phases
of either block:

- prefill: ``T`` new tokens of one or more sequences (each with its position
  and the cache row it is written to), attending to what their sequences
  already hold in the cache plus themselves, causally, 16 tokens a q-tile;
- decode: one new token per row: the same function with one token a q-tile.

Both write each token's cached row (MLA: ``[RMSNorm(c_kv) ; rope(k_pe) ;
0]``; GQA: ``[rope(k) of the 4 heads ; v of the 4 heads]``) into the layer's
pool BEFORE attention reads it, so attention sees one source of keys. A
cache has one or two KINDS of page (`cache_kinds`: a latent cache one; a K/V
cache one for its full layers and one for its window layers, which keep only
a session's last pages); ``write_page`` and ``items`` come per kind and a
layer takes its kind's.

Precision, as the configuration files state it: weights and cache bfloat16;
every matmul takes bfloat16 inputs and accumulates in float32; the residual
stream, RMSNorm, rotary angles, the router (logits, softmax, top-k) and the
attention softmax are float32.

Rotary pairing: the published codes de-interleave ``(2i, 2i+1)`` pairs into
halves before ``rotate_half``; here the pairs are rotated in place. q and k
go through the same permutation, so every score is the same number; the
cache holds rotated keys in the interleaved order (the references do the
same).

Equations: ISSUE 29 and ISSUE 34 / PERF.md section 4; the plain references
are `benchmark/reference/deepseek_v2.py` and `benchmark/reference/mellum2.py`.
"""

from __future__ import annotations

import dataclasses
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from ..ops import moe, paged_attention


@dataclasses.dataclass(frozen=True)
class PageKind:
    """One kind of page of a session's cache (`serve.state_cache.PagedCache`):
    one page id indexes the pools of ``layers``; a row is ``width`` lanes;
    with a ``window`` a session keeps only the pages that hold a key its
    next query can see."""
    name: str
    layers: tuple[int, ...]
    num_pages: int
    width: int
    window: int | None = None


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    vocab_size: int
    hidden_size: int
    num_hidden_layers: int
    moe_intermediate_size: int
    n_routed_experts: int          # the router's width (published)
    experts_first: int             # the range of experts held on this chip
    experts_held: int
    num_experts_per_tok: int
    num_attention_heads: int
    norm_topk_prob: bool = False   # router weights divided by their sum
    model_type: str = "deepseek_v2"
    first_k_dense_replace: int = 0
    intermediate_size: int = 0
    n_shared_experts: int = 0
    n_group: int = 1
    topk_group: int = 1
    routed_scaling_factor: float = 1.0
    # latent attention (deepseek_v2)
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0      # mellum: the whole head is rotated
    v_head_dim: int = 0
    # grouped-query attention (mellum)
    num_key_value_heads: int = 0
    layer_types: tuple[str, ...] = ()
    sliding_window: int = 0
    rope_attention_factor: float = 1.0   # on cos and sin of the full layers
    rms_norm_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 40.0
    rope_original_max: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.707
    rope_mscale_all_dim: float = 0.707
    q_init_gain: float = 2.0       # PERF.md section 2: peaked attention
    family: str = "decoder"

    @property
    def grouped(self) -> bool:
        return self.model_type == "mellum"

    @property
    def reading(self) -> paged_attention.Reading:
        if self.grouped:
            return paged_attention.grouped(
                self.num_key_value_heads, self.num_attention_heads,
                self.qk_rope_head_dim)
        return paged_attention.latent(self.kv_lora_rank,
                                      self.qk_rope_head_dim,
                                      self.num_attention_heads)

    @property
    def latent_width(self) -> int:
        """Lanes of one cached row."""
        return self.reading.width

    @property
    def softmax_scale(self) -> float:
        if self.grouped:        # `rope_attention_factor` is on cos and sin
            return self.qk_rope_head_dim ** -0.5
        m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
        return (self.qk_nope_head_dim + self.qk_rope_head_dim) ** -0.5 * m * m

    @property
    def layer_kinds(self) -> tuple[int, ...]:
        """Per layer, which of `cache_kinds`' kinds holds its rows."""
        return tuple(int(t == "sliding_attention") for t in self.layer_types) \
            or (0,) * self.num_hidden_layers

    @classmethod
    def from_model(cls, doc: dict) -> "DecoderConfig":
        """From a model file: the published `config.json` keys at the top
        level (nested groups as published). Where this chip holds a share,
        the key that counts the experts (``n_routed_experts`` /
        ``num_experts``) counts those HELD here (``experts_first`` says from
        which), and ``published`` keeps the router's width;
        ``assumed.q_init_gain`` scales the seeded query projection."""
        fields = {f.name for f in dataclasses.fields(cls)}
        kw = {k: v for k, v in doc.items() if k in fields}
        kw["norm_topk_prob"] = bool(doc.get("norm_topk_prob", False))
        kw["experts_first"] = doc.get("experts_first", 0)
        if doc.get("model_type", "deepseek_v2") == "mellum":
            full = doc["rope_parameters"]["full_attention"]
            plain = doc["rope_parameters"]["sliding_attention"]
            if (plain["rope_type"], full["rope_type"]) != ("default", "yarn") \
                    or plain["rope_theta"] != full["rope_theta"]:
                raise ValueError("mellum: plain rope in the sliding layers "
                                 "and YaRN in the full ones, on one theta")
            if set(doc["mlp_layer_types"]) != {"sparse"}:
                raise ValueError("mellum: every layer is an expert layer")
            kw.update(
                n_routed_experts=doc.get("published", {}).get(
                    "num_experts", doc["num_experts"]),
                experts_held=doc["num_experts"],
                moe_intermediate_size=doc["moe_intermediate_size"],
                qk_rope_head_dim=doc["head_dim"],
                layer_types=tuple(doc["layer_types"]),
                rope_theta=full["rope_theta"], rope_factor=full["factor"],
                rope_original_max=full["original_max_position_embeddings"],
                rope_beta_fast=full["beta_fast"],
                rope_beta_slow=full["beta_slow"],
                rope_attention_factor=full["attention_factor"])
        else:
            rope = doc["rope_scaling"]
            kw.update(
                n_routed_experts=doc.get("published", {}).get(
                    "n_routed_experts", doc["n_routed_experts"]),
                experts_held=doc["n_routed_experts"],
                rope_factor=rope["factor"],
                rope_original_max=rope["original_max_position_embeddings"],
                rope_beta_fast=rope["beta_fast"],
                rope_beta_slow=rope["beta_slow"],
                rope_mscale=rope["mscale"],
                rope_mscale_all_dim=rope["mscale_all_dim"])
        gain = doc.get("assumed", {}).get("q_init_gain")
        if gain is not None:
            kw["q_init_gain"] = gain
        cfg = cls(**kw)
        if len(cfg.layer_kinds) != cfg.num_hidden_layers:
            raise ValueError(f"{len(cfg.layer_types)} layer_types for "
                             f"{cfg.num_hidden_layers} layers")
        return cfg


def cache_kinds(cfg: DecoderConfig, num_pages) -> tuple[PageKind, ...]:
    """The kinds of page ``cfg``'s cache has, ``num_pages`` of each (an int
    where there is one kind): a latent cache one (``latent``), a K/V cache
    ``full`` for the layers that see every key and ``window`` for those that
    see the last ``sliding_window``."""
    counts = (num_pages,) if isinstance(num_pages, int) else tuple(num_pages)
    of = lambda k: tuple(i for i, x in enumerate(cfg.layer_kinds) if x == k)  # noqa: E731
    if not cfg.grouped:
        kinds = (PageKind("latent", of(0), counts[0], cfg.latent_width),)
    else:
        kinds = (PageKind("full", of(0), counts[0], cfg.latent_width),
                 PageKind("window", of(1), counts[-1], cfg.latent_width,
                          cfg.sliding_window))
    if len(counts) != len(kinds):
        raise ValueError(f"{len(counts)} page counts for {len(kinds)} kinds "
                         f"of page ({', '.join(k.name for k in kinds)})")
    return kinds


def load_model_file(path: str) -> tuple[DecoderConfig, dict]:
    """``(config, the file's dict)`` of a decoder model file (a benchmark
    configuration file is one)."""
    with open(path) as f:
        doc = json.load(f)
    return DecoderConfig.from_model(doc), doc


def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_inv_freq(cfg: DecoderConfig) -> np.ndarray:
    """Inverse frequencies of the rope dimensions: per frequency, a blend of
    the unscaled and the interpolated (``/ factor``) value by the linear
    ramp between the two correction dimensions."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    extra = 1.0 / base ** (np.arange(0, dim, 2, dtype=np.float64) / dim)
    inter = extra / cfg.rope_factor

    def correction_dim(rotations):
        return (dim * math.log(cfg.rope_original_max
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction_dim(cfg.rope_beta_fast)), 0)
    high = min(math.ceil(correction_dim(cfg.rope_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp                       # 1: unscaled, 0: interpolated
    return (inter * (1 - keep) + extra * keep).astype(np.float32)


def plain_inv_freq(cfg: DecoderConfig) -> np.ndarray:
    """Unscaled rope frequencies (``rope_type: default``)."""
    dim = cfg.qk_rope_head_dim
    return (1.0 / cfg.rope_theta ** (
        np.arange(0, dim, 2, dtype=np.float64) / dim)).astype(np.float32)


def rope(x, pos, inv_freq, scale: float = 1.0):
    """Rotate interleaved pairs ``(2i, 2i+1)`` of ``x [T, ..., dim]`` by
    ``pos[t] * inv_freq[i]``, in float32; cos and sin times ``scale``
    (DeepSeek-V2: mscale over mscale_all_dim = 1 at the published values;
    Mellum2's full layers: ``attention_factor``)."""
    ang = pos.astype(jnp.float32)[:, None] * inv_freq[None, :]
    cos, sin = scale * jnp.cos(ang), scale * jnp.sin(ang)
    shape = x.shape
    x = x.astype(jnp.float32).reshape(*shape[:-1], shape[-1] // 2, 2)
    extra = (1,) * (len(shape) - 2)
    cos = cos.reshape(shape[0], *extra, -1)
    sin = sin.reshape(shape[0], *extra, -1)
    a, b = x[..., 0], x[..., 1]
    return jnp.stack([a * cos - b * sin, a * sin + b * cos],
                     axis=-1).reshape(shape)


def rmsnorm(x, weight, eps):
    x = x.astype(jnp.float32)
    return (x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
            * weight.astype(jnp.float32))


def _normal(key, shape, std, dtype):
    return (jax.random.normal(key, shape, jnp.float32) * std).astype(dtype)


def init_decoder(seed: int, cfg: DecoderConfig, dtype=jnp.bfloat16):
    """Seeded random weights, each tensor made on the device in its own
    dispatch (at the published widths the tree is 8-10 GB: no float32 copy
    of all of it ever exists). Standard deviation ``fan_in ** -0.5`` (the
    embedding 1), the query projection (``w_qb`` / ``w_q``) times
    ``q_init_gain``; norm weights 1. Gate and up projections are stored side
    by side (``[D, 2I]`` = ``[W_gate | W_up]``), experts stacked on a
    leading axis; a grouped-query layer's key and value projections side by
    side too (``w_kv [D, 2 G d]`` = ``[W_k | W_v]``: one product makes the
    cached row)."""
    d, h = cfg.hidden_size, cfg.num_attention_heads
    qd = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    key = jax.random.PRNGKey(seed)
    count = [0]

    def w(*shape, fan_in=None, gain=1.0):
        count[0] += 1
        std = gain * (shape[-2] if fan_in is None else fan_in) ** -0.5
        return jax.jit(_normal, static_argnums=(1, 2, 3))(
            jax.random.fold_in(key, count[0]), shape, std, dtype)

    ones = lambda n: jnp.ones((n,), jnp.float32)
    layers = []
    for i in range(cfg.num_hidden_layers):
        layer = {"attn_norm": ones(d), "mlp_norm": ones(d)}
        if cfg.grouped:
            layer.update(
                w_q=w(d, h * qd, gain=cfg.q_init_gain),
                w_kv=w(d, 2 * cfg.num_key_value_heads * qd),
                w_o=w(h * qd, d))
        else:
            layer.update(
                w_qa=w(d, cfg.q_lora_rank), q_norm=ones(cfg.q_lora_rank),
                w_qb=w(cfg.q_lora_rank, h * qd, gain=cfg.q_init_gain),
                w_kva=w(d, cfg.kv_lora_rank + cfg.qk_rope_head_dim),
                kv_norm=ones(cfg.kv_lora_rank),
                w_kvb=w(cfg.kv_lora_rank,
                        h * (cfg.qk_nope_head_dim + cfg.v_head_dim)),
                w_o=w(h * cfg.v_head_dim, d))
        if i < cfg.first_k_dense_replace:
            layer["w_gate_up"] = w(d, 2 * cfg.intermediate_size)
            layer["w_down"] = w(cfg.intermediate_size, d)
        else:
            inter, shared = (cfg.moe_intermediate_size,
                             cfg.moe_intermediate_size * cfg.n_shared_experts)
            layer["w_router"] = w(d, cfg.n_routed_experts)
            if shared:
                layer["shared_gate_up"] = w(d, 2 * shared)
                layer["shared_down"] = w(shared, d)
            layer["w_gate_up"] = w(cfg.experts_held, d, 2 * inter)
            layer["w_down"] = w(cfg.experts_held, inter, d)
        layers.append(layer)
    return {"embedding": w(cfg.vocab_size, d, fan_in=1), "layers": layers,
            "final_norm": ones(d), "head": w(d, cfg.vocab_size)}


def absorb(params, cfg: DecoderConfig):
    """Per layer, ``W_kvb`` split into the two factors the absorbed form
    multiplies by: ``w_uk [H, nope, kv_rank]`` (before attention) and
    ``w_uv [H, kv_rank, v]`` (after it). Made once, like `fuse_layers`.
    (Grouped-query layers have nothing to absorb.)"""
    h, nope, v = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                  cfg.v_head_dim)
    if cfg.grouped:
        return [{} for _ in params["layers"]]
    out = []
    for layer in params["layers"]:
        kvb = layer["w_kvb"].reshape(cfg.kv_lora_rank, h, nope + v)
        out.append({"w_uk": jnp.transpose(kvb[:, :, :nope], (1, 2, 0)),
                    "w_uv": jnp.transpose(kvb[:, :, nope:], (1, 0, 2))})
    return out


def _mm(x, w):
    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def swiglu(x, w_gate_up, w_down):
    gu = _mm(x, w_gate_up)
    inter = gu.shape[-1] // 2
    return _mm(jax.nn.silu(gu[..., :inter]) * gu[..., inter:], w_down)


def moe_tile_rows(tokens: int) -> int:
    """Rows of a grouped-product tile for a program of ``tokens`` tokens:
    small when few pairs reach each expert (a decode step reads weights,
    it does not fill tiles), large when many do."""
    return 16 if tokens <= 32 else 64 if tokens <= 128 else \
        128 if tokens <= 512 else 256


def split_query(q, nope: int, pos, inv_freq):
    """``q [T, H, nope + rope]`` -> ``(q_nope, rotated q_pe)``: only the
    rope part is rotated."""
    return q[..., :nope], rope(q[..., nope:], pos, inv_freq)


def cached_latent(kva, kv_norm, kv_rank: int, pos, inv_freq, eps):
    """What the cache holds of a token: ``c_kv`` AFTER its norm and ``k_pe``
    AFTER rotation, from ``x W_kva`` ``[T, kv_rank + rope]``."""
    return (rmsnorm(kva[:, :kv_rank], kv_norm, eps),
            rope(kva[:, kv_rank:], pos, inv_freq))


def _latent_attention(layer, ab, cfg: DecoderConfig, xn, pool, pos,
                      write_page, write_off, items, layer_kind, *, tq: int,
                      interpret: bool):
    """MLA in the absorbed form: ``(Attn(xn) W_o, the layer's pool)``."""
    del layer_kind
    inv_freq = jnp.asarray(yarn_inv_freq(cfg))
    t, dtype = xn.shape[0], pool.dtype
    h, nope, rope_d = (cfg.num_attention_heads, cfg.qk_nope_head_dim,
                       cfg.qk_rope_head_dim)
    kv, width, eps = cfg.kv_lora_rank, cfg.latent_width, cfg.rms_norm_eps
    c_q = rmsnorm(_mm(xn, layer["w_qa"]), layer["q_norm"], eps)
    q_nope, q_pe = split_query(
        _mm(c_q, layer["w_qb"]).reshape(t, h, nope + rope_d), nope,
        pos, inv_freq)
    c_kv, k_pe = cached_latent(_mm(xn, layer["w_kva"]),
                               layer["kv_norm"], kv, pos, inv_freq, eps)
    pad = jnp.zeros((t, width - kv - rope_d), jnp.float32)
    row = jnp.concatenate([c_kv, k_pe, pad], axis=-1).astype(dtype)
    pool = pool.at[write_page, write_off].set(row)
    q_abs = jnp.einsum("thn,hnc->thc", q_nope.astype(dtype), ab["w_uk"],
                       preferred_element_type=jnp.float32)
    q_cat = jnp.concatenate(
        [q_abs, q_pe, jnp.zeros((t, h, width - kv - rope_d))],
        axis=-1).astype(dtype)
    ctx = paged_attention.paged_attention(
        q_cat.reshape(t // tq, tq * h, width), pool, items,
        scale=cfg.softmax_scale, reading=cfg.reading,
        name=("mla_decode" if tq == paged_attention.DECODE_TQ
              else "mla_prefill"),
        interpret=interpret).reshape(t, h, kv)
    o = jnp.einsum("thc,hcv->thv", ctx, ab["w_uv"],
                   preferred_element_type=jnp.float32)
    return _mm(o.reshape(t, h * cfg.v_head_dim), layer["w_o"]), pool


def rotated_query_key(q, k, pos, cfg: DecoderConfig, layer_kind: int):
    """Rope over the whole head of ``q [T, H, d]`` and ``k [T, G, d]``: a
    full layer (kind 0) by YaRN's frequencies with cos and sin times
    ``attention_factor`` (a score carries its square), a window layer (kind
    1) plain."""
    if layer_kind == 0:
        inv_freq, scale = yarn_inv_freq(cfg), cfg.rope_attention_factor
    else:
        inv_freq, scale = plain_inv_freq(cfg), 1.0
    inv_freq = jnp.asarray(inv_freq)
    return rope(q, pos, inv_freq, scale), rope(k, pos, inv_freq, scale)


def group_of_heads(q, groups: int):
    """``q [..., H, d]`` -> ``[..., G, H // G, d]``: query head ``j`` reads
    key/value head ``j // (H // G)``."""
    *lead, h, d = q.shape
    return q.reshape(*lead, groups, h // groups, d)


def _grouped_attention(layer, ab, cfg: DecoderConfig, xn, pool, pos,
                       write_page, write_off, items, layer_kind, *, tq: int,
                       interpret: bool):
    """Grouped-query attention over cached keys and values per head, with
    the layer's window in the kernel's mask: ``(Attn(xn) W_o, pool)``."""
    del ab
    t, dtype = xn.shape[0], pool.dtype
    h, g, d = (cfg.num_attention_heads, cfg.num_key_value_heads,
               cfg.qk_rope_head_dim)
    kv = _mm(xn, layer["w_kv"])
    q, k = rotated_query_key(_mm(xn, layer["w_q"]).reshape(t, h, d),
                             kv[:, :g * d].reshape(t, g, d), pos, cfg,
                             layer_kind)
    row = jnp.concatenate([k.reshape(t, g * d), kv[:, g * d:]], axis=-1)
    pool = pool.at[write_page, write_off].set(row.astype(dtype))
    # a q-tile's rows group-major: [tile, G, tq, H // G, d]
    q = jnp.swapaxes(group_of_heads(q, g).reshape(t // tq, tq, g, h // g, d),
                     1, 2).astype(dtype)
    ctx = paged_attention.paged_attention(
        q.reshape(t // tq, tq * h, d), pool, items, scale=cfg.softmax_scale,
        reading=cfg.reading, window=(cfg.sliding_window if layer_kind else None),
        name=("gqa_decode" if tq == paged_attention.DECODE_TQ
              else "gqa_prefill"),
        interpret=interpret)
    o = jnp.swapaxes(ctx.reshape(t // tq, g, tq, h // g, d), 1, 2)
    return _mm(o.reshape(t, h * d), layer["w_o"]), pool


def forward_tokens(params, absorbed, cfg: DecoderConfig, pools, tokens, pos,
                   live, write_page, write_off, items, *, tq: int,
                   interpret: bool = False):
    """The decoder over a flat axis of ``T`` tokens (``T`` a multiple of
    ``tq``): ``tokens``/``pos``/``live`` ``[T]``, each token's cached row
    written to ``pools[layer][write_page[kind][t], write_off[t]]`` (dead
    tokens: the scratch page), attention per `paged_attention.plan_items`'
    ``items[kind]``, ``kind`` the layer's (`DecoderConfig.layer_kinds`).

    Returns ``(hidden [T, D] float32 after the final norm, new pools,
    counters)``; the head is the caller's (`head_logits`): prefill needs it
    for a row's last token only."""
    t = tokens.shape[0]
    eps = cfg.rms_norm_eps
    x = jnp.take(params["embedding"], tokens, axis=0).astype(jnp.float32)
    pools = list(pools)
    counters = {k: jnp.zeros((), jnp.int32) for k in
                ("moe_pairs_total", "moe_pairs_here", "experts_touched")}
    attention = _grouped_attention if cfg.grouped else _latent_attention
    for i, (layer, ab, kind) in enumerate(zip(params["layers"], absorbed,
                                              cfg.layer_kinds)):
        with jax.named_scope(f"layer{i}_attention"):
            out, pools[i] = attention(
                layer, ab, cfg, rmsnorm(x, layer["attn_norm"], eps), pools[i],
                pos, write_page[kind], write_off, items[kind], kind, tq=tq,
                interpret=interpret)
            x = x + out
        with jax.named_scope(f"layer{i}_mlp"):
            xn = rmsnorm(x, layer["mlp_norm"], eps)
            if "w_router" not in layer:
                x = x + swiglu(xn, layer["w_gate_up"], layer["w_down"])
                continue
            routed, counts = moe.routed_experts(
                xn, live, layer["w_router"],
                layer["w_gate_up"], layer["w_down"],
                first=cfg.experts_first, n_group=cfg.n_group,
                topk_group=cfg.topk_group, top_k=cfg.num_experts_per_tok,
                scale=cfg.routed_scaling_factor,
                renormalise=cfg.norm_topk_prob, tm=moe_tile_rows(t),
                interpret=interpret)
            x = x + routed
            if "shared_gate_up" in layer:
                x = x + swiglu(xn, layer["shared_gate_up"],
                               layer["shared_down"])
            counters = {k: counters[k] + counts[k] for k in counters}
    return rmsnorm(x, params["final_norm"], eps), tuple(pools), counters


def head_logits(params, hidden):
    """``hidden [N, D]`` -> float32 logits over the vocabulary slice."""
    return _mm(hidden, params["head"])


def pick_greedy(logits):
    """``(token [N] int32, chosen logit, largest logit)``: greedy hands the
    judge the two floats it compares with the reference's."""
    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    top = jnp.max(logits, axis=-1)
    return tok, jnp.take_along_axis(logits, tok[:, None], axis=-1)[:, 0], top


def param_count(cfg: DecoderConfig) -> int:
    """Parameters held on this chip (norm weights included)."""
    shapes = jax.eval_shape(lambda: init_decoder(0, cfg))
    return int(sum(np.prod(x.shape) for x in jax.tree.leaves(shapes)))
