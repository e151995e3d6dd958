"""Autoregressive text generation for the LSTM LM.

Reference parity: SURVEY.md §2 "Eval / inference" [P] — the reference's
inference surface is a forward-only predict path. For a language model the
natural predict operation is sampling continuations; this module supplies it
TPU-natively: one jitted program containing the prompt prefill (batched
`lm_forward` over [B, T0]) and the decode loop (`lax.scan` over new tokens,
recurrent carries threaded on-device). No per-token host round-trips — the
host sees only the final [B, T0 + N] token array.

Sampling modes (all static at trace time): greedy argmax, temperature
scaling, top-k truncation, top-p (nucleus) truncation.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
from jax import lax

from ..ops.lstm_cell import fuse_params, lstm_step
from .lstm_lm import LMConfig, init_carries, lm_forward


def sample_logits(
    rng: jax.Array,
    logits: jax.Array,
    *,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    greedy: bool = False,
) -> jax.Array:
    """Sample token ids [B] from logits [B, V]. ``top_k`` and ``top_p``
    (nucleus) truncation compose: k-truncation first, then the smallest
    prefix of the remaining distribution whose mass reaches ``top_p``."""
    logits = logits.astype(jnp.float32)
    if greedy:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    if temperature != 1.0:
        logits = logits / jnp.maximum(temperature, 1e-6)
    if top_k is not None and top_k < 1:
        raise ValueError(f"top_k must be >= 1, got {top_k}")
    if top_p is not None and not 0.0 < top_p <= 1.0:
        raise ValueError(f"top_p must be in (0, 1], got {top_p}")
    if top_k is not None and top_k < logits.shape[-1]:
        kth = lax.top_k(logits, top_k)[0][..., -1:]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p is not None and top_p < 1.0:
        desc = jnp.sort(logits, axis=-1)[..., ::-1]
        probs = jax.nn.softmax(desc, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # keep tokens whose EXCLUSIVE cumulative mass is < top_p (the
        # highest-probability token always survives)
        keep = (cum - probs) < top_p
        cutoff = jnp.min(
            jnp.where(keep, desc, jnp.inf), axis=-1, keepdims=True
        )
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(rng, logits, axis=-1).astype(jnp.int32)


def family_of(cfg) -> str:
    """The serving family of a model configuration: ``"lstm"`` (state is a
    fixed-size carry: this module's `decode_one`, `serve.ServeEngine`) or
    ``"decoder"`` (state grows a latent row per token: `models.decoder`,
    `serve.DecoderEngine`). The seam the serve stack dispatches on: an
    engine of either family answers the same calls, and
    `serve.engine.build_engine` picks it here."""
    return getattr(cfg, "family", "lstm")


def fuse_layers(params, cfg: LMConfig):
    """Fuse every layer's gate matrices ONCE (outside the decode scan) — per
    lstm_cell.py's contract that fusing happens once per forward pass.

    Shared with the serving engine (serve/engine.py), which fuses once at
    engine construction and reuses the result for every decode batch."""
    cdtype = None if cfg.cdtype == jnp.float32 else cfg.cdtype
    return [fuse_params(layer, compute_dtype=cdtype) for layer in params["layers"]]


def decode_one(params, fused_layers, cfg: LMConfig, carries, token: jax.Array):
    """One decode step: token [B] int32 → (logits [B, V], new carries).

    Shares the exact cell math with training (`lstm_step` on fused kernels) —
    the decode path cannot drift from the train path.
    """
    x = jnp.take(params["embedding"], token, axis=0)
    new_carries = []
    for fused, carry in zip(fused_layers, carries):
        carry, x = lstm_step(fused, carry, x)
        new_carries.append(carry)
    head = params["head"]
    kernel = params["embedding"].T if cfg.tie_embeddings else head["kernel"]
    # cfg.ldtype, NOT hardcoded f32: the prefill's logits come from
    # lm_forward at cfg.ldtype, and sampling from the prefill's last
    # position must match sampling from a decode step over the same
    # prefix — same precision or near-tied logits argmax differently
    logits = (
        jnp.dot(x.astype(kernel.dtype), kernel,
                preferred_element_type=cfg.ldtype)
        + head["bias"].astype(cfg.ldtype)
    )
    return logits, new_carries


def generate(
    params,
    prompt: jax.Array,
    cfg: LMConfig,
    rng: jax.Array,
    *,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    greedy: bool = False,
) -> jax.Array:
    """Generate continuations: prompt [B, T0] int32 → [B, T0 + N] int32.

    Pure function of (params, prompt, rng) — jit with static
    (cfg, max_new_tokens, temperature, top_k, greedy) via
    :func:`make_generate_fn`.
    """
    B = prompt.shape[0]
    # Inference needs no rematerialisation: remat_chunk is a training-memory
    # device and would reject prompt lengths not divisible by the chunk.
    if cfg.remat_chunk is not None:
        cfg = dataclasses.replace(cfg, remat_chunk=None)
    logits, carries = lm_forward(
        params, prompt, cfg, carries=init_carries(cfg, B)
    )
    rng, sub = jax.random.split(rng)
    token = sample_logits(
        sub, logits[:, -1, :], temperature=temperature, top_k=top_k,
        top_p=top_p, greedy=greedy,
    )

    fused_layers = fuse_layers(params, cfg)

    def step(carry, _):
        rng, token, carries = carry
        logits, carries = decode_one(params, fused_layers, cfg, carries, token)
        rng, sub = jax.random.split(rng)
        nxt = sample_logits(
            sub, logits, temperature=temperature, top_k=top_k,
            top_p=top_p, greedy=greedy,
        )
        return (rng, nxt, carries), token

    if max_new_tokens > 1:
        (_, last, _), toks = lax.scan(
            step, (rng, token, carries), None, length=max_new_tokens - 1
        )
        new = jnp.concatenate([jnp.moveaxis(toks, 0, 1), last[:, None]], axis=1)
    else:
        new = token[:, None]
    return jnp.concatenate([prompt, new], axis=1)


def reference_next_logits(params, tokens, cfg: LMConfig) -> jax.Array:
    """The plain reference for ONE position: logits [V] of the token that
    follows ``tokens`` [T], the whole model in float32 at matmul precision
    "highest" on `lax.scan` — what a greedy pick is judged against when
    two fast paths disagree (a rounding tie at a wide vocabulary in bf16,
    or a bug)."""
    ref = dataclasses.replace(cfg, compute_dtype="float32",
                              logits_dtype="float32", use_pallas=False,
                              remat_chunk=None)
    with jax.default_matmul_precision("highest"):
        logits, _ = lm_forward(params, jnp.asarray(tokens)[None, :], ref)
    return logits[0, -1]


def judge_greedy_divergence(params, cfg: LMConfig, prompt, got, want):
    """Two greedy continuations of ``prompt`` that should be one: equal,
    a rounding TIE, or really different? Two programs (batched windows,
    a single-sequence scan) round near-tied logits differently, so greedy
    identity between them can flip without either being wrong. Judged at
    the FIRST divergence only — after it the two legitimately feed
    different tokens back: a tie when both picks sit within a stated
    tolerance of the `reference_next_logits` maximum. The tolerance is
    ``2^-6 x max|logit|`` where the matmuls take bf16 inputs (bf16
    compute, or a TPU at default precision), ``2^-16 x`` in true float32.

    Returns ``(verdict, detail)``: verdict is ``"equal"``, ``"tie"`` or
    ``"real"``; ``detail`` is the sentence to print."""
    import numpy as np

    got, want = np.asarray(got), np.asarray(want)
    if np.array_equal(got, want):
        return "equal", ""
    if got.shape != want.shape:
        return "real", f"lengths differ: {got.size} vs {want.size}"
    j = int(np.argmax(got != want))
    logits = np.asarray(reference_next_logits(
        params, np.concatenate([np.asarray(prompt), want[:j]]), cfg))
    low_precision = (cfg.compute_dtype == "bfloat16"
                     or jax.default_backend() == "tpu")
    log2_rel = -6 if low_precision else -16
    tol = 2.0 ** log2_rel * float(np.abs(logits).max())
    gap = float(logits.max() - min(logits[got[j]], logits[want[j]]))
    verdict = "tie" if gap <= tol else "real"
    return verdict, (
        f"{verdict.upper()} at token {j}: picks {int(got[j])}/"
        f"{int(want[j])} are {gap:.3g} below the float32 reference "
        f"maximum (tolerance {tol:.3g} = 2^{log2_rel} x max|logit|)")


def make_generate_fn(
    cfg: LMConfig,
    *,
    max_new_tokens: int,
    temperature: float = 1.0,
    top_k: int | None = None,
    top_p: float | None = None,
    greedy: bool = False,
):
    """Jitted generate: fn(params, prompt [B, T0], rng) -> [B, T0 + N]."""

    def fn(params, prompt, rng):
        return generate(
            params, prompt, cfg, rng,
            max_new_tokens=max_new_tokens,
            temperature=temperature, top_k=top_k, top_p=top_p, greedy=greedy,
        )

    return jax.jit(fn)
