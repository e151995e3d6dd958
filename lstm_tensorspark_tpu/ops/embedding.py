"""Vocabulary indexing tuned for TPU: gather/scatter vs one-hot matmul.

Reference parity: SURVEY.md §2 "Data pipeline" / "Multi-layer network
wrapper" rows — the reference vectorizes tokens by index and trains an
embedding + softmax head; HOW the lookup runs is an implementation choice
the TPU makes differently.

Why this module exists (measured, not guessed): profiling the config-1
train step on v5e showed 48% of device time in two vocabulary-indexing
kernels — the cross-entropy target-logit gather (43 us/step) and the
embedding-gradient scatter-add (28 us/step) — while the fused Pallas
recurrence pair ran at its roofline (29 us/step combined). TPU gathers and
scatter-adds over the minor dimension serialize; at small vocabularies the
same operation expressed as a one-hot contraction runs on the MXU in ~1 us.

Two helpers, both gated on vocab size:

- ``embed_lookup``: forward stays the bit-identical row gather; at
  V <= _MM_GRAD_MAX_V a custom VJP computes the embedding gradient as
  ``one_hot(tokens)^T @ g`` (an MXU matmul) instead of XLA's scatter-add.
  Above the threshold the one-hot factor itself would dominate (e.g.
  273 MB at V=50k for a 4096-token batch), so the scatter stays.

- ``selected_logits``: ``logits[..., target]`` as a one-hot
  multiply-reduce at small V. XLA fuses the iota/compare one-hot into the
  reduction loop (nothing materializes in HBM) and the backward is
  elementwise — no gather forward, no scatter backward. Above the
  threshold the take_along_axis gather stays: its cost is bounded by
  token count while a second full read of [N, V] logits is not.

Thresholds are conservative 2^11; the configs that matter sit far on
either side (V=26..370 vs V=25k..50k).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

# Above this vocab size the one-hot contraction's [N, V] factor costs more
# (FLOPs and/or HBM traffic) than the serialized gather/scatter it replaces.
_MM_GRAD_MAX_V = 2048
_SELECT_MAX_V = 2048


@functools.partial(jax.custom_vjp, nondiff_argnums=())
def _embed_mm_grad(embedding: jax.Array, tokens: jax.Array) -> jax.Array:
    return jnp.take(embedding, tokens, axis=0)


def _embed_mm_grad_fwd(embedding, tokens):
    return jnp.take(embedding, tokens, axis=0), (tokens, embedding.shape)


def _embed_mm_grad_bwd(res, g):
    tokens, (V, E) = res
    # dE[v, e] = sum_n 1[tokens_n == v] * g[n, e]: contraction over the
    # flattened token axis on the MXU. The one-hot factor holds exact 0/1
    # in any float dtype; products are g or 0, so the result differs from
    # the scatter-add only by float summation order — PROVIDED the MXU
    # does not first round f32 cotangents to bf16 (TPU's DEFAULT matmul
    # precision does exactly that; measured 1.7e-2 max abs error vs the
    # scatter at H=128). HIGHEST keeps f32 operand fidelity, and the
    # matmul is ~1 us at the V<=2048 gate, so exactness is free.
    n = tokens.size
    oh = jax.nn.one_hot(tokens.reshape(n), V, dtype=g.dtype)
    dE = jax.lax.dot_general(
        oh, g.reshape(n, E),
        (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.HIGHEST,
    ).astype(g.dtype)
    return dE, None


_embed_mm_grad.defvjp(_embed_mm_grad_fwd, _embed_mm_grad_bwd)


def embed_lookup(embedding: jax.Array, tokens: jax.Array) -> jax.Array:
    """``embedding[tokens]`` — row gather forward everywhere (bit-identical
    to ``jnp.take``); matmul-backward custom VJP at small vocab."""
    if embedding.shape[0] <= _MM_GRAD_MAX_V:
        return _embed_mm_grad(embedding, tokens)
    return jnp.take(embedding, tokens, axis=0)


def selected_logits(logits: jax.Array, targets: jax.Array) -> jax.Array:
    """``logits[..., targets]`` over the trailing vocab axis: one-hot
    multiply-reduce where it wins, gather elsewhere. targets has logits'
    shape minus the last axis.

    The two forms are BIT-EXACT equal (the sum has one nonzero term, and
    the one-hot backward writes exactly one cotangent per position), so
    the dispatch is pure performance policy: on TPU the one-hot fuses
    into the surrounding loss reduction at ANY vocab size and keeps the
    backward elementwise — measured at V=33k it is neutral with f32
    logits and +20% with bf16 logits, where the gather's backward scatter
    forces an f32 dlogits materialization (the dense LM head's backward
    is hand-written since, ops/xent.py: there only the forward half
    applies). On CPU the fused one-hot pass costs real work at large V
    while the gather is a cheap row lookup, so large-V CPU keeps the
    gather (identical values either way)."""
    V = logits.shape[-1]
    if V <= _SELECT_MAX_V or jax.default_backend() == "tpu":
        oh = jax.nn.one_hot(targets, V, dtype=logits.dtype)
        return jnp.sum(logits * oh, axis=-1)
    return jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
