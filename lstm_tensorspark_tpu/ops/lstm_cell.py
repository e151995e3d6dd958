"""Hand-rolled LSTM cell as pure functions on an explicit parameter pytree.

Reference parity: SURVEY.md §2 "LSTM cell (hand-rolled)" [D] — per-gate affine
transforms + nonlinearities (input i, forget f, output o, cell-candidate g;
``c' = f*c + i*g``, ``h' = o*tanh(c')``) with explicit gate weight matrices
``W_i, W_f, W_g, W_o`` (+ recurrent ``U_*``, biases ``b_*``). The reference
mount was empty during the survey (SURVEY.md §0), so the gate math follows the
driver-confirmed description [D] with standard defaults (forget-gate bias 1.0).

TPU-first design (NOT a translation of the reference's per-gate TF matmuls):
parameters are *stored* per-gate for parity and inspection, but *fused* into a
single ``(D, 4H)`` input kernel / ``(H, 4H)`` recurrent kernel before the
sequence scan, so each recurrence step is two MXU-shaped matmuls instead of
eight small ones. Cell state ``c`` stays float32; matmuls optionally run in
bfloat16 with float32 accumulation (``preferred_element_type``).
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp

GATE_ORDER = ("i", "f", "g", "o")  # input, forget, cell-candidate, output


class LSTMParams(NamedTuple):
    """Per-gate LSTM parameters (the reference's explicit gate matrices).

    Shapes: W_* (input_size, hidden), U_* (hidden, hidden), b_* (hidden,).
    """

    W_i: jax.Array
    W_f: jax.Array
    W_g: jax.Array
    W_o: jax.Array
    U_i: jax.Array
    U_f: jax.Array
    U_g: jax.Array
    U_o: jax.Array
    b_i: jax.Array
    b_f: jax.Array
    b_g: jax.Array
    b_o: jax.Array

    @property
    def input_size(self) -> int:
        return self.W_i.shape[0]

    @property
    def hidden_size(self) -> int:
        return self.W_i.shape[1]


class FusedLSTMParams(NamedTuple):
    """Gate-fused view: kernel (D, 4H), recurrent (H, 4H), bias (4H,)."""

    kernel: jax.Array
    recurrent: jax.Array
    bias: jax.Array

    @property
    def hidden_size(self) -> int:
        return self.recurrent.shape[0]


def _orthogonal(key: jax.Array, shape, dtype) -> jax.Array:
    return jax.nn.initializers.orthogonal()(key, shape, dtype)


def _glorot(key: jax.Array, shape, dtype) -> jax.Array:
    return jax.nn.initializers.glorot_uniform()(key, shape, dtype)


def init_lstm_params(
    key: jax.Array,
    input_size: int,
    hidden_size: int,
    *,
    dtype=jnp.float32,
    forget_bias: float = 1.0,
) -> LSTMParams:
    """Initialize per-gate parameters.

    Glorot-uniform input kernels, orthogonal recurrent kernels, zero biases
    except the forget gate (``forget_bias``, default 1.0 — the standard
    default assumed for the reference per SURVEY.md §7 "Hard parts").
    """
    kW = jax.random.split(key, 8)
    Ws = [_glorot(kW[j], (input_size, hidden_size), dtype) for j in range(4)]
    Us = [_orthogonal(kW[4 + j], (hidden_size, hidden_size), dtype) for j in range(4)]
    # an array per bias: leaves that share a buffer cannot be donated
    biases = [jnp.full((hidden_size,), b, dtype) for b in (0.0, forget_bias, 0.0, 0.0)]
    return LSTMParams(*Ws, *Us, *biases)


def fuse_params(params: LSTMParams, *, compute_dtype=None) -> FusedLSTMParams:
    """Concatenate per-gate matrices into MXU-shaped fused kernels.

    Done once per forward pass (outside the scan), so the per-step work is a
    single ``x @ (D,4H)`` plus ``h @ (H,4H)``. Gate order is i, f, g, o.
    """
    kernel = jnp.concatenate([params.W_i, params.W_f, params.W_g, params.W_o], axis=1)
    recurrent = jnp.concatenate([params.U_i, params.U_f, params.U_g, params.U_o], axis=1)
    bias = jnp.concatenate([params.b_i, params.b_f, params.b_g, params.b_o])
    if compute_dtype is not None:
        kernel = kernel.astype(compute_dtype)
        recurrent = recurrent.astype(compute_dtype)
    return FusedLSTMParams(kernel, recurrent, bias)


def lstm_step(
    fused: FusedLSTMParams,
    carry: tuple[jax.Array, jax.Array],
    x: jax.Array,
) -> tuple[tuple[jax.Array, jax.Array], jax.Array]:
    """One recurrence step on fused params.

    carry = (h, c) each [B, H] (h stored in compute dtype, c in float32);
    x is [B, D]. Returns ((h', c'), h').
    """
    h, c = carry
    dtype = fused.kernel.dtype
    z = jnp.dot(x.astype(dtype), fused.kernel, preferred_element_type=jnp.float32)
    z = z + jnp.dot(h.astype(dtype), fused.recurrent, preferred_element_type=jnp.float32)
    z = z + fused.bias
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return (h_new, c_new), h_new


def lstm_step_hoisted(
    fused: FusedLSTMParams,
    carry: tuple[jax.Array, jax.Array],
    zx: jax.Array,
) -> tuple[tuple[jax.Array, jax.Array], jax.Array]:
    """Recurrence step on a PRE-PROJECTED input: ``zx = x @ kernel + bias``
    [B, 4H] float32, computed for all T steps in one MXU matmul before the
    scan (ops/scan.py). Leaves only the unavoidable sequential work —
    ``h @ recurrent`` + gate nonlinearities — inside the loop, halving the
    per-iteration matmul count (the standard cuDNN-style LSTM split)."""
    h, c = carry
    dtype = fused.recurrent.dtype
    z = zx + jnp.dot(
        h.astype(dtype), fused.recurrent, preferred_element_type=jnp.float32
    )
    i, f, g, o = jnp.split(z, 4, axis=-1)
    i = jax.nn.sigmoid(i)
    f = jax.nn.sigmoid(f)
    g = jnp.tanh(g)
    o = jax.nn.sigmoid(o)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return (h_new, c_new), h_new


def lstm_step_unfused(
    params: LSTMParams,
    carry: tuple[jax.Array, jax.Array],
    x: jax.Array,
) -> tuple[tuple[jax.Array, jax.Array], jax.Array]:
    """Reference-shaped step: eight per-gate matmuls (SURVEY.md §3.2).

    Kept as the parity/readability form and as the oracle for tests; the
    production path is :func:`lstm_step` on fused kernels — both compute the
    same math.
    """
    h, c = carry
    i = jax.nn.sigmoid(x @ params.W_i + h @ params.U_i + params.b_i)
    f = jax.nn.sigmoid(x @ params.W_f + h @ params.U_f + params.b_f)
    g = jnp.tanh(x @ params.W_g + h @ params.U_g + params.b_g)
    o = jax.nn.sigmoid(x @ params.W_o + h @ params.U_o + params.b_o)
    c_new = f * c + i * g
    h_new = o * jnp.tanh(c_new)
    return (h_new, c_new), h_new


def zero_carry(batch: int, hidden_size: int, dtype=jnp.float32):
    h = jnp.zeros((batch, hidden_size), dtype)
    c = jnp.zeros((batch, hidden_size), jnp.float32)
    return (h, c)
