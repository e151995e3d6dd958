"""The plain reference: a stacked-LSTM language model in `jax.numpy`.

Float32 throughout, matmul precision "highest", one `lax.scan` over time per
layer, no kernels, no cache, no batching tricks, no dropout (evaluation
mode). It follows the textbook cell the program documents
(`ops/lstm_cell.py`):

    i, f, o = sigmoid(x W_* + h U_* + b_*)      g = tanh(x W_g + h U_g + b_g)
    c' = f * c + i * g                          h' = o * tanh(c')

and reads the program's parameter tree as it is: ``embedding`` [V, E],
``layers`` (per layer the twelve per-gate arrays W_i..b_o) and ``head``
(``kernel`` [H, V], ``bias`` [V]). Nothing here imports the program.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

GATES = ("i", "f", "g", "o")


def _gate_arrays(layer):
    """(W [D,4H], U [H,4H], b [4H]) from a layer's twelve per-gate arrays
    (attributes ``W_i`` .. ``b_o``)."""
    get = lambda k: getattr(layer, k)  # noqa: E731
    f32 = lambda x: jnp.asarray(x, jnp.float32)  # noqa: E731
    W = jnp.concatenate([f32(get(f"W_{g}")) for g in GATES], axis=1)
    U = jnp.concatenate([f32(get(f"U_{g}")) for g in GATES], axis=1)
    b = jnp.concatenate([f32(get(f"b_{g}")) for g in GATES], axis=0)
    return W, U, b


def _layer(W, U, b, xs, h0, c0):
    """xs [B,T,D] -> ys [B,T,H], final (h, c)."""
    H = U.shape[0]

    def step(carry, x):
        h, c = carry
        z = x @ W + h @ U + b
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H:2 * H])
        g = jnp.tanh(z[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H:])
        c = f * c + i * g
        h = o * jnp.tanh(c)
        return (h, c), h

    (h, c), ys = jax.lax.scan(step, (h0, c0), jnp.swapaxes(xs, 0, 1))
    return jnp.swapaxes(ys, 0, 1), (h, c)


def hidden_states(params, tokens, carries=None):
    """tokens [B,T] int32 -> (top layer's outputs [B,T,H], final carries)."""
    with jax.default_matmul_precision("highest"):
        xs = jnp.asarray(params["embedding"], jnp.float32)[tokens]
        B = tokens.shape[0]
        finals = []
        for n, layer in enumerate(params["layers"]):
            W, U, b = _gate_arrays(layer)
            H = U.shape[0]
            if carries is None:
                h0 = c0 = jnp.zeros((B, H), jnp.float32)
            else:
                h0, c0 = (jnp.asarray(x, jnp.float32) for x in carries[n])
            xs, fin = _layer(W, U, b, xs, h0, c0)
            finals.append(fin)
        return xs, finals


def logits(params, tokens, carries=None):
    """tokens [B,T] -> float32 logits [B,T,V]."""
    ys, _ = hidden_states(params, tokens, carries)
    with jax.default_matmul_precision("highest"):
        return (ys @ jnp.asarray(params["head"]["kernel"], jnp.float32)
                + jnp.asarray(params["head"]["bias"], jnp.float32))


def loss(params, inputs, targets):
    """Mean next-token cross-entropy over B*T tokens and max|logit|."""
    z = logits(params, inputs)
    lse = jax.nn.logsumexp(z, axis=-1)
    tgt = jnp.take_along_axis(z, targets[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - tgt), jnp.max(jnp.abs(z))


def judge_greedy(params, consumed, produced, *, rel_tol: float, carries=None):
    """Teacher-forced judgement of greedy tokens. ``consumed`` is every token
    the model had read, from ``carries`` (per layer (h, c), each [H]; zeros
    if None), before the first produced one; each token of
    ``produced`` must be the reference's argmax at its position or lie
    within ``rel_tol * max|logit|`` of it. Returns (ok, exact, ties, worst)
    where ``worst`` is the largest gap as a share of max|logit|."""
    import numpy as np

    consumed = np.asarray(consumed, np.int32)
    produced = np.asarray(produced, np.int32)
    stream = np.concatenate([consumed, produced[:-1]])
    n = stream.size
    padded = 1 << max(int(n - 1).bit_length(), 6)  # few distinct programs
    tokens = np.zeros((1, padded), np.int32)
    tokens[0, :n] = stream
    if carries is not None:
        carries = [(h[None, :], c[None, :]) for h, c in carries]
    z = np.asarray(_logits_jit(params, jnp.asarray(tokens), carries))[0]
    rows = z[consumed.size - 1:n]                 # one row per produced token
    top = rows.max(axis=-1)
    got = rows[np.arange(produced.size), produced]
    scale = np.abs(rows).max(axis=-1)
    gap = (top - got) / scale
    exact = int((gap == 0).sum())
    ties = int(((gap > 0) & (gap <= rel_tol)).sum())
    return bool((gap <= rel_tol).all()), exact, ties, float(gap.max())


_logits_jit = jax.jit(logits)
