"""The LM head and its loss, two ways: dense and vocab-chunked.

Both compute the mean next-token NLL ``mean(logsumexp(ys·W + b) - z_target)``
(the reference's plain softmax cross-entropy, SURVEY.md §3.2
``xent(softmax(h·W_out), y)``) with a hand-written backward, and differ in
what they keep in HBM. Exactness tests: tests/test_xent.py.

``dense_xent_mean`` — the path every configuration below
`models/lstm_lm._CHUNKED_XENT_MIN_V` runs (configs 1, 3 and 5). The
``[N, V]`` logits array exists (819 MB in bf16 at config 5's N = 8192,
V = 50,000). On a TPU a train step writes it once and reads it twice, and
every reduction over it happens inside a matmul that holds the tile in VMEM
(`ops/pallas_xent.py`): ``lm_head_fwd`` writes the logits with their
logsumexp and target logit, ``lm_head_dx`` forms dlogits from
``(logits, lse, targets)`` for ``dys`` and sums the bias gradient, and
XLA's weight-gradient matmul forms the same dlogits in its own operand. No
dlogits array is stored and none is copied into a second layout (the
autodiff backward wrote dlogits, and XLA then wrote it again in the layout
the other matmul preferred: PERF.md §6). Where `pallas_xent.plan`
says no — another backend, a width off the MXU's 128 lanes, a head the TPU
stores row-major, rows no tile divides, a ``shard_map`` that leaves a mesh
axis automatic (the tensor-, sequence- and pipeline-parallel steps without
``use_pallas``: Mosaic lowers no kernel there), a head the data-parallel step
gathers (its weight gradient is faster after XLA's logits) — XLA runs the same
algorithm: logits matmul, logsumexp and target logit, the bias gradient,
and the two backward matmuls, each forming dlogits as it reads.

``chunked_xent_mean`` — above that threshold: the ``[N, V]`` logits never
exist in HBM. The vocabulary is processed in ``chunk``-column tiles:

- forward: one pass of ONLINE logsumexp (flash-attention-style running
  (m, s) accumulators) + in-chunk target-logit gather — the only [N, Vc]
  tile alive is the current one;
- backward (custom VJP): recompute each chunk's logits, form its dlogits
  tile, and immediately contract it into dys / dW / db accumulators.

The trade is the standard recompute-vs-traffic one: head matmul FLOPs ×2
(the backward re-projects each chunk) against the passes over the logits.
Measured on v5e at V=33k/50k it is 16-18% SLOWER than the dense path, so
it is a memory capability for vocabularies whose logits would not fit, not
a throughput optimisation. XLA's job remains the matmuls; this is pure
jax-level restructuring (lax.scan over weight column tiles), no Pallas
needed — the tiles are large MXU-friendly matmuls already.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from . import pallas_xent
from .embedding import selected_logits


def _pad_vocab(kernel, bias, chunk):
    """Pad V up to a multiple of ``chunk``. Padded columns get bias -1e30,
    so their softmax mass underflows to exactly 0 and the online logsumexp
    ignores them (no target ever points at a padded id)."""
    V = kernel.shape[1]
    pad = -V % chunk
    if pad:
        kernel = jnp.pad(kernel, ((0, 0), (0, pad)))
        bias = jnp.pad(bias, (0, pad), constant_values=-1e30)
    return kernel, bias, V + pad


def _chunk_logits(ys, k_tile, b_tile):
    return (
        jnp.dot(ys.astype(k_tile.dtype), k_tile,
                preferred_element_type=jnp.float32)
        + b_tile
    )


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def chunked_xent_mean(ys, kernel, bias, targets, chunk: int = 8192):
    """Mean next-token NLL over all N = B·T positions, logits never
    materialised. ``ys`` [B, T, H] (float), ``kernel`` [H, V], ``bias``
    [V], ``targets`` [B, T] int32. Returns a scalar; grads flow to
    ys/kernel/bias via the recompute backward."""
    loss, _ = _xent_fwd_pass(ys, kernel, bias, targets, chunk)
    return loss


def _xent_fwd_pass(ys, kernel, bias, targets, chunk):
    B, T, H = ys.shape
    N = B * T
    ys_f = ys.reshape(N, H)
    tgt = targets.reshape(N)
    kernel_p, bias_p, Vp = _pad_vocab(kernel, bias, chunk)
    K = Vp // chunk
    k_tiles = kernel_p.T.reshape(K, chunk, H)  # [K, Vc, H] (scan-sliced)
    b_tiles = bias_p.reshape(K, chunk)

    def body(carry, tile):
        m, s, tl = carry
        k_t, b_t, c0 = tile
        logits = _chunk_logits(ys_f, k_t.T, b_t)  # [N, Vc]
        m_new = jnp.maximum(m, jnp.max(logits, axis=-1))
        s = s * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(logits - m_new[:, None]), axis=-1
        )
        idx = tgt - c0
        in_chunk = (idx >= 0) & (idx < chunk)
        got = jnp.take_along_axis(
            logits, jnp.clip(idx, 0, chunk - 1)[:, None], axis=-1
        )[:, 0]
        tl = jnp.where(in_chunk, got, tl)
        return (m_new, s, tl), None

    init = (
        jnp.full((N,), -jnp.inf, jnp.float32),
        jnp.zeros((N,), jnp.float32),
        jnp.zeros((N,), jnp.float32),
    )
    c0s = jnp.arange(K, dtype=jnp.int32) * chunk
    (m, s, tl), _ = lax.scan(body, init, (k_tiles, b_tiles, c0s))
    lse = m + jnp.log(s)
    loss = jnp.mean(lse - tl)
    return loss, (ys_f, tgt, lse, (B, T, H))


def _xent_fwd(ys, kernel, bias, targets, chunk):
    loss, (ys_f, tgt, lse, dims) = _xent_fwd_pass(ys, kernel, bias, targets,
                                                  chunk)
    return loss, (ys_f, kernel, bias, tgt, lse, dims)


def _xent_bwd(chunk, residuals, g):
    ys_f, kernel, bias, tgt, lse, (B, T, H) = residuals
    N = B * T
    kernel_p, bias_p, Vp = _pad_vocab(kernel, bias, chunk)
    K = Vp // chunk
    k_tiles = kernel_p.T.reshape(K, chunk, H)
    b_tiles = bias_p.reshape(K, chunk)
    gN = (g / N).astype(jnp.float32)  # d(mean)/d(per-token nll)
    cdtype = kernel.dtype

    def body(dys, tile):
        k_t, b_t, c0 = tile
        logits = _chunk_logits(ys_f, k_t.T, b_t)
        # dlogits tile = (softmax - onehot) * g/N; padded cols: softmax
        # underflows to 0 and no target points there, so exactly 0
        p = jnp.exp(logits - lse[:, None])
        idx = tgt - c0
        in_chunk = (idx >= 0) & (idx < chunk)
        onehot = (
            jax.nn.one_hot(jnp.clip(idx, 0, chunk - 1), chunk,
                           dtype=jnp.float32)
            * in_chunk[:, None]
        )
        dlog = (p - onehot) * gN
        dlog_c = dlog.astype(cdtype)
        dk_t = jnp.dot(ys_f.astype(cdtype).T, dlog_c,
                       preferred_element_type=jnp.float32)  # [H, Vc]
        db_t = jnp.sum(dlog, axis=0)
        dys = dys + jnp.dot(dlog_c, k_t.astype(cdtype),
                            preferred_element_type=jnp.float32)
        return dys, (dk_t, db_t)

    c0s = jnp.arange(K, dtype=jnp.int32) * chunk
    dys, (dk_tiles, db_tiles) = lax.scan(
        body, jnp.zeros((N, H), jnp.float32), (k_tiles, b_tiles, c0s)
    )
    V = kernel.shape[1]
    dkernel = jnp.moveaxis(dk_tiles, 0, 1).reshape(H, Vp)[:, :V]
    dbias = db_tiles.reshape(Vp)[:V]
    return (
        dys.reshape(B, T, H).astype(ys_f.dtype),
        dkernel.astype(kernel.dtype),
        dbias.astype(bias.dtype),
        np.zeros((B, T), dtype=jax.dtypes.float0),  # int targets
    )


chunked_xent_mean.defvjp(_xent_fwd, _xent_bwd)


# ---- the dense head + loss: logits in HBM, dlogits in one layout ---------


@functools.partial(jax.custom_vjp, nondiff_argnums=(4,))
def dense_xent_mean(ys, kernel, bias, targets, logits_dtype):
    """Mean next-token NLL with the whole ``[N, V]`` logits array in HBM
    (the fast path below `_CHUNKED_XENT_MIN_V`), and a hand-written
    backward in which dlogits is ONE ``[N, V]`` expression that both
    backward products take in the logits' own layout: on a TPU the
    kernels of `ops/pallas_xent.py` and XLA's weight gradient, elsewhere
    XLA's operations alone (the module's docstring says when).

    ``ys`` [B, T, H] (float), ``kernel`` [H, V], ``bias`` [V], ``targets``
    [B, T] int. ``logits_dtype`` is the dtype the logits are stored in and
    dlogits is rounded to (`LMConfig.ldtype`); logsumexp and the loss are
    float32 over the upcast values. Returns a scalar.

    Why not autodiff: it hands the two transposed matmuls one ``[B, T, V]``
    cotangent array, XLA's layout assignment gives each the layout it
    likes best, and the 819 MB array (config 5) is written a second time
    by a relayout ``copy`` that computes nothing — 2.5 ms of a 43.9 ms
    step on v5e. Here N = T·B is the flattened TIME-MAJOR row axis (the
    recurrence kernels' own order, so flattening ``ys`` moves no data; the
    int targets are transposed instead), dys contracts dlogits' V axis and
    dW its N axis, and dlogits is element-wise in (logits, lse, targets),
    so XLA fuses it into the operand of each matmul: neither the copy nor
    a dlogits array is left in the step (40.0 ms; PERF.md §6, PR 27;
    tests_tpu/test_head_layout_tpu.py holds the compiled step to it). The
    kernels read the head vocabulary-major (``kernel.T``), which is free
    where the TPU stores it with H minor (config 5's ``f32[1024,50000]``
    is stored ``{0,1}``; `pallas_xent.stored_vocab_major`); a tied head
    (``embedding.T``) is read as the embedding it is.
    """
    loss, _ = _dense_fwd(ys, kernel, bias, targets, logits_dtype)
    return loss


def _time_major_rows(ys, targets):
    """``ys`` [B, T, H] → [T·B, H] and ``targets`` [B, T] → [T·B], rows in
    time-major order."""
    B, T, H = ys.shape
    return jnp.swapaxes(ys, 0, 1).reshape(T * B, H), targets.T.reshape(T * B)


def _dense_fwd(ys, kernel, bias, targets, logits_dtype):
    ys2d, tgt = _time_major_rows(ys, targets)
    N, H = ys2d.shape
    plan = pallas_xent.plan(N, H, kernel.shape[1], logits_dtype,
                            head_dtype=kernel.dtype)
    if plan is not None:
        # one kernel writes the logits and reduces them while they are in
        # VMEM: logsumexp and target logit on the stored values, float32
        w = kernel.T
        logits, lse, tl = pallas_xent.lm_head_fwd(ys2d, w, bias, tgt,
                                                  logits_dtype, plan)
        nll = lse - tl
    else:
        w = None
        logits = (
            jnp.dot(ys2d.astype(kernel.dtype), kernel,
                    preferred_element_type=logits_dtype)
            + bias.astype(logits_dtype)
        )
        # nll via logsumexp, NOT log_softmax: identical math (nll = lse -
        # z_t) without an [N, V] log-prob array
        logits_f = logits.astype(jnp.float32)
        lse = jax.nn.logsumexp(logits_f, axis=-1)
        nll = lse - selected_logits(logits_f, tgt)
    # the mean sums in [B, T] order, so the value is bit-for-bit the plain
    # formula's (32 KB transposed, against 819 MB left alone)
    loss = jnp.mean(nll.reshape(targets.shape[::-1]).T)
    return loss, (logits, lse, ys, kernel, bias, targets, w)


def _dense_bwd(logits_dtype, residuals, g):
    logits, lse, ys, kernel, bias, targets, w = residuals
    B, T, H = ys.shape
    N, V = logits.shape
    ys2d, tgt = _time_major_rows(ys, targets)
    gN = (g / N).astype(jnp.float32)
    # dlogits = (softmax - onehot) * g/N, element-wise over the logits and
    # rounded once to the stored dtype (what autodiff handed the matmuls
    # too); db is summed in float32, before the rounding
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    onehot = lax.broadcasted_iota(tgt.dtype, (N, V), 1) == tgt[:, None]
    dlog_f = (p - onehot.astype(jnp.float32)) * gN
    dlog = dlog_f.astype(logits_dtype)
    if w is not None:
        # dys and db from one kernel that forms its dlogits tile in VMEM
        dys, dbias = pallas_xent.lm_head_dx(
            logits, lse, tgt, w, gN,
            pallas_xent.plan(N, H, V, logits_dtype, head_dtype=kernel.dtype))
    else:
        dbias = jnp.sum(dlog_f, axis=0)
        # both products take dlogits as [N, V]: dys contracts V, dW
        # contracts N. Output dtypes as autodiff's transposes had them
        # (preferred_element_type rides along)
        dys = lax.dot_general(dlog, kernel, (((1,), (1,)), ((), ())),
                              preferred_element_type=logits_dtype)
    dkernel = lax.dot_general(ys2d.astype(kernel.dtype), dlog,
                              (((0,), (0,)), ((), ())),
                              preferred_element_type=logits_dtype)
    return (
        jnp.swapaxes(dys.reshape(T, B, H), 0, 1).astype(ys.dtype),
        dkernel.astype(kernel.dtype),
        dbias.astype(bias.dtype),
        np.zeros(targets.shape, dtype=jax.dtypes.float0),  # int targets
    )


dense_xent_mean.defvjp(_dense_fwd, _dense_bwd)
