"""Sequence unrolling of the LSTM cell with `jax.lax.scan`.

Reference parity: SURVEY.md §3.2 — the reference unrolls the recurrence in a
Python ``for t in 1..T`` loop re-executed per batch through TF ``session.run``.
TPU-native replacement: the recurrence is a `lax.scan`, traced once and
compiled by XLA into a single on-device loop (static shapes, no per-step host
round-trips).

Long-sequence memory (SURVEY.md §7 "Hard parts"): BPTT through T steps stores
O(T) activations; ``remat_chunk`` wraps fixed-size chunks of the scan in
`jax.checkpoint`, storing only O(T/chunk) boundary carries and recomputing
inside chunks during the backward pass — the scan-with-remat crux kernel.

Variable-length sequences (SURVEY.md §7): a boolean ``mask`` freezes the carry
at padded steps, so the final (h, c) is each sequence's state at its true end,
and reversed scans over right-padded batches stay correct.

BPTT modes (``bptt=``): ``"sequential"`` (default) differentiates through
the scan with the ordinary reverse-mode transpose — a T-deep chain;
``"assoc"`` swaps in the parallel-scan backward of ops/parallel_scan.py
(BPPSA-style: the adjoint chain is an associative scan of per-step
Jacobian operators, O(log T) depth); ``"auto"`` picks assoc only when the
`parallel_scan.plan_bytes` memory model fits and T >= its threshold,
counting every fallback. Forward values are identical in every mode.

Masked + remat interaction: both the mask reshape and the chunked scan
require ``T % remat_chunk == 0`` — a silent tail chunk would give the two
bptt modes different step groupings for the same inputs, so indivisible
T raises instead (same error from `parallel_scan.assoc_lstm_scan`).
"""

from __future__ import annotations

import os
import sys
from typing import Sequence

import jax
import jax.numpy as jnp
from jax import lax

from .lstm_cell import (
    LSTMParams,
    fuse_params,
    lstm_step_hoisted,
    zero_carry,
)


def lstm_scan(
    params: LSTMParams,
    xs: jax.Array,
    carry: tuple[jax.Array, jax.Array] | None = None,
    *,
    mask: jax.Array | None = None,
    reverse: bool = False,
    remat_chunk: int | None = None,
    compute_dtype=None,
    unroll: int = 1,
    bptt: str = "sequential",
):
    """Run the LSTM over a batch of sequences.

    Args:
      params: per-gate `LSTMParams` (fused once here, outside the scan).
      xs: inputs ``[B, T, D]`` (batch-major).
      carry: optional initial ``(h, c)`` each ``[B, H]``; zeros if None.
      mask: optional bool ``[B, T]``; False steps leave the carry unchanged.
      reverse: scan right-to-left (for the backward direction of a bi-LSTM).
      remat_chunk: if set, chunk size for `jax.checkpoint` rematerialisation
        (T must be divisible by it).
      compute_dtype: e.g. ``jnp.bfloat16`` for the matmuls; cell state and
        accumulation stay float32.
      unroll: `lax.scan` unroll factor (amortises loop overhead on TPU).
      bptt: ``"sequential"`` | ``"assoc"`` | ``"auto"`` — how the backward
        pass runs (module docstring; ops/parallel_scan.py). Values are
        mode-independent; gradients agree to numerical tolerance
        (tests/test_parallel_scan.py, tests/test_property_scan.py).

    Returns:
      ``((h_T, c_T), ys)`` with ``ys`` ``[B, T, H]`` (hidden state per step).
    """
    B, T, _ = xs.shape
    if bptt != "sequential":
        from .parallel_scan import assoc_lstm_scan, resolve_bptt

        if resolve_bptt(bptt, B, T, params.hidden_size,
                        remat_chunk=remat_chunk) == "assoc":
            return assoc_lstm_scan(
                params, xs, carry, mask=mask, reverse=reverse,
                remat_chunk=remat_chunk, compute_dtype=compute_dtype,
                unroll=unroll,
            )
    fused = fuse_params(params, compute_dtype=compute_dtype)
    if carry is None:
        carry = zero_carry(B, params.hidden_size)

    xs_t = jnp.moveaxis(xs, 0, 1)  # [T, B, D] — scan runs over the leading axis

    def project(x_td):
        # Input projection for a whole [t, B, D] block in ONE MXU matmul —
        # hoisted out of the scan so the sequential loop only carries the
        # unavoidable h @ recurrent (cuDNN-style split). float32 out.
        z = jnp.dot(
            x_td.astype(fused.kernel.dtype),
            fused.kernel,
            preferred_element_type=jnp.float32,
        )
        return z + fused.bias

    def step(c, inp):
        if mask is None:
            new_carry, y = lstm_step_hoisted(fused, c, inp)
        else:
            zx, m = inp
            (h_new, c_new), _ = lstm_step_hoisted(fused, c, zx)
            h = jnp.where(m, h_new, c[0])
            cc = jnp.where(m, c_new, c[1])
            new_carry, y = (h, cc), h
        return new_carry, y

    def with_mask(zx_t):
        if mask is None:
            return zx_t
        return (zx_t, jnp.moveaxis(mask, 0, 1)[..., None])

    if remat_chunk is None:
        final, ys = lax.scan(
            step, carry, with_mask(project(xs_t)), reverse=reverse, unroll=unroll
        )
    else:
        if T % remat_chunk != 0:
            raise ValueError(
                f"T={T} not divisible by remat_chunk={remat_chunk} — a "
                "tail chunk would silently change remat (and bptt-mode) "
                "semantics; pad or pick a divisor")
        n_chunks = T // remat_chunk

        def chunk_fn(c, chunk_inputs):
            # project per chunk, INSIDE the checkpoint: the [chunk, B, 4H]
            # activations are rematerialised, not stored — keeps the remat
            # memory bound at O(T/chunk) carries.
            x_td, m = chunk_inputs if mask is not None else (chunk_inputs, None)
            zx = project(x_td)
            inp = zx if m is None else (zx, m)
            return lax.scan(step, c, inp, reverse=reverse, unroll=unroll)

        chunk_fn = jax.checkpoint(chunk_fn, prevent_cse=False)
        inputs = xs_t if mask is None else (xs_t, jnp.moveaxis(mask, 0, 1)[..., None])
        chunked = jax.tree.map(
            lambda a: a.reshape(n_chunks, remat_chunk, *a.shape[1:]), inputs
        )
        final, ys = lax.scan(chunk_fn, carry, chunked, reverse=reverse)
        ys = ys.reshape(T, B, ys.shape[-1])

    return final, jnp.moveaxis(ys, 0, 1)


def recurrence_path(
    batch: int,
    seq_len: int,
    d_in: int,
    hidden: int,
    *,
    use_pallas: bool,
    compute_dtype=None,
    has_mask: bool = False,
    remat_chunk: int | None = None,
    bptt: str = "sequential",
    bidir: bool = False,
) -> tuple[str, str]:
    """THE dispatch decision of `auto_lstm_scan` / `bidir_lstm_scan` for
    one layer of these shapes, as ``(path, note)``: ``path`` is
    ``"bilstm"`` (stacked-direction kernel), ``"pallas"`` (fused
    single-direction kernels) or ``"scan"`` (`lax.scan`); ``note`` says
    which kernel strategies, or why not — the line the CLI's ``start``
    record carries, so nobody has to guess which recurrence ran. A pure
    function of shapes, flags and `jax.default_backend()`."""
    if not use_pallas:
        return "scan", "lax.scan (--use-pallas not given)"
    if bptt == "assoc":
        return "scan", "lax.scan (--bptt-mode assoc overrides --use-pallas)"
    from . import pallas_lstm as pk

    pbytes = 2 if compute_dtype == jnp.bfloat16 else 4
    shape = f"B={batch} T={seq_len} H={hidden} {pbytes} bytes/param"
    if (bidir and remat_chunk is None
            and os.environ.get("LSTM_TSP_NO_BIDIR_FUSE") != "1"):
        from .pallas_bilstm import bilstm_supported

        if bilstm_supported(batch, hidden, d_in, seq_len,
                            param_dtype_bytes=pbytes, has_mask=has_mask):
            return "bilstm", f"pallas stacked bi-LSTM residentx ({shape})"
    if not pk.supported(batch, hidden, param_dtype_bytes=pbytes,
                        has_mask=has_mask):
        platform = jax.default_backend()
        if platform != "tpu":
            why = f"the kernels are TPU programs and this is {platform}"
        elif batch % 8:
            why = "the per-device batch is not a multiple of 8"
        else:
            why = "no kernel strategy fits VMEM"
        return "scan", f"lax.scan ({why}; {shape})"
    hp = pk._pad_to_lane(hidden)
    dp = pk._pad_to_lane(d_in) if seq_len >= pk._FUSEDX_MIN_T else None
    bwd = pk.chosen_bwd_strategy(batch, seq_len, hp, pbytes,
                                 has_mask=has_mask, Dp=dp,
                                 remat_chunk=remat_chunk)
    fused_bwd = bwd != "recompute"
    fwd = pk._plan_fwd(
        batch, hp, pbytes, save_residuals=fused_bwd, has_mask=has_mask,
        Dp=dp if bwd == "residentx" or not fused_bwd else None)
    if not fused_bwd:
        bwd = ("recompute lax.scan (--remat-chunk)" if remat_chunk
               else "recompute lax.scan (no fused backward fits)")
    return "pallas", f"pallas fwd={fwd[0]} bwd={bwd} ({shape})"


#: every distinct note a trace has taken, in order (insertion-ordered set)
_TRACED_PATHS: dict[str, None] = {}


def _note_traced(note: str) -> None:
    """Log a recurrence path the first time a trace takes it."""
    if note not in _TRACED_PATHS:
        _TRACED_PATHS[note] = None
        print(f"recurrence: {note}", file=sys.stderr, flush=True)


def traced_paths() -> list[str]:
    """The recurrence paths traced so far in this process — what RAN, as
    opposed to `recurrence_path`'s prediction for the ``start`` record."""
    return list(_TRACED_PATHS)


def auto_lstm_scan(
    params: LSTMParams,
    xs: jax.Array,
    carry: tuple[jax.Array, jax.Array] | None = None,
    *,
    mask: jax.Array | None = None,
    reverse: bool = False,
    use_pallas: bool = False,
    compute_dtype=None,
    remat_chunk: int | None = None,
    unroll: int = 1,
    bptt: str = "sequential",
):
    """`lstm_scan` with optional fused-Pallas dispatch.

    When ``use_pallas`` and the shapes/platform pass the kernel's VMEM cost
    model (`pallas_lstm.supported`), runs the fused `pallas_lstm_scan` —
    which now covers masked AND reversed scans, so the bi-LSTM classifier
    and seq2seq decoder recurrences take the fused path too; otherwise
    falls back to the plain `lax.scan`. Same signature contract as
    `lstm_scan`; returns ``((hT, cT), ys)``.

    Precedence with ``bptt``: an EXPLICIT ``bptt="assoc"`` wins over the
    Pallas forward dispatch (the caller asked for the parallel-scan
    backward, which the fused forward kernel does not provide);
    ``bptt="auto"`` defers to the Pallas kernel when it engages — pinning
    one fast path must not silently disable the other — and only
    consults the assoc plan on the `lstm_scan` fallback.
    """
    if bptt == "assoc":
        return lstm_scan(
            params, xs, carry, mask=mask, reverse=reverse,
            compute_dtype=compute_dtype, remat_chunk=remat_chunk,
            unroll=unroll, bptt=bptt,
        )
    if use_pallas:
        B, T, D = xs.shape
        path, note = recurrence_path(
            B, T, D, params.hidden_size, use_pallas=True,
            compute_dtype=compute_dtype, has_mask=mask is not None,
            remat_chunk=remat_chunk, bptt=bptt)
        _note_traced(note)
        if path == "pallas":
            from .pallas_lstm import pallas_lstm_scan

            return pallas_lstm_scan(
                params, xs, carry, mask=mask, reverse=reverse,
                compute_dtype=compute_dtype, remat_chunk=remat_chunk,
                unroll=unroll,
            )
    return lstm_scan(
        params, xs, carry, mask=mask, reverse=reverse,
        compute_dtype=compute_dtype, remat_chunk=remat_chunk, unroll=unroll,
        bptt=bptt,
    )


def bidir_lstm_scan(
    params_fwd: LSTMParams,
    params_bwd: LSTMParams,
    xs: jax.Array,
    *,
    mask: jax.Array | None = None,
    use_pallas: bool = False,
    compute_dtype=None,
    remat_chunk: int | None = None,
    unroll: int = 1,
    bptt: str = "sequential",
):
    """Both directions of one bi-LSTM layer (VERDICT r3 item 2).

    When ``use_pallas`` and the stacked-direction kernel's plan fits
    (`pallas_bilstm.bilstm_supported` — residentx-class shapes: long T,
    VMEM/HBM budgets, no remat memory priority), BOTH chains advance in
    ONE fused `pallas_call`, halving the serialized chain count per
    layer. Otherwise: two `auto_lstm_scan` calls (which keep the full
    per-direction strategy lattice, including the recompute fallback).
    ``LSTM_TSP_NO_BIDIR_FUSE=1`` disables the stacked path (A/B lever
    for benchmarking the fusion itself).

    Returns ``(((hT_f, cT_f), ys_f), ((hT_b, cT_b), ys_b))``.
    """
    B, T, D = xs.shape
    if use_pallas and params_fwd.hidden_size == params_bwd.hidden_size:
        path, note = recurrence_path(
            B, T, D, params_fwd.hidden_size, use_pallas=True,
            compute_dtype=compute_dtype, has_mask=mask is not None,
            remat_chunk=remat_chunk, bptt=bptt, bidir=True)
        if path == "bilstm":
            from .pallas_bilstm import pallas_bilstm_scan

            _note_traced(note)
            return pallas_bilstm_scan(
                params_fwd, params_bwd, xs, mask=mask,
                compute_dtype=compute_dtype,
            )
    out_f = auto_lstm_scan(
        params_fwd, xs, mask=mask, use_pallas=use_pallas,
        compute_dtype=compute_dtype, remat_chunk=remat_chunk, unroll=unroll,
        bptt=bptt,
    )
    out_b = auto_lstm_scan(
        params_bwd, xs, mask=mask, reverse=True, use_pallas=use_pallas,
        compute_dtype=compute_dtype, remat_chunk=remat_chunk, unroll=unroll,
        bptt=bptt,
    )
    return out_f, out_b


def stacked_lstm_scan(
    layer_params: Sequence[LSTMParams],
    xs: jax.Array,
    carries: Sequence[tuple[jax.Array, jax.Array]] | None = None,
    *,
    mask: jax.Array | None = None,
    dropout_rate: float = 0.0,
    dropout_rng: jax.Array | None = None,
    deterministic: bool = True,
    **scan_kwargs,
):
    """Stack LSTM layers over the same time axis (SURVEY.md §2 "Multi-layer").

    Inter-layer dropout is applied to the full ``[B, T, H]`` output between
    layers (not on the recurrent path). Returns (list of per-layer final
    carries, top-layer outputs ``[B, T, H]``).
    """
    use_pallas = scan_kwargs.pop("use_pallas", False)
    ys = xs
    finals = []
    n = len(layer_params)
    for idx, p in enumerate(layer_params):
        c0 = None if carries is None else carries[idx]
        final, ys = auto_lstm_scan(
            p, ys, c0, mask=mask, use_pallas=use_pallas,
            reverse=scan_kwargs.get("reverse", False),
            compute_dtype=scan_kwargs.get("compute_dtype"),
            remat_chunk=scan_kwargs.get("remat_chunk"),
            unroll=scan_kwargs.get("unroll", 1),
            bptt=scan_kwargs.get("bptt", "sequential"),
        )
        finals.append(final)
        if idx < n - 1 and dropout_rate > 0.0 and not deterministic:
            if dropout_rng is None:
                raise ValueError("dropout_rng required when deterministic=False")
            from .masking import dropout

            dropout_rng, ys = dropout(dropout_rng, dropout_rate, ys)
    return finals, ys
