"""Stacked-direction fused Pallas kernel for one bi-LSTM layer.

Motivation (VERDICT r3 item 2): the bi-LSTM classifier (BASELINE.md
config 2) ran its forward and reverse directions as TWO sequential
`pallas_lstm_scan` invocations — 2T serialized chain steps per layer —
even though the two chains are completely data-independent until the
output concat (models/classifier.py): that serialization was config 2's
binding constraint.

Design: ONE `pallas_call` advances BOTH chains in every sub-step. The
reverse direction is realised exactly as in `pallas_lstm_scan` — a
forward-in-time scan over time-flipped inputs and mask (flips live
outside the custom VJP, so autodiff transposes them for free) — which
makes the two directions the SAME computation with different weights.
Operands are batch-stacked (rows 0:B = forward, B:2B = reverse, so all
VPU gate algebra vectorizes over 2B rows unchanged) while the weights
carry a leading direction axis ([2, Dp, 4H] W, [2, H, 4H] U): each
sub-step issues the two directions' ``h_d @ U_d`` back-to-back. The two
matmuls are data-independent, so the MXU pipelines the second behind
the first instead of waiting a full chain-step latency — the serialized
chain count per layer drops from 2 (fwd direction then rev direction)
to ~1 (both at once).

Strategy: the residentx (fully-fused, recompute-z backward) pair only —
the plan config 2's shape selects. Everything else (short T, VMEM
overflow, remat_chunk memory priority, recompute fallback) falls back
to two single-direction calls at the dispatch layer
(`ops.scan.bidir_lstm_scan`), which keeps its own full strategy
lattice. VMEM planning reuses `pallas_lstm`'s per-buffer cost model at
2B rows plus the second direction's weight copies.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import pallas_lstm as _pl
from .lstm_cell import LSTMParams, fuse_params
from .pallas_lstm import (_LANE, _chunk_for, _pad_params_lane, _pad_to_lane,
                          _residual_dtype)


def _bi_fwd_vmem(B2: int, H: int, Dp: int, pbytes: int, save_c: bool,
                 has_mask: bool, c: int) -> int:
    """Stacked forward = the residentx forward at 2B rows plus the second
    direction's W/U/bias copies (streamed blocks already scale with B2)."""
    return (_pl._residentx_fwd_vmem(B2, H, Dp, pbytes, save_c, has_mask, c)
            + 4 * H * H * pbytes + Dp * 4 * H * pbytes + 4 * H * 4)


def _bi_bwd_vmem(B2: int, H: int, Dp: int, pbytes: int, has_mask: bool,
                 c: int) -> int:
    """Stacked backward = residentx backward at 2B rows plus the second
    direction's W, U (z recompute), U^T (dh carry) and bias copies."""
    return (_pl._residentx_bwd_vmem(B2, H, Dp, pbytes, has_mask, c)
            + 2 * 4 * H * H * pbytes + Dp * 4 * H * pbytes + 4 * H * 4)


def _bi_plan(B: int, H: int, Dp: int, pbytes: int,
             has_mask: bool) -> int | None:
    """Largest VMEM-feasible time chunk for the stacked pair (the TRAIN
    shape: residual-saving forward AND the recompute-z backward must both
    fit at the same chunk), or None when nothing fits."""
    for c in (8, 4, 2, 1):
        if (_bi_fwd_vmem(2 * B, H, Dp, pbytes, True, has_mask,
                         c) <= _pl._VMEM_BUDGET
                and _bi_bwd_vmem(2 * B, H, Dp, pbytes, has_mask,
                                 c) <= _pl._VMEM_BUDGET):
            return c
    return None


def bilstm_supported(batch: int, hidden: int, d_in: int, seq_len: int,
                     platform: str | None = None, *,
                     param_dtype_bytes: int = 4,
                     has_mask: bool = False) -> bool:
    """Can the stacked-direction kernel run this layer? Mirrors
    `pallas_lstm.supported` but for the TRAIN pair at 2B rows, gated on
    the fusedx sequence-length threshold (short sequences prefer the
    hoisted-xproj single-direction kernels — same trade as the
    single-direction `_FUSEDX_MIN_T` gate) and the O(T) cs residual
    fitting the HBM budget at 2B rows."""
    if platform is None:
        platform = jax.default_backend()
    hp = _pad_to_lane(hidden)
    return (
        platform == "tpu"
        and batch % 8 == 0
        and hidden >= 1
        and seq_len >= _pl._FUSEDX_MIN_T
        and _bi_plan(batch, hp, _pad_to_lane(d_in), param_dtype_bytes,
                     has_mask) is not None
        and (seq_len * 2 * batch * hp * 4) <= _pl._RESIDUAL_HBM_BUDGET
    )


# ---------------------------------------------------------------------------
# Kernels. Batch-stacked values (2B rows), direction-stacked weights.
# ---------------------------------------------------------------------------


def _bi_fwdx_kernel(*refs, hidden: int, dpad: int, chunk: int, batch: int,
                    save_c: bool, has_mask: bool):
    """Stacked residentx forward: per grid step, TWO chunk-batched xproj
    matmuls (one per direction's W), then each sequential sub-step issues
    the two directions' ``h_d @ U_d`` back-to-back — independent MXU ops
    the hardware pipelines — and runs the gate algebra once over all 2B
    rows. With ``save_c`` only the cell states stream out (the
    recompute-z backward's sole residual)."""
    n_in = 6 + has_mask
    xs_ref, w_ref, b_ref, u_ref, h0_ref, c0_ref = refs[:6]
    mask_ref = refs[6] if has_mask else None
    ys_ref, hT_ref, cT_ref = refs[n_in:n_in + 3]
    rest = refs[n_in + 3:]
    if save_c:
        cs_ref, h_scr, c_scr = rest
    else:
        h_scr, c_scr = rest
    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden
    B = batch

    @pl.when(t == 0)
    def _():
        h_scr[:] = h0_ref[:]
        c_scr[:] = c0_ref[:]

    xs = xs_ref[:]  # [C, 2B, Dp]
    zx = []
    for d in range(2):
        zd = jnp.dot(
            xs[:, d * B:(d + 1) * B].reshape(-1, dpad).astype(w_ref.dtype),
            w_ref[d], preferred_element_type=jnp.float32,
        ) + b_ref[d]
        zx.append(zd.reshape(chunk, -1, 4 * H))
    h = h_scr[:]
    c = c_scr[:]
    for s in range(chunk):
        z = jnp.concatenate(
            [zx[d][s] + jnp.dot(
                h[d * B:(d + 1) * B].astype(u_ref.dtype), u_ref[d],
                preferred_element_type=jnp.float32,
            ) for d in range(2)],
            axis=0,
        )  # [2B, 4H]
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H:2 * H])
        g = jnp.tanh(z[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H:])
        c_new = f * c + i * g
        h_new = o * jnp.tanh(c_new)
        if has_mask:
            m = mask_ref[s][:, :1]
            c = m * c_new + (1.0 - m) * c
            h = m * h_new + (1.0 - m) * h
        else:
            c = c_new
            h = h_new
        ys_ref[s] = h
        if save_c:
            cs_ref[s] = c
    h_scr[:] = h
    c_scr[:] = c

    @pl.when(t == T - 1)
    def _():
        hT_ref[:] = h
        cT_ref[:] = c


def _bi_bwdx_kernel(*refs, hidden: int, dpad: int, chunk: int, batch: int,
                    has_mask: bool):
    """Stacked recompute-z BPTT: rebuilds both directions' z in-kernel
    (two xproj matmuls per chunk, two ``h_prev_d @ U_d`` per sub-step —
    bit-identical to the forward's f32 values), runs the cotangent
    algebra once over 2B rows, and carries dh through two back-to-back
    ``dz_d @ U_d^T`` matmuls. dU/dW/db/dxs are contracted OUTSIDE per
    direction (`_bi_backward`) — same split as the single-direction
    kernels (`pallas_lstm._lstm_bwdx_kernel`'s rationale)."""
    n_in = 10 + has_mask
    xs_ref, dys_ref, cprev_ref, hprev_ref = refs[:4]
    mask_ref = refs[4] if has_mask else None
    w_ref, b_ref, u_ref, ut_ref, dhT_ref, dcT_ref = refs[4 + has_mask:n_in]
    dz_ref, dh0_ref, dc0_ref = refs[n_in:n_in + 3]
    dh_scr, dc_scr = refs[n_in + 3:]
    t = pl.program_id(0)
    T = pl.num_programs(0)
    H = hidden
    B = batch

    @pl.when(t == 0)
    def _():
        dh_scr[:] = dhT_ref[:]
        dc_scr[:] = dcT_ref[:]

    xs = xs_ref[:]  # [C, 2B, Dp]
    zx = []
    for d in range(2):
        zd = jnp.dot(
            xs[:, d * B:(d + 1) * B].reshape(-1, dpad).astype(w_ref.dtype),
            w_ref[d], preferred_element_type=jnp.float32,
        ) + b_ref[d]
        zx.append(zd.reshape(chunk, -1, 4 * H))
    dh = dh_scr[:]
    dc = dc_scr[:]
    for s in range(chunk - 1, -1, -1):
        hp = hprev_ref[s]
        z = jnp.concatenate(
            [zx[d][s] + jnp.dot(
                hp[d * B:(d + 1) * B].astype(u_ref.dtype), u_ref[d],
                preferred_element_type=jnp.float32,
            ) for d in range(2)],
            axis=0,
        )
        i = jax.nn.sigmoid(z[:, :H])
        f = jax.nn.sigmoid(z[:, H:2 * H])
        g = jnp.tanh(z[:, 2 * H:3 * H])
        o = jax.nn.sigmoid(z[:, 3 * H:])
        c_prev = cprev_ref[s]
        tc = jnp.tanh(f * c_prev + i * g)  # tanh(c_new), recomputed
        dh_tot = dh + dys_ref[s]
        dc_in = dc
        if has_mask:
            m = mask_ref[s][:, :1]
            dh_eff = m * dh_tot
            dc_eff = m * dc_in
        else:
            dh_eff = dh_tot
            dc_eff = dc_in
        dc_new = dc_eff + dh_eff * o * (1.0 - tc * tc)
        do = dh_eff * tc * o * (1.0 - o)
        di = dc_new * g * i * (1.0 - i)
        df = dc_new * c_prev * f * (1.0 - f)
        dg = dc_new * i * (1.0 - g * g)
        dz = jnp.concatenate([di, df, dg, do], axis=1)  # [2B, 4H] f32
        dz_ref[s] = dz.astype(dz_ref.dtype)  # stream dtype
        dh = jnp.concatenate(
            [jnp.dot(
                dz[d * B:(d + 1) * B].astype(ut_ref.dtype), ut_ref[d],
                preferred_element_type=jnp.float32,
            ) for d in range(2)],
            axis=0,
        )
        dc = dc_new * f
        if has_mask:
            # frozen fraction of the cotangents bypasses the gates
            dh = dh + (1.0 - m) * dh_tot
            dc = dc + (1.0 - m) * dc_in
    dh_scr[:] = dh
    dc_scr[:] = dc

    @pl.when(t == T - 1)
    def _():
        dh0_ref[:] = dh
        dc0_ref[:] = dc


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def _stack_weights(fused_f, fused_b, Dp: int):
    """Direction-stacked W [2, Dp, 4H] (rows zero-padded to Dp — exact,
    they multiply zero xs lanes), bias [2, 4H] f32, U [2, H, 4H]."""
    D = fused_f.kernel.shape[0]
    pad = ((0, Dp - D), (0, 0))
    w2 = jnp.stack([jnp.pad(fused_f.kernel, pad),
                    jnp.pad(fused_b.kernel, pad)])
    b2 = jnp.stack([fused_f.bias, fused_b.bias]).astype(jnp.float32)
    u2 = jnp.stack([fused_f.recurrent, fused_b.recurrent])
    return w2, b2, u2


def _bi_forward(fused_f, fused_b, xs2, h0, c0, mask_tbl=None, *,
                save_c: bool = False, interpret: bool = False):
    """xs2 [2B, T, D] (rows B: = the time-flipped reverse direction) →
    (ys2 [2B, T, H], hT [2B, H], cT[, cs]). Residentx strategy only."""
    B2, T, D = xs2.shape
    B = B2 // 2
    H = fused_f.hidden_size
    pbytes = 2 if fused_f.kernel.dtype == jnp.bfloat16 else 4
    has_mask = mask_tbl is not None
    Dp = _pad_to_lane(D)
    cap = _bi_plan(B, H, Dp, pbytes, has_mask)
    if cap is None:
        raise ValueError(f"no stacked bilstm plan for B={B}, H={H}, D={D}")
    C = _chunk_for(T, cap)

    sdtype = _residual_dtype(fused_f.kernel.dtype)
    xs_t = jnp.moveaxis(xs2, 0, 1).astype(sdtype)  # [T, 2B, D]
    if Dp != D:
        xs_t = jnp.pad(xs_t, ((0, 0), (0, 0), (0, Dp - D)))
    w2, b2, u2 = _stack_weights(fused_f, fused_b, Dp)

    in_specs = [
        pl.BlockSpec((C, B2, Dp), lambda t: (t, 0, 0),
                     memory_space=pltpu.VMEM),  # xs
        pl.BlockSpec(memory_space=pltpu.VMEM),  # W [2, Dp, 4H]
        pl.BlockSpec(memory_space=pltpu.VMEM),  # bias [2, 4H]
        pl.BlockSpec(memory_space=pltpu.VMEM),  # U [2, H, 4H]
        pl.BlockSpec(memory_space=pltpu.VMEM),  # h0
        pl.BlockSpec(memory_space=pltpu.VMEM),  # c0
    ]
    operands = [xs_t, w2, b2, u2,
                h0.astype(jnp.float32), c0.astype(jnp.float32)]
    if has_mask:
        in_specs.append(
            pl.BlockSpec((C, B2, _LANE), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM))
        operands.append(mask_tbl)
    out_specs = [
        pl.BlockSpec((C, B2, H), lambda t: (t, 0, 0),
                     memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.VMEM),
        pl.BlockSpec(memory_space=pltpu.VMEM),
    ]
    out_shape = [
        jax.ShapeDtypeStruct((T, B2, H), jnp.float32),
        jax.ShapeDtypeStruct((B2, H), jnp.float32),
        jax.ShapeDtypeStruct((B2, H), jnp.float32),
    ]
    if save_c:
        out_specs.append(
            pl.BlockSpec((C, B2, H), lambda t: (t, 0, 0),
                         memory_space=pltpu.VMEM))
        out_shape.append(jax.ShapeDtypeStruct((T, B2, H), jnp.float32))
    out = pl.pallas_call(
        functools.partial(
            _bi_fwdx_kernel, hidden=H, dpad=Dp, chunk=C, batch=B,
            save_c=save_c, has_mask=has_mask,
        ),
        grid=(T // C,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[
            pltpu.VMEM((B2, H), jnp.float32),  # h
            pltpu.VMEM((B2, H), jnp.float32),  # c
        ],
        interpret=interpret,
        name="bilstm_rec_fwd",
    )(*operands)
    ys2 = jnp.moveaxis(out[0], 0, 1)
    if save_c:
        return ys2, out[1], out[2], out[3]
    return ys2, out[1], out[2]


def _bi_backward(fused_f, fused_b, params_f, params_b, xs2, h0, c0,
                 mask_tbl, ys2, cs, dys2, dhT, dcT, *,
                 interpret: bool = False):
    """Stacked recompute-z BPTT + per-direction outside contractions.
    Returns (dparams_f, dparams_b, dxs2, dh0, dc0)."""
    B2, T, D = xs2.shape
    B = B2 // 2
    H = fused_f.hidden_size
    dtype = fused_f.kernel.dtype
    pbytes = 2 if dtype == jnp.bfloat16 else 4
    has_mask = mask_tbl is not None
    Dp = _pad_to_lane(D)
    cap = _bi_plan(B, H, Dp, pbytes, has_mask)
    if cap is None:
        raise ValueError(f"no stacked bilstm plan for B={B}, H={H}, D={D}")
    C = _chunk_for(T, cap)
    n = T // C
    rev = lambda t: (n - 1 - t, 0, 0)  # noqa: E731 — reverse-time grid

    ys_t = jnp.moveaxis(ys2, 0, 1)  # [T, 2B, H] f32
    h_prev = jnp.concatenate(
        [h0.astype(jnp.float32)[None], ys_t[:-1]], axis=0)
    c_prev = jnp.concatenate(
        [c0.astype(jnp.float32)[None], cs[:-1]], axis=0)
    dys_t = jnp.moveaxis(dys2.astype(jnp.float32), 0, 1)
    sdtype = _residual_dtype(dtype)
    xs_t = jnp.moveaxis(xs2, 0, 1).astype(sdtype)
    if Dp != D:
        xs_t_pad = jnp.pad(xs_t, ((0, 0), (0, 0), (0, Dp - D)))
    else:
        xs_t_pad = xs_t
    w2, b2, u2 = _stack_weights(fused_f, fused_b, Dp)
    ut2 = jnp.stack([fused_f.recurrent.T, fused_b.recurrent.T])

    in_specs = [
        pl.BlockSpec((C, B2, Dp), rev, memory_space=pltpu.VMEM),  # xs
        pl.BlockSpec((C, B2, H), rev, memory_space=pltpu.VMEM),   # dys
        pl.BlockSpec((C, B2, H), rev, memory_space=pltpu.VMEM),   # c_prev
        pl.BlockSpec((C, B2, H), rev, memory_space=pltpu.VMEM),   # h_prev
    ]
    operands = [xs_t_pad, dys_t, c_prev, h_prev]
    if has_mask:
        in_specs.append(
            pl.BlockSpec((C, B2, _LANE), rev, memory_space=pltpu.VMEM))
        operands.append(mask_tbl)
    in_specs += [pl.BlockSpec(memory_space=pltpu.VMEM)] * 6  # w/b/u/ut/dhT/dcT
    operands += [w2, b2, u2, ut2,
                 dhT.astype(jnp.float32), dcT.astype(jnp.float32)]
    dz, dh0, dc0 = pl.pallas_call(
        functools.partial(_bi_bwdx_kernel, hidden=H, dpad=Dp, chunk=C,
                          batch=B, has_mask=has_mask),
        grid=(n,),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((C, B2, 4 * H), rev, memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
            pl.BlockSpec(memory_space=pltpu.VMEM),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, B2, 4 * H), sdtype),  # dz stream
            jax.ShapeDtypeStruct((B2, H), jnp.float32),
            jax.ShapeDtypeStruct((B2, H), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((B2, H), jnp.float32),
            pltpu.VMEM((B2, H), jnp.float32),
        ],
        interpret=interpret,
        name="bilstm_rec_bwd",
    )(*operands)

    # per-direction weight/input cotangents: large MXU contractions over
    # all T·B outside the sequential kernel (same split as pallas_lstm)
    dparams = []
    dxs_parts = []
    for d, (fused, params) in enumerate(
            ((fused_f, params_f), (fused_b, params_b))):
        rows = slice(d * B, (d + 1) * B)
        dz_d = dz[:, rows]
        dz_c = dz_d.astype(dtype)
        dU = jnp.einsum("tbh,tbk->hk", h_prev[:, rows].astype(dtype), dz_c,
                        preferred_element_type=jnp.float32)
        dW = jnp.einsum("tbd,tbk->dk", xs_t[:, rows].astype(dtype), dz_c,
                        preferred_element_type=jnp.float32)
        db = jnp.sum(dz_d, axis=(0, 1), dtype=jnp.float32)
        dxs_parts.append(jnp.moveaxis(
            jnp.einsum("tbk,dk->tbd", dz_c, fused.kernel,
                       preferred_element_type=jnp.float32),
            0, 1,
        ).astype(xs2.dtype))
        Ws = jnp.split(dW, 4, axis=1)
        Us = jnp.split(dU, 4, axis=1)
        bs = jnp.split(db, 4)
        dp = LSTMParams(*Ws, *Us, *bs)
        dparams.append(jax.tree.map(lambda g, p: g.astype(p.dtype),
                                    dp, params))
    dxs2 = jnp.concatenate(dxs_parts, axis=0)
    return (dparams[0], dparams[1], dxs2,
            dh0.astype(h0.dtype), dc0.astype(c0.dtype))


# ---------------------------------------------------------------------------
# custom-VJP core + public entry
# ---------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _bi_core(params_f, params_b, xs2, h0, c0, mask_tbl, compute_dtype,
             interpret, has_mask):
    fused_f = fuse_params(params_f, compute_dtype=compute_dtype)
    fused_b = fuse_params(params_b, compute_dtype=compute_dtype)
    ys2, hT, cT = _bi_forward(
        fused_f, fused_b, xs2, h0, c0, mask_tbl if has_mask else None,
        interpret=interpret,
    )
    return ys2, hT, cT


def _bi_core_fwd(params_f, params_b, xs2, h0, c0, mask_tbl, compute_dtype,
                 interpret, has_mask):
    fused_f = fuse_params(params_f, compute_dtype=compute_dtype)
    fused_b = fuse_params(params_b, compute_dtype=compute_dtype)
    ys2, hT, cT, cs = _bi_forward(
        fused_f, fused_b, xs2, h0, c0, mask_tbl if has_mask else None,
        save_c=True, interpret=interpret,
    )
    return (ys2, hT, cT), (params_f, params_b, xs2, h0, c0, mask_tbl,
                           ys2, cs)


def _bi_core_bwd(compute_dtype, interpret, has_mask, residuals, cotangents):
    params_f, params_b, xs2, h0, c0, mask_tbl, ys2, cs = residuals
    fused_f = fuse_params(params_f, compute_dtype=compute_dtype)
    fused_b = fuse_params(params_b, compute_dtype=compute_dtype)
    dys2, dhT, dcT = cotangents
    dpf, dpb, dxs2, dh0, dc0 = _bi_backward(
        fused_f, fused_b, params_f, params_b, xs2, h0, c0,
        mask_tbl if has_mask else None, ys2, cs, dys2, dhT, dcT,
        interpret=interpret,
    )
    return dpf, dpb, dxs2, dh0, dc0, jnp.zeros_like(mask_tbl)


_bi_core.defvjp(_bi_core_fwd, _bi_core_bwd)


def pallas_bilstm_scan(
    params_fwd: LSTMParams,
    params_bwd: LSTMParams,
    xs: jax.Array,
    *,
    mask: jax.Array | None = None,
    compute_dtype=None,
    interpret: bool = False,
):
    """Both directions of one bi-LSTM layer in ONE fused kernel pass.

    Equivalent to
    ``pallas_lstm_scan(params_fwd, xs, mask=mask)`` and
    ``pallas_lstm_scan(params_bwd, xs, mask=mask, reverse=True)`` — the
    reverse direction walks right-padded tails first with a frozen zero
    carry, exactly like `lstm_scan(reverse=True)` — but with the two
    serialized chains advanced together (module docstring). Zero initial
    carries (the bi-LSTM layer contract; models/classifier.py never
    seeds carries).

    Returns ``(((hT_f, cT_f), ys_f), ((hT_b, cT_b), ys_b))``.
    """
    B, T, _ = xs.shape
    H = params_fwd.hidden_size
    if params_bwd.hidden_size != H:
        raise ValueError("direction hidden sizes differ")
    hp = _pad_to_lane(H)
    pf = _pad_params_lane(params_fwd, hp) if hp != H else params_fwd
    pb = _pad_params_lane(params_bwd, hp) if hp != H else params_bwd
    # rows B:2B are the time-flipped reverse direction; the flips sit
    # OUTSIDE the custom VJP so autodiff transposes them automatically
    xs2 = jnp.concatenate([xs, jnp.flip(xs, axis=1)], axis=0)
    has_mask = mask is not None
    if has_mask:
        m2 = jnp.concatenate([mask, jnp.flip(mask, axis=1)], axis=0)
        mask_tbl = jnp.broadcast_to(
            jnp.moveaxis(m2, 0, 1).astype(jnp.float32)[:, :, None],
            (T, 2 * B, _LANE),
        )
    else:
        mask_tbl = jnp.zeros((1, 1, _LANE), jnp.float32)  # unused dummy
    h0 = jnp.zeros((2 * B, hp), jnp.float32)
    c0 = jnp.zeros((2 * B, hp), jnp.float32)
    ys2, hT, cT = _bi_core(pf, pb, xs2, h0, c0, mask_tbl, compute_dtype,
                           interpret, has_mask)
    if hp != H:
        ys2, hT, cT = ys2[..., :H], hT[:, :H], cT[:, :H]
    ys_f = ys2[:B]
    ys_b = jnp.flip(ys2[B:], axis=1)
    return ((hT[:B], cT[:B]), ys_f), ((hT[B:], cT[B:]), ys_b)
